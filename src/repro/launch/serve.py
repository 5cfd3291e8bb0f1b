"""Serving driver: batched request loop (prefill + decode) on the local
mesh, with paged-KV bookkeeping and the learned page table.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 8 --new-tokens 24
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.configs import get_arch
from repro.configs.reduced import reduce_cfg
from repro.launch.mesh import make_smoke_mesh
from repro.models import model as M
from repro.serve import step as serve_step
from repro.serve.kvcache import PagedKVCache, learned_page_table


def serve(arch: str, *, reduced: bool, requests: int, prompt_len: int,
          new_tokens: int, d_model: int = 128, seed: int = 0):
    cfg = get_arch(arch)
    if reduced:
        cfg = reduce_cfg(cfg, d_model=d_model, vocab=2048)
    mesh = make_smoke_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prefill, _ = serve_step.make_prefill(cfg, mesh)
    decode, _ = serve_step.make_decode_step(cfg, mesh)

    S_max = prompt_len + new_tokens
    rng = np.random.default_rng(seed)
    B = requests
    if cfg.embed_input:
        prompts = jnp.asarray(
            rng.normal(0, 1, (B, prompt_len, cfg.d_model)), jnp.bfloat16)
    else:
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, prompt_len)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(prompt_len)[None],
                           (B, prompt_len)).astype(jnp.int32)
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, prompt_len))

    caches = M.init_cache(cfg, B, S_max)
    t0 = time.time()
    logits, caches = prefill(params, caches, prompts, pos)
    t_pre = time.time() - t0
    tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]

    # paged-KV bookkeeping (control plane) alongside the decode loop
    page = 16
    pkv = PagedKVCache(n_pages=B * (S_max // page + 1), page_size=page,
                       n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                       n_layers=1)
    for r in range(B):
        for blk in range(S_max // page + 1):
            pkv.allocate(r, blk)

    out = [np.asarray(tok[:, 0])]
    t0 = time.time()
    for i in range(new_tokens):
        dpos = jnp.full((B, 1), prompt_len + i, jnp.int32)
        if cfg.rope == "mrope":
            dpos = jnp.broadcast_to(dpos[None], (3, B, 1))
        if cfg.embed_input:
            tok_in = jnp.asarray(rng.normal(0, 1, (B, 1, cfg.d_model)),
                                 jnp.bfloat16)
        else:
            tok_in = tok
        nxt, caches = decode(params, caches, tok_in, dpos,
                             jnp.asarray(prompt_len + i, jnp.int32))
        tok = nxt[:, None]
        out.append(np.asarray(nxt))
    dt = time.time() - t0
    lookup, keys, pages = learned_page_table(pkv.table)
    q = keys[:: max(len(keys) // 16, 1)]
    assert bool(jnp.all(lookup(q) == pages[jnp.searchsorted(keys, q)]))
    print(f"[serve] {cfg.name}: prefill {t_pre:.2f}s, "
          f"{B * new_tokens / max(dt, 1e-9):.1f} tok/s decode, "
          f"learned page table exact over {len(pkv.table)} pages")
    return np.stack(out, 1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()
    serve(args.arch, reduced=args.reduced, requests=args.requests,
          prompt_len=args.prompt_len, new_tokens=args.new_tokens)


if __name__ == "__main__":
    main()
