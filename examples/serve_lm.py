"""Serve a small model with batched requests: prefill + decode loop with
the paged KV cache and the learned page table (deliverable (b)).

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.configs.reduced import reduced
from repro.launch.mesh import make_smoke_mesh
from repro.models import model as M
from repro.serve import step as serve_step
from repro.serve.kvcache import PagedKVCache, learned_page_table

use_compile_cache()

ARCH = "qwen3-4b"   # reduced variant: qk_norm + GQA family
B, S_PRE, N_NEW, S_MAX = 4, 48, 16, 128

cfg = reduced(ARCH)
mesh = make_smoke_mesh()
params = M.init_params(cfg, jax.random.PRNGKey(0))

prefill, _ = serve_step.make_prefill(cfg, mesh)
decode, _ = serve_step.make_decode_step(cfg, mesh)

rng = np.random.default_rng(0)
prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S_PRE)), jnp.int32)
pos = jnp.broadcast_to(jnp.arange(S_PRE)[None], (B, S_PRE)).astype(jnp.int32)

caches = M.init_cache(cfg, B, S_MAX)
t0 = time.time()
logits, caches = prefill(params, caches, prompts, pos)
print(f"prefill B={B} S={S_PRE}: {time.time()-t0:.2f}s "
      f"logits {logits.shape}")

tok = jnp.argmax(logits[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
out = [np.asarray(tok[:, 0])]
t0 = time.time()
for i in range(N_NEW):
    dpos = jnp.full((B, 1), S_PRE + i, jnp.int32)
    nxt, caches = decode(params, caches, tok, dpos,
                         jnp.asarray(S_PRE + i, jnp.int32))
    tok = nxt[:, None]
    out.append(np.asarray(nxt))
dt = time.time() - t0
gen = np.stack(out, 1)
print(f"decoded {N_NEW} tokens x {B} reqs in {dt:.2f}s "
      f"({B*N_NEW/dt:.1f} tok/s on 1 CPU core)")
print("sequences:\n", gen)

# paged KV bookkeeping with the learned page table
pkv = PagedKVCache(n_pages=64, page_size=16, n_kv_heads=cfg.n_kv_heads,
                   head_dim=cfg.head_dim, n_layers=cfg.n_layers)
for req in range(B):
    for blk in range((S_PRE + N_NEW) // 16 + 1):
        pkv.allocate(req, blk)
lookup, keys, pages = learned_page_table(pkv.table)
q = keys[:: max(len(keys) // 8, 1)]
got = lookup(q)
want = pages[jnp.searchsorted(keys, q)]
assert bool(jnp.all(got == want))
print(f"learned page table: {len(pkv.table)} mappings, lookups exact ✓")
