"""End-to-end training driver.

Runs REAL steps on the available devices (reduced configs on CPU; the full
mesh path is exercised by dryrun.py). Wires together: config -> data
pipeline -> shard_map train step -> checkpointing -> elastic controller.

  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
      --reduced --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.configs import get_arch
from repro.configs.reduced import reduce_cfg
from repro.data.indexed_dataset import synthetic_token_stream
from repro.launch.mesh import make_smoke_mesh
from repro.models import model as M
from repro.train import optimizer
from repro.train.checkpoint import Checkpointer
from repro.train.elastic import ElasticController
from repro.train.step import make_train_step


def train(arch: str, *, steps: int, batch: int, seq: int, lr: float,
          reduced: bool, ckpt_dir: str | None, ckpt_every: int = 50,
          d_model: int = 128, n_layers: int | None = None,
          log_every: int = 10, seed: int = 0):
    cfg = get_arch(arch)
    if reduced:
        cfg = reduce_cfg(cfg, d_model=d_model, n_layers=n_layers,
                         vocab=2048)
    mesh = make_smoke_mesh()
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    opt = optimizer.init(params)
    residual = jnp.zeros(())
    step_fn, _ = make_train_step(cfg, mesh, lr=lr, donate=False)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    elastic = ElasticController(n_hosts=1)
    stream = synthetic_token_stream(seed, cfg.vocab_size, batch, seq)

    n_params = cfg.param_count()
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params "
          f"({cfg.param_count(active_only=True)/1e6:.1f}M active), "
          f"batch={batch} seq={seq}")
    losses = []
    for step in range(steps):
        toks, labels = next(stream)
        if cfg.embed_input:
            rngl = np.random.default_rng(step)
            inputs = jnp.asarray(
                rngl.normal(0, 1, (batch, seq, cfg.d_model)), jnp.bfloat16)
        else:
            inputs = jnp.asarray(toks)
        pos = jnp.broadcast_to(jnp.arange(seq)[None], (batch, seq)).astype(jnp.int32)
        if cfg.rope == "mrope":
            pos = jnp.broadcast_to(pos[None], (3, batch, seq))
        t0 = time.time()
        params, opt, residual, metrics = step_fn(
            params, opt, residual, inputs, jnp.asarray(labels), pos)
        dt = time.time() - t0
        elastic.heartbeat(0, dt)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0:
            print(f"step {step:4d} loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
        if ckpt and step and step % ckpt_every == 0:
            ckpt.save(step, {"params": params, "opt": opt})
    if ckpt:
        ckpt.save(steps, {"params": params, "opt": opt}, blocking=True)
        ckpt.wait()
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    use_compile_cache()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          lr=args.lr, reduced=args.reduced, ckpt_dir=args.ckpt_dir,
          d_model=args.d_model, n_layers=args.n_layers)


if __name__ == "__main__":
    main()
