"""Quickstart: the paper in ~60 lines.

1. Generate the synthetic corpus for eps=0.9 (1,221 datasets, Table 2).
2. Pre-train the whole model pool in one batched program.
3. Index a new "real" dataset by agile model reuse (Algorithm 1).
4. Build RMI-NN-MR and RMRT, run exact lookups through the Pallas kernel.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np
import jax.numpy as jnp

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.api import Index
from repro.core import reuse, rmi, rmrt, synth
from repro.kernels import ops

use_compile_cache()

EPS = 0.9

t0 = time.time()
corpus = synth.generate_pool(EPS)
print(f"synthetic corpus: {corpus.size} datasets "
      f"(paper Table 2: 1,221) [{time.time()-t0:.1f}s]")

t0 = time.time()
pool = reuse.build_pool(corpus, kind="mlp", train_steps=400)
print(f"pool pre-trained in ONE batched program [{time.time()-t0:.1f}s]")

# a new dataset arrives (lognormal keys, e.g. item popularities)
rng = np.random.default_rng(7)
keys = jnp.asarray(np.sort(rng.lognormal(0, 0.7, 300_000) * 1e9))

t0 = time.time()
index = rmi.build_rmi(keys, n_leaves=1024, kind="mlp", pool=pool)
print(f"RMI-NN-MR built: {index.reuse_fraction:.0%} of leaves REUSED "
      f"pre-trained models (no training) [{time.time()-t0:.1f}s]")

tree = rmrt.build_rmrt(keys, leaf_cap=4096, fanout=64, kind="linear",
                       pool=reuse.build_pool(corpus, kind="linear"))
print(f"RMRT built: depth={tree.depth}, {tree.num_nodes} nodes, "
      f"reuse={tree.reuse_fraction:.0%}")

# exact lookups
q = jnp.asarray(rng.choice(np.asarray(keys), 10_000))
pos = rmi.lookup(index, q)
assert bool(jnp.all(keys[pos] == q)), "lookup mismatch"
pos2 = rmrt.lookup(tree, q)
assert bool(jnp.all(keys[pos2] == q))
print("RMI + RMRT lookups: exact ✓")

# the Pallas serving kernel (interpret mode on CPU): in-kernel leaf routing
# over the VMEM-resident tables, search depth clamped to the error window
root_blk, mat, vec = index.packed_tables()
qf = q.astype(jnp.float32)   # tracelint: ok[f32-cast](demo runs at f32 resolution)
kf = index.keys.astype(jnp.float32)  # tracelint: ok[f32-cast](same demo cast)
r = ops.index_lookup(qf, root_blk, mat, vec, kf,
                     n_leaves=index.n_leaves, root_kind=index.root_kind,
                     leaf_kind=index.leaf_kind, iters=index.search_iters)
hit = float(jnp.mean((jnp.abs(keys[jnp.clip(r, 0, index.n-1)] - q)
                      / q < 1e-6).astype(jnp.float32)))
print(f"Pallas fused-lookup kernel: {hit:.1%} within f32 resolution ✓")

# the unified dynamic facade (repro.api.Index): one verb set over the
# single-host and sharded backends — find/insert/delete/gather_range —
# with the same pool driving Algorithm-1 reuse on rebuilds
dyn = Index.build(keys[: 1 << 16], n_leaves=256)
extra = np.asarray(keys[: 1 << 16])[-1] + np.asarray([3.0, 7.0])
dyn.insert(extra)
found, rank = dyn.find(extra, path="jnp")
assert bool(jnp.all(found)), "facade must serve fresh inserts"
lo, hi = dyn.find_range(extra[:1], extra[1:])
(span,) = dyn.gather_range(lo, hi)
assert span.size == 2
print(f"repro.api.Index facade: dynamic insert + find + range exact ✓ "
      f"({dyn.live_count} live keys)")
