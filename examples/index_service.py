"""Multi-tenant learned-index service on the batched serving front-end.

Two dynamic sharded indexes of different build sizes serve as tenants of
one ``repro.serve.frontend.BatchingFrontend`` over a 4-device simulated
mesh: requests coalesce up to a 2ms latency budget, pad to pow2 capacity
classes (zero hot-path retraces after warmup), and every tenant answers in
one stacked shard_map dispatch.  A short open-loop Poisson drive reports
the serving SLO — sustained QPS plus p50/p99 latency — alongside the
indexed data-pipeline demo (agile reuse on every new shard).

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python examples/index_service.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import time

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.api import Index
from repro.data.indexed_dataset import IndexedDataset
from repro.serve.frontend import BatchingFrontend, Request, ServeConfig

use_compile_cache()

mesh = jax.make_mesh((4,), ("data",))
rng = np.random.default_rng(3)

# --- multi-tenant serving front-end ----------------------------------------
# Tenants build through the unified facade (repro.api.Index): mesh= selects
# the sharded backend, and .backend hands the front-end its tenant object.
tenants, live = [], []
for i, (n, n_leaves) in enumerate(((1 << 16, 256), (1 << 14, 64))):
    keys = np.unique(np.sort(rng.lognormal(0, 1, n) * 1e6 + i * 1e12))
    tenants.append(Index.build(jnp.asarray(keys), mesh=mesh,
                               n_leaves=n_leaves).backend)
    live.append(keys)

with BatchingFrontend(tenants,
                      config=ServeConfig(latency_budget_s=2e-3)) as fe:
    fe.warmup((1, 128))

    # one insert riding the same queue as the finds (applies before the
    # coalesced batch's finds dispatch) — submitted as a typed Request,
    # the primitive every submit_* convenience wrapper funnels through
    extra = np.asarray([live[1][-1] + 7.0, live[1][-1] + 9.0])
    fe.submit(Request(1, "insert", extra)).result(timeout=300.0)
    found, rank = fe.lookup(1, extra)
    assert found.all(), "inserted keys must be visible to the next find"

    # open-loop Poisson drive: 300 point lookups/s for 2s across tenants
    rate, duration = 300.0, 2.0
    gaps = rng.exponential(1.0 / rate, size=int(rate * duration * 2))
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals < duration]
    reqs, t0 = [], fe.clock()
    for dt in arrivals:
        lag = (t0 + dt) - fe.clock()
        if lag > 0:
            time.sleep(lag)
        tid = int(rng.random() < 0.3)
        q = rng.choice(live[tid], 1)
        reqs.append((t0 + dt, fe.submit_find(tid, q)))
    for _, r in reqs:
        r.result(timeout=60.0)
    lats = np.asarray([r.done_at - sched for sched, r in reqs]) * 1e3
    span = max(r.done_at for _, r in reqs) - t0
    st = fe.stats
    print(f"serving front-end: {len(reqs)} requests, "
          f"{len(reqs) / span:.0f} QPS sustained (offered {rate:.0f}), "
          f"p50={np.percentile(lats, 50):.1f}ms "
          f"p99={np.percentile(lats, 99):.1f}ms")
    print(f"  {st.batches} stacked dispatches over "
          f"{fe.pack.n_tenants} tenants x 4 shards, capacity classes "
          f"{sorted(st.qcaps)}, {st.padded_slots} pad lanes for "
          f"{st.queries} keys")

# --- indexed data pipeline --------------------------------------------------
ds = IndexedDataset.create(eps=0.9, kind="linear", n_leaves=128)
for shard in range(4):
    sk = np.sort(rng.lognormal(0, 0.6, 100_000)) * 1e6 + shard * 1e12
    info = ds.add_shard(sk)
    print(f"shard {shard}: indexed with {info.reuse_fraction:.0%} leaf reuse")
sample = rng.choice(ds.shards[2].keys, 1000)
sid, off = ds.locate(sample)
assert (sid == 2).all()
assert np.allclose(ds.shards[2].keys[off], sample)
print(f"pipeline locate(): exact; mean reuse {ds.mean_reuse:.0%}")
