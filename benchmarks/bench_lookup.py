"""Lookup-path microbenchmark — the serving perf trajectory.

Times ns/query for the paper's §5 roster across key counts and lookup
paths, and writes ``BENCH_lookup.json`` (committed) so subsequent PRs can
track the hot path:

  jnp-full-depth      the pre-PR serving path: XLA bounded search at
                      ceil(log2 n) + 1 iterations (``clamp_iters=False``)
  jnp-window-clamped  same path with the §4 error-window-clamped static
                      depth (RMIIndex.search_iters) — the "after" row
  pallas-interpret    the fused Pallas kernel (in-kernel leaf routing +
                      tiled keys) under the interpreter; correctness-grade
                      timing only — on CPU containers this measures the
                      interpreter, not the kernel, but pins the trajectory
                      for TPU runs
  native              variants without a depth toggle (BTree; PGM/RS are
                      always eps-clamped now)

  PYTHONPATH=src python -m benchmarks.bench_lookup [--sizes 65536 262144]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache
from repro.core import btree, pgm, radix_spline, rmi, rmrt

from . import harness

Q = 16_384
REPEATS = 3


def _time(fn, queries) -> float:
    import jax
    jax.block_until_ready(fn(queries))          # compile / warm
    times = []
    for _ in range(REPEATS):
        t0 = time.time()
        jax.block_until_ready(fn(queries))
        times.append(time.time() - t0)
    return float(np.median(times)) / queries.shape[0] * 1e9


def bench(sizes: list[int], eps: float = 0.9) -> list[dict]:
    import jax.numpy as jnp
    from repro.kernels.lookup import full_iters

    lin_pool, mlp_pool, *_ = harness.pools(eps)
    rows: list[dict] = []
    rng = np.random.default_rng(7)
    for n in sizes:
        keys = np.sort(rng.lognormal(0, 0.7, n) * 1e6)
        # tracelint: ok[f32-cast](f32-exact key synthesis: the roundtrip dedup is the point)
        keys = np.unique(keys.astype(np.float32)).astype(np.float64)
        kj = jnp.asarray(keys)
        q = jnp.asarray(rng.choice(keys, Q))

        builds = {
            "BTree": lambda kj=kj: btree.build_btree(kj, fanout=16),
            "RMI": lambda kj=kj: rmi.build_rmi(kj, 1024, kind="linear"),
            "RMI-MR": lambda kj=kj: rmi.build_rmi(kj, 1024, kind="linear",
                                            pool=lin_pool),
            "RMI-NN": lambda kj=kj: rmi.build_rmi(kj, 1024, kind="mlp",
                                            train_steps=150),
            "RMI-NN-MR": lambda kj=kj: rmi.build_rmi(kj, 1024, kind="mlp",
                                               pool=mlp_pool,
                                               train_steps=150),
            "PGM": lambda kj=kj: pgm.build_pgm(kj, eps=64),
            "RS": lambda kj=kj: radix_spline.build_rs(kj, eps=32),
            "RMRT": lambda kj=kj: rmrt.build_rmrt(kj, leaf_cap=4096, fanout=64,
                                            kind="linear", pool=lin_pool),
        }
        for name, build in builds.items():
            idx = build()
            paths: dict[str, tuple] = {}
            if name.startswith("RMI"):
                paths = {
                    "jnp-full-depth": (
                        lambda qq, i=idx: rmi.lookup(i, qq,
                                                     clamp_iters=False),
                        full_iters(idx.n)),
                    "jnp-window-clamped": (
                        lambda qq, i=idx: rmi.lookup(i, qq),
                        idx.search_iters),
                    "pallas-interpret": (
                        lambda qq, i=idx: rmi.lookup(i, qq, use_kernel=True),
                        idx.search_iters),
                }
            elif name == "RMRT":
                paths = {
                    "jnp-full-depth": (
                        lambda qq, i=idx: rmrt.lookup(i, qq,
                                                      clamp_iters=False),
                        full_iters(idx.n)),
                    "jnp-window-clamped": (
                        lambda qq, i=idx: rmrt.lookup(i, qq),
                        idx.search_iters),
                    "pallas-interpret": (
                        lambda qq, i=idx: rmrt.lookup(i, qq,
                                                      use_kernel=True),
                        idx.search_iters),
                }
            else:
                look = {"BTree": btree.lookup, "PGM": pgm.lookup,
                        "RS": radix_spline.lookup}[name]
                paths = {"native": (lambda qq, i=idx, lk=look: lk(i, qq),
                                    None)}
            for path, (fn, iters) in paths.items():
                ns = _time(fn, q)
                assert harness.verify(kj, q, fn(q)), (name, path)
                rows.append({"variant": name, "n_keys": int(kj.shape[0]),
                             "path": path, "ns_per_query": round(ns, 1),
                             "iters": iters})
                print(f"{name:10s} n={int(kj.shape[0]):>8d} {path:20s} "
                      f"{ns:10.0f} ns/q  iters={iters}")
    return rows


def _time_range(fn, q_lo, q_hi) -> float:
    import jax
    jax.block_until_ready(fn(q_lo, q_hi))       # compile / warm
    times = []
    for _ in range(REPEATS):
        t0 = time.time()
        jax.block_until_ready(fn(q_lo, q_hi))
        times.append(time.time() - t0)
    return float(np.median(times)) / q_lo.shape[0] * 1e9


def bench_range(sizes: list[int], eps: float = 0.9) -> list[dict]:
    """YCSB-style point/range/mixed mixes over the dynamic two-tier index.

    Per size: a churned DynamicRMI (batched inserts + tombstones so the
    delta tier and live-rank prefix sums are exercised) timed under three
    mixes —

      point   100% point lookups (YCSB-C)
      range   100% range lookups (YCSB-E's scan op)
      mixed   95% range / 5% point (YCSB-E's default mix)

    each on both lookup paths (jnp / pallas-interpret).  ns_per_query is
    per *operation* (a range op routes two endpoints but counts once).
    """
    import jax.numpy as jnp
    from repro.core.updates import DynamicRMI

    rows: list[dict] = []
    rng = np.random.default_rng(11)
    for n in sizes:
        keys = np.sort(rng.lognormal(0, 0.7, n) * 1e6)
        # tracelint: ok[f32-cast](f32-exact key synthesis: the roundtrip dedup is the point)
        keys = np.unique(keys.astype(np.float32)).astype(np.float64)
        dyn = DynamicRMI.build(jnp.asarray(keys), n_leaves=1024,
                               kind="linear")
        extra = np.unique((rng.lognormal(0, 0.7, n // 8) * 1e6)
                          .astype(np.float32)).astype(np.float64)
        extra = np.setdiff1d(extra, keys)
        dyn.insert_batch(jnp.asarray(extra))
        dyn.delete_batch(jnp.asarray(rng.choice(keys, n // 16,
                                                replace=False)))
        live = dyn.live_keys()
        qp = jnp.asarray(rng.choice(live, Q))
        q_lo = np.asarray(rng.choice(live, Q))
        # tracelint: ok[f32-cast](f32-exact range-hi synthesis, same roundtrip)
        q_hi = (q_lo * (1.0 + rng.uniform(0.0, 0.01, Q))).astype(
            np.float32).astype(np.float64)
        q_lo, q_hi = jnp.asarray(q_lo), jnp.asarray(q_hi)
        # verify once per size against the flat live-array oracle
        lf = np.asarray(live)
        el = np.searchsorted(lf, np.asarray(q_lo), side="left")
        eh = np.maximum(np.searchsorted(lf, np.asarray(q_hi), side="right"),
                        el)
        for use_kernel, path in ((False, "jnp-window-clamped"),
                                 (True, "pallas-interpret")):
            rl, rh = dyn.find_range(q_lo, q_hi, use_kernel=use_kernel)
            assert (np.array_equal(np.asarray(rl), el)
                    and np.array_equal(np.asarray(rh), eh)), path
            t_point = _time(
                lambda qq, uk=use_kernel, d=dyn: d.find(qq, use_kernel=uk)[1],
                qp)
            t_range = _time_range(
                lambda a, b, uk=use_kernel, d=dyn: d.find_range(
                    a, b, use_kernel=uk), q_lo, q_hi)
            for mix, ns in (("point", t_point), ("range", t_range),
                            ("mixed", 0.95 * t_range + 0.05 * t_point)):
                rows.append({"variant": "DynamicRMI", "mix": mix,
                             "n_keys": int(live.shape[0]), "path": path,
                             "ns_per_query": round(ns, 1)})
                print(f"DynamicRMI n={int(live.shape[0]):>8d} "
                      f"{mix:6s} {path:20s} {ns:10.0f} ns/op")
    return rows


def bench_distributed(n: int, n_shards: int) -> list[dict]:
    """Sharded-service rows on an ``n_shards``-device CPU mesh (kernel vs
    jnp per-shard path).  Must run in a process whose XLA host-device count
    is already >= n_shards (see --distributed-worker below)."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed

    rng = np.random.default_rng(7)
    keys = np.sort(rng.lognormal(0, 0.7, n) * 1e6)
    # tracelint: ok[f32-cast](f32-exact key synthesis: the roundtrip dedup is the point)
    keys = np.unique(keys.astype(np.float32)).astype(np.float64)
    q = jnp.asarray(rng.choice(keys, Q))
    mesh = jax.make_mesh((n_shards,), ("data",))
    idx = distributed.build_sharded(jnp.asarray(keys), mesh, axis="data",
                                    n_leaves=256)
    rows = []
    for path, use_kernel in (("shard-jnp-clamped", False),
                             ("shard-pallas-interpret", True)):
        fn = distributed.make_lookup_fn(idx, use_kernel=use_kernel)
        ns = _time(fn, q)
        rows.append({"variant": f"Distributed-{n_shards}shard",
                     "n_keys": int(keys.shape[0]), "path": path,
                     "ns_per_query": round(ns, 1),
                     "iters": idx.search_iters})
        print(f"Distributed-{n_shards}shard n={keys.shape[0]:>8d} "
              f"{path:20s} {ns:10.0f} ns/q  iters={idx.search_iters}")
    return rows


def _distributed_rows(n_shards: int, n: int) -> list[dict]:
    """Collect the distributed rows from a forced-device-count subprocess
    (harness.worker_rows — the host-device count locks at first jax
    init)."""
    return harness.worker_rows("benchmarks.bench_lookup",
                               "--distributed-worker", n_shards,
                               ["--sizes", n], timeout=1800)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[1 << 16, 1 << 18])
    ap.add_argument("--shards", type=int, default=4,
                    help="mesh width for the distributed rows (0 disables)")
    ap.add_argument("--distributed-worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: emit rows as JSON
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_lookup.json"))
    args = ap.parse_args()
    use_compile_cache()
    if args.distributed_worker:
        rows = bench_distributed(max(args.sizes), args.distributed_worker)
        print(json.dumps(rows))
        return
    rows = bench(args.sizes)
    if args.shards:
        rows += _distributed_rows(args.shards, max(args.sizes))
    # Per-PR trajectory: append keyed by (git sha, suite) — the committed
    # baseline meta/rows from the seeding run stay untouched so every PR's
    # numbers remain comparable against them.
    harness.append_bench(
        args.out, "lookup", rows,
        note="pallas-interpret rows time the Pallas interpreter "
             "(correctness-grade); jnp rows are the XLA serving path. "
             "Distributed rows run the sharded service on a "
             "forced-host-device CPU mesh.")
    harness.append_bench(
        args.out, "lookup-range", bench_range(args.sizes),
        note="YCSB-style point/range/mixed mixes over the churned dynamic "
             "two-tier index; ns_per_query is per operation (a range op "
             "routes both endpoints in one fused pass but counts once).")


if __name__ == "__main__":
    main()
