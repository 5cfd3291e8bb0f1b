"""Benchmark entry point — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all, quick sizes
  PYTHONPATH=src python -m benchmarks.run --only fig5 --n 1000000
  PYTHONPATH=src python -m benchmarks.run --only sharded --record

Prints ``name,us_per_call,derived`` CSV rows (plus a kernel microbench and
the serving-path row for the Pallas lookup kernel).  ``--record`` appends
the collected rows to the committed BENCH_*.json trajectories keyed by
(git sha, suite) — appended, never regenerated, so per-PR history
accumulates.
"""
from __future__ import annotations

import argparse
import sys
import time


def kernel_rows(n: int = 200_000, q: int = 16_384):
    """Pallas kernels (interpret mode on CPU): correctness-grade timing."""
    import numpy as np
    import jax.numpy as jnp
    import repro  # noqa: F401
    from repro.kernels import ops

    from repro.core import rmi

    rng = np.random.default_rng(0)
    keys = np.sort(rng.lognormal(0, 1, n)).astype(np.float32)
    qs = jnp.asarray(rng.choice(keys, q))
    idx = rmi.build_rmi(jnp.asarray(keys), n_leaves=256, kind="linear")
    root, mat, vec = idx.packed_tables()
    args = (qs, root, mat, vec, jnp.asarray(keys))
    kw = dict(n_leaves=idx.n_leaves, root_kind=idx.root_kind,
              leaf_kind=idx.leaf_kind, iters=idx.search_iters)
    r = ops.index_lookup(*args, **kw)
    r.block_until_ready()
    t0 = time.time()
    ops.index_lookup(*args, **kw).block_until_ready()
    dt = time.time() - t0
    h = ops.histogram(jnp.asarray(keys), 64, float(keys[0]), float(keys[-1]))
    h.block_until_ready()
    t0 = time.time()
    ops.histogram(jnp.asarray(keys), 64, float(keys[0]),
                  float(keys[-1])).block_until_ready()
    dth = time.time() - t0
    return [
        {"name": "kernel_lookup_fused", "us_per_call": dt / q * 1e6,
         "derived": f"{dt/q*1e9:.0f}ns/q interpret-mode n={n}"},
        {"name": "kernel_histogram", "us_per_call": dth * 1e6,
         "derived": f"{dth*1e3:.1f}ms for {n} keys m=64 interpret-mode"},
    ]


def rmrt_rows(n: int = 200_000, q: int = 16_384):
    """RMRT serving paths: fused Pallas kernel (in-kernel fixed-depth
    descent + clamped search, interpret mode on CPU) vs the clamped jnp
    masked-descent path."""
    import time as _time
    import numpy as np
    import jax
    import jax.numpy as jnp
    import repro  # noqa: F401
    from repro.core import rmrt

    rng = np.random.default_rng(0)
    keys = np.unique(np.sort(rng.lognormal(0, 1, n))
                     .astype(np.float32)).astype(np.float64)
    qs = jnp.asarray(rng.choice(keys, q))
    idx = rmrt.build_rmrt(jnp.asarray(keys), leaf_cap=4096, fanout=64,
                          kind="linear")
    rows = []
    for path, kw in (("kernel_rmrt_fused", dict(use_kernel=True)),
                     ("rmrt_jnp_clamped", dict())):
        jax.block_until_ready(rmrt.lookup(idx, qs, **kw))
        t0 = _time.time()
        jax.block_until_ready(rmrt.lookup(idx, qs, **kw))
        dt = _time.time() - t0
        rows.append({"name": path, "us_per_call": dt / q * 1e6,
                     "derived": f"{dt/q*1e9:.0f}ns/q n={n} "
                                f"depth={idx.depth} "
                                f"iters={idx.search_iters}"})
    return rows


SUITES = ["table2", "fig5", "fig6", "table3", "fig7", "updates", "sharded",
          "restack", "recover", "drift", "serve", "kernels", "rmrt"]

# --record routes each suite's rows into the matching committed trajectory
# (appended keyed by git sha + suite — never regenerated; see
# harness.append_bench).
_RECORD_TARGETS = {
    "fig7": "BENCH_updates.json", "updates": "BENCH_updates.json",
    "sharded": "BENCH_updates.json", "restack": "BENCH_updates.json",
    "recover": "BENCH_updates.json",
    "drift": "BENCH_updates.json",
    "serve": "BENCH_serve.json",
    "kernels": "BENCH_lookup.json", "rmrt": "BENCH_lookup.json",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list from {SUITES}")
    ap.add_argument("--n", type=int, default=None,
                    help="dataset size override (default 200k)")
    ap.add_argument("--record", action="store_true",
                    help="append the collected rows to the committed "
                         "BENCH_*.json trajectories (keyed by git sha + "
                         "suite)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    only = set(args.only.split(",")) if args.only else set(SUITES)

    by_suite: dict[str, list] = {}
    t_start = time.time()
    if "table2" in only:
        from . import table2_synth
        by_suite["table2"] = table2_synth.run()
    if "fig5" in only:
        from . import fig5_real
        by_suite["fig5"] = fig5_real.run(**({"n": args.n} if args.n else {}))
    if "fig6" in only:
        from . import fig6_skew
        by_suite["fig6"] = fig6_skew.run(**({"n": args.n} if args.n else {}))
    if "table3" in only:
        from . import table3_eps
        by_suite["table3"] = table3_eps.run(
            **({"n": args.n} if args.n else {}))
    if "fig7" in only:
        from . import fig7_updates
        by_suite["fig7"] = fig7_updates.run(
            **({"n": args.n} if args.n else {}))
    if "updates" in only:
        from . import bench_updates
        by_suite["updates"] = bench_updates.quick_rows(
            **({"n": args.n} if args.n else {}))
    if "sharded" in only:
        from . import bench_updates
        by_suite["sharded"] = bench_updates.sharded_quick_rows(
            **({"n": args.n} if args.n else {}))
    if "restack" in only:
        from . import bench_updates
        by_suite["restack"] = bench_updates.restack_quick_rows(
            **({"n": args.n} if args.n else {}))
    if "recover" in only:
        from . import bench_updates
        by_suite["recover"] = bench_updates.recover_quick_rows(
            **({"n": args.n} if args.n else {}))
    if "drift" in only:
        from . import bench_updates
        by_suite["drift"] = bench_updates.drift_quick_rows(
            **({"n": args.n} if args.n else {}))
    if "serve" in only:
        from . import bench_serve
        by_suite["serve"] = bench_serve.quick_rows(
            **({"n": args.n} if args.n else {}))
    if "kernels" in only:
        by_suite["kernels"] = kernel_rows(
            **({"n": args.n} if args.n else {}))
    if "rmrt" in only:
        by_suite["rmrt"] = rmrt_rows(**({"n": args.n} if args.n else {}))

    rows = [r for suite in SUITES for r in by_suite.get(suite, [])]
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.3f},\"{r['derived']}\"")
    print(f"# total {time.time()-t_start:.0f}s, {len(rows)} rows",
          file=sys.stderr)

    if args.record:
        from pathlib import Path
        from . import harness
        root = Path(__file__).resolve().parent.parent
        for suite, suite_rows in by_suite.items():
            target = _RECORD_TARGETS.get(suite)
            if target and suite_rows:
                harness.append_bench(root / target, f"run:{suite}",
                                     suite_rows)
            elif not target:
                print(f"# --record: suite {suite} has no trajectory "
                      f"target, skipped", file=sys.stderr)


if __name__ == "__main__":
    main()
