"""Serving SLO benchmark: sustained QPS vs p50/p99/p999 latency under
open-loop Poisson arrivals (harness.poisson_arrivals).

Three tenant workload mixes drive the async batched front-end
(``repro.serve.frontend.BatchingFrontend``) over two tenants of different
build sizes on a small CPU mesh:

  * ``point``  — pure point lookups (70/30 tenant split),
  * ``insert`` — insert-heavy churn (80% inserts of 8 keys, 20% finds),
  * ``mixed``  — 50% finds / 30% inserts / 20% deletes.

The driver is open-loop: requests fire at their scheduled Poisson arrival
times whether or not the server keeps up, so queueing delay lands in the
measured latency (completion - *scheduled* arrival) instead of silently
throttling the offered load.  Rows append to BENCH_serve.json keyed by
(sha, suite) like the other trajectories.

Run ``python -m benchmarks.bench_serve`` for the committed sweep, or with
``--smoke`` for a seconds-scale CI pass (no file writes).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _build_tenants(n: int, n_shards: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.distributed import ShardedDynamicIndex

    if len(jax.devices()) < n_shards:
        raise RuntimeError(f"need {n_shards} devices, "
                           f"have {len(jax.devices())}")
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("data",))
    rng = np.random.default_rng(7)
    tenants, fresh = [], []
    for i, (nt, nl) in enumerate(((n, 256), (n // 4, 64))):
        keys = np.sort(rng.choice(
            np.arange(i << 24, (i << 24) + (1 << 23), dtype=np.float64),
            size=nt, replace=False))
        tenants.append(ShardedDynamicIndex.build(
            jnp.asarray(keys), mesh, "data", n_leaves=nl))
        # disjoint insert feed + delete feed per tenant
        ins = np.setdiff1d(np.arange(
            (i << 24) + (1 << 23), (i << 24) + (1 << 23) + (1 << 22),
            dtype=np.float64), keys)
        rng.shuffle(ins)
        dels = keys.copy()
        rng.shuffle(dels)
        fresh.append([ins, 0, dels, 0])
    return tenants, fresh


_MIXES = {
    # (find_frac, insert_frac) — the rest are deletes
    "point": (1.0, 0.0),
    "insert": (0.2, 0.8),
    "mixed": (0.5, 0.3),
}

# Per-workload offered rates (CPU-interpret scale): a host-driven insert
# costs ~3 orders of magnitude more than a batched find lane, so the
# update-heavy mixes are driven at rates that probe saturation instead of
# drowning the queue from the first second.
_RATES = {
    "point": (500.0, 2000.0),
    "insert": (5.0, 25.0),
    "mixed": (10.0, 40.0),
}
_SMOKE_RATES = {"point": (200.0,), "insert": (5.0,), "mixed": (8.0,)}


def _drive(frontend, fresh, workload: str, rate: float, duration: float,
           keys_per_update: int = 8, seed: int = 0) -> dict:
    """One open-loop run: returns the latency/throughput row."""
    from benchmarks import harness

    find_f, ins_f = _MIXES[workload]
    arrivals = harness.poisson_arrivals(rate, duration, seed=seed)
    rng = np.random.default_rng(seed + 1)
    n_tenants = frontend.pack.n_tenants
    kinds = rng.choice(3, size=arrivals.size,
                       p=[find_f, ins_f, 1.0 - find_f - ins_f])
    tenant_of = rng.choice(n_tenants, size=arrivals.size, p=[0.7, 0.3])
    live0 = [t.live_keys() for t in frontend.pack.tenants]

    reqs = []
    clock = frontend.clock
    t0 = clock()
    for dt, kind, tid in zip(arrivals, kinds, tenant_of, strict=True):
        sched = t0 + dt
        lag = sched - clock()
        if lag > 0:
            time.sleep(lag)
        if kind == 0:
            q = rng.choice(live0[tid], 1)
            reqs.append((sched, frontend.submit_find(tid, q)))
        elif kind == 1:
            feed = fresh[tid]
            ks = feed[0][feed[1]:feed[1] + keys_per_update]
            feed[1] += keys_per_update
            reqs.append((sched, frontend.submit_insert(tid, ks)))
        else:
            feed = fresh[tid]
            ks = feed[2][feed[3]:feed[3] + keys_per_update]
            feed[3] += keys_per_update
            reqs.append((sched, frontend.submit_delete(tid, ks)))
    for _, r in reqs:
        r.result(timeout=120.0)
    lats = np.asarray([r.done_at - sched for sched, r in reqs])
    span = max(r.done_at for _, r in reqs) - t0
    q = lambda p: float(np.percentile(lats, p) * 1e3)
    st = frontend.stats
    return {
        "workload": workload,
        "tenants": n_tenants,
        "offered_qps": float(rate),
        "achieved_qps": float(len(reqs) / span),
        "p50_ms": q(50), "p99_ms": q(99), "p999_ms": q(99.9),
        "detail": f"reqs={len(reqs)} batches={st.batches} "
                  f"pad_frac={st.pad_fraction:.2f} "
                  f"qcaps={sorted(st.qcaps)}",
    }


def bench_serve(n: int = 1 << 14, n_shards: int = 2, rates=None,
                duration: float = 1.0) -> list[dict]:
    """The full sweep: every workload mix at its offered rates (``rates``
    overrides with one dict or tuple for all).  Tenants rebuild per run so
    insert churn in one mix doesn't skew the next."""
    from repro.serve.frontend import BatchingFrontend, ServeConfig

    rows = []
    for workload in _MIXES:
        wrates = rates.get(workload, ()) if isinstance(rates, dict) else \
            (rates if rates is not None else _RATES[workload])
        for k, rate in enumerate(wrates):
            tenants, fresh = _build_tenants(n, n_shards)
            fe = BatchingFrontend(
                tenants, config=ServeConfig(latency_budget_s=2e-3))
            with fe:
                fe.warmup((1, fe.config.batch_floor))
                _warm_updates(fe, fresh)
                rows.append(_drive(fe, fresh, workload, rate, duration,
                                   seed=17 * k + 1))
            print(f"[bench_serve] {rows[-1]}", file=sys.stderr)
    return rows


def _warm_updates(fe, fresh, k: int = 8) -> None:
    """Pre-warm the insert/delete/restack jits so one-time compiles don't
    masquerade as serving latency (capacity-class crossings mid-run still
    show up in p999 — that spike is the honest dynamic)."""
    for tid, feed in enumerate(fresh):
        fe.submit_insert(tid, feed[0][feed[1]:feed[1] + k])
        feed[1] += k
        fe.submit_delete(tid, feed[2][feed[3]:feed[3] + k])
        feed[3] += k
        fe.lookup(tid, feed[2][feed[3]:feed[3] + 1])


def quick_rows(n: int = 1 << 14, n_shards: int = 2) -> list[dict]:
    """CSV rows for benchmarks.run's ``serve`` suite (subprocess mesh).
    Each row keeps the full BENCH_serve schema underneath the CSV keys so
    ``run.py --record`` stays compatible with the trajectory guard."""
    from benchmarks import harness

    return [{**r,
             "name": f"serve_{r['workload']}_{int(r['offered_qps'])}qps",
             "us_per_call": r["p50_ms"] * 1e3,
             "derived": f"p99={r['p99_ms']:.2f}ms "
                        f"achieved={r['achieved_qps']:.0f}qps"}
            for r in harness.worker_suite("benchmarks.bench_serve",
                                          "--serve-worker", n_shards, n)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 14)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run, print rows, write nothing")
    ap.add_argument("--serve-worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    if args.serve_worker is not None:
        # forced-device-count subprocess (harness.worker_suite protocol):
        # rows as JSON on the last stdout line.
        if args.smoke:
            rows = bench_serve(args.n, args.serve_worker,
                               rates=_SMOKE_RATES, duration=0.4)
        else:
            rows = bench_serve(args.n, args.serve_worker)
        print(json.dumps(rows))
        return

    from benchmarks import harness

    if args.smoke:
        rows = harness.worker_rows(
            "benchmarks.bench_serve", "--serve-worker", args.shards,
            ["--n", min(args.n, 1 << 13), "--smoke"], timeout=900)
        if not rows:
            raise SystemExit("serve smoke produced no rows")
        print(json.dumps(rows, indent=1))
        return

    rows = harness.worker_suite("benchmarks.bench_serve", "--serve-worker",
                                args.shards, args.n)
    if rows:
        harness.append_bench("BENCH_serve.json", "serve", rows,
                             note=f"n={args.n} shards={args.shards} "
                                  f"open-loop poisson")


if __name__ == "__main__":
    main()
