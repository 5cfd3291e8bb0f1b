"""Update-path microbenchmark — the dynamic-index perf trajectory.

Times ns/op for the §4 update subsystem and writes ``BENCH_updates.json``
(committed) so subsequent PRs can track the update hot path the way
``BENCH_lookup.json`` tracks lookups:

  insert        the paper's fig7 bulk-insertion workload (insert ratio 0.5,
                one batch, warm jit caches)
                  host-loop-seed   the seed implementation: per-leaf host
                                   Python buffers (np.sort/np.concatenate
                                   per touched leaf, one O(n) rebuild scan
                                   per over-budget leaf)
                  two-tier         the device-resident delta tier: one
                                   vectorized route-sort-merge per batch,
                                   one batched merge + refit per rebuild
  find-churn    point queries after >=10% inserts + tombstoned deletes
                  host-loop-seed   per-query Python scan over leaf buffers
                  two-tier-jnp     the fused jnp oracle path (XLA)
                  two-tier-pallas  the fused Pallas kernel (interpret mode
                                   on CPU: correctness-grade timing only)
  rebuild       an insert storm sized to exhaust Lemma 4.1 budgets —
                ns per *merged key* including the pool-reuse refits
  sharded       ShardedDynamicIndex insert/delete/find churn on a forced
                n-host-device CPU mesh vs single-device DynamicRMI at equal
                total keys (per-shard cost trajectory; runs in a worker
                subprocess because the device count locks at first jax init)
  restack       hot-shard maintenance sweep over 2/4/8-shard submeshes of
                one forced 8-device mesh: restack-churn rows (one shard
                takes every insert, rebalancing off) must stay ~flat in
                shard count — the per-shard slice cache makes per-batch
                restack work O(touched shards); migrate-skew rows count
                incremental (delta-riding) vs full-rebuild migrations
  recover       durability sweep (core.persist) on a forced 4-device mesh:
                snapshot cost vs index size, same-width restore latency,
                and restore-resharded 4->2 latency (the elastic-restart
                path) — full_rebuilds in the detail must stay 0
  drift         drifting-ingest trajectory through the ``repro.api.Index``
                facade: per-key insert+maintenance latency over a
                stationary -> shifted-lognormal -> zipf ingest, swap mode
                (online KS monitor + bound-checked pool hot-swaps, see
                core.drift) vs refit-only — swap-mode p99 must stay ~flat
                while refit-only spikes on the merge storms the shifted
                phases trigger

Rows *append* to ``BENCH_updates.json`` under ``trajectory``, keyed by
(git sha, suite) — the committed baseline rows stay untouched.

  PYTHONPATH=src python -m benchmarks.bench_updates [--n 65536] [--shards 4]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

import repro  # noqa: F401
from repro.compile_cache import use_compile_cache

Q = 8_192
REPEATS = 3


def _median(fn) -> float:
    times = []
    for _ in range(REPEATS):
        t0 = time.time()
        fn()
        times.append(time.time() - t0)
    return float(np.median(times))


def _keys(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    k = np.sort(rng.lognormal(0, 0.7, n) * 1e6)
    # tracelint: ok[f32-cast](f32-exact key synthesis: the roundtrip dedup is the point)
    return np.unique(k.astype(np.float32)).astype(np.float64)  # f32-exact


def bench(n: int = 1 << 17, eps: float = 0.9, n_leaves: int = 8192,
          with_pool: bool = True) -> list[dict]:
    import jax
    import jax.numpy as jnp
    from repro.core import reuse, synth
    from repro.core.updates import DynamicRMI, HostBufferDynamicRMI

    base = _keys(n)
    extra = _keys(2 * n, seed=9)
    ins = np.setdiff1d(extra, base)
    rng = np.random.default_rng(4)
    pool = reuse.build_pool(synth.generate_pool(eps, limit=300),
                            kind="linear") if with_pool else None
    rows: list[dict] = []

    def _row(op, impl, ns, detail):
        rows.append({"op": op, "impl": impl, "n_keys": int(base.size),
                     "ns_per_op": round(ns, 1), "detail": detail})
        print(f"{op:12s} {impl:16s} {ns:12.0f} ns/op  {detail}")

    # ---- batched insert: the paper's fig7 bulk-insertion workload (ratio
    # 0.5 of the base, one insert_batch call).  The seed rebuilds each
    # over-budget leaf with its own O(n) scan; the two-tier path batches the
    # merge + pool-policy refits.  Fresh structure per repeat (builds
    # untimed); one throwaway warm pass primes the jit caches. -------------
    bulk = ins[:n // 2]

    def _time_inserts(cls):
        times, rebuilds = [], 0
        w = cls.build(jnp.asarray(base), pool=pool, eps=eps,
                      n_leaves=n_leaves, kind="linear")
        w.insert_batch(bulk)                # warm (jit trace + capacity)
        for _ in range(REPEATS):
            d = cls.build(jnp.asarray(base), pool=pool, eps=eps,
                          n_leaves=n_leaves, kind="linear")
            t0 = time.time()
            d.insert_batch(bulk)
            times.append(time.time() - t0)
            rebuilds = d.rebuilds
        return float(np.median(times)) / bulk.size * 1e9, rebuilds

    ns_legacy, rb = _time_inserts(HostBufferDynamicRMI)
    _row("insert", "host-loop-seed", ns_legacy,
         f"bulk={bulk.size} leaves={n_leaves} rebuilds={rb}")
    ns_two, rb = _time_inserts(DynamicRMI)
    _row("insert", "two-tier", ns_two,
         f"bulk={bulk.size} leaves={n_leaves} rebuilds={rb} "
         f"speedup={ns_legacy / max(ns_two, 1e-9):.1f}x")

    # ---- find under churn (>=10% inserted, some tombstoned) --------------
    churn = ins[:max(n // 8, 1024)]         # ~12.5% of base
    dels = rng.choice(churn, churn.size // 10, replace=False)

    legacy = HostBufferDynamicRMI.build(jnp.asarray(base), pool=pool,
                                        eps=eps, n_leaves=n_leaves,
                                        kind="linear")
    legacy.insert_batch(churn)
    for k in dels[:64]:                     # seed delete is per-key only
        legacy.delete(k)
    dyn = DynamicRMI.build(jnp.asarray(base), pool=pool, eps=eps,
                           n_leaves=n_leaves, kind="linear")
    dyn.insert_batch(churn)
    dyn.delete_batch(dels)

    q = jnp.asarray(np.concatenate(
        [rng.choice(base, Q // 2), rng.choice(churn, Q - Q // 2)]))
    jax.block_until_ready(legacy.find(q))
    dt = _median(lambda: jax.block_until_ready(legacy.find(q)))
    _row("find-churn", "host-loop-seed", dt / Q * 1e9,
         f"Q={Q} churn={churn.size} tombstones=64")

    jax.block_until_ready(dyn.find(q, use_kernel=False))
    dt = _median(lambda: jax.block_until_ready(dyn.find(q,
                                                        use_kernel=False)))
    _row("find-churn", "two-tier-jnp", dt / Q * 1e9,
         f"Q={Q} churn={churn.size} tombstones={dels.size} "
         f"iters={dyn.index.search_iters}")

    jax.block_until_ready(dyn.find(q, use_kernel=True))
    dt = _median(lambda: jax.block_until_ready(dyn.find(q, use_kernel=True)))
    _row("find-churn", "two-tier-pallas", dt / Q * 1e9,
         f"Q={Q} interpret-mode (correctness-grade)")

    # ---- rebuild (budget-exhausting storm; merges + forced Algorithm-1
    # pool-reuse refits, reuse_on_rebuild=True) ----------------------------
    storm = ins[:max(n // 4, 2048)]
    for _warm in (True, False):         # first pass primes the jit caches
        dyn = DynamicRMI.build(jnp.asarray(base), pool=pool, eps=eps,
                               n_leaves=n_leaves, kind="linear",
                               reuse_on_rebuild=True if with_pool else None)
        t0 = time.time()
        dyn.insert_batch(storm)
        dt = time.time() - t0
    _row("rebuild", "two-tier", dt / storm.size * 1e9,
         f"storm={storm.size} rebuilds={dyn.rebuilds} "
         f"reuse={float(np.mean(np.asarray(dyn.index.reused_mask))):.2f} "
         f"live_keys={dyn.base_n + dyn.delta_live}")
    return rows


def bench_sharded(n: int = 1 << 16, n_shards: int = 4,
                  eps: float = 0.7) -> list[dict]:
    """Sharded insert/delete/find churn vs a single-device ``DynamicRMI``
    at equal total keys — the per-shard cost trajectory.  Must run in a
    process whose XLA host-device count is already >= n_shards (see
    --sharded-worker below); the single-device rows are measured in the
    same process so the comparison shares one XLA config."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed
    from repro.core.updates import DynamicRMI

    base = _keys(n)
    extra = _keys(2 * n, seed=9)
    ins = np.setdiff1d(extra, base)
    rng = np.random.default_rng(4)
    n_leaves = max(n // 64, 16)
    mesh = jax.make_mesh((n_shards,), ("data",))
    rows: list[dict] = []

    def _row(op, impl, ns, detail):
        rows.append({"op": op, "impl": impl, "n_keys": int(base.size),
                     "ns_per_op": round(ns, 1), "detail": detail})
        print(f"{op:12s} {impl:24s} {ns:12.0f} ns/op  {detail}")

    bulk = ins[:n // 2]
    churn = ins[n // 2:n // 2 + max(n // 8, 1024)]
    dels = rng.choice(churn, churn.size // 10, replace=False)
    q = jnp.asarray(np.concatenate(
        [rng.choice(base, Q // 2), rng.choice(churn, Q - Q // 2)]))

    # ---- single-device reference at equal total keys ---------------------
    def _build_single():
        return DynamicRMI.build(jnp.asarray(base), eps=eps,
                                n_leaves=n_leaves, kind="linear")

    def _time_mutation(build_fn, op):
        """Median over REPEATS of op(fresh structure) — builds untimed, one
        throwaway warm pass primes the jit caches (the protocol of
        :func:`bench`'s _time_inserts)."""
        op(build_fn())                      # warm
        times, last = [], None
        for _ in range(REPEATS):
            d = build_fn()
            t0 = time.time()
            op(d)
            times.append(time.time() - t0)
            last = d
        return float(np.median(times)), last

    dt, _ = _time_mutation(_build_single, lambda d: d.insert_batch(bulk))
    _row("insert", "single-device", dt / bulk.size * 1e9,
         f"bulk={bulk.size} leaves={n_leaves}")
    d = _build_single()
    d.insert_batch(churn)
    d.delete_batch(dels)
    jax.block_until_ready(d.find(q, use_kernel=False))
    dt = _median(lambda: jax.block_until_ready(d.find(q, use_kernel=False)))
    _row("find-churn", "single-device-jnp", dt / Q * 1e9,
         f"Q={Q} churn={churn.size} tombstones={dels.size}")

    # ---- sharded ---------------------------------------------------------
    def _build_sharded():
        return distributed.ShardedDynamicIndex.build(
            jnp.asarray(base), mesh, n_leaves=n_leaves, eps=eps)

    dt, s2 = _time_mutation(_build_sharded, lambda s: s.insert_batch(bulk))
    per_shard = bulk.size / n_shards
    _row("insert", f"sharded-{n_shards}", dt / bulk.size * 1e9,
         f"bulk={bulk.size} per_shard={per_shard:.0f} "
         f"rebalances={s2.rebalances}")

    def _churned():
        s = _build_sharded()
        s.insert_batch(churn)
        return s

    dt, s = _time_mutation(_churned, lambda s: s.delete_batch(dels))
    _row("delete", f"sharded-{n_shards}", dt / max(dels.size, 1) * 1e9,
         f"dels={dels.size} churn={churn.size}")
    for impl, uk in ((f"sharded-{n_shards}-jnp", False),
                     (f"sharded-{n_shards}-pallas", True)):
        jax.block_until_ready(s.find(q, use_kernel=uk))
        dt = _median(
            lambda uk=uk: jax.block_until_ready(s.find(q, use_kernel=uk)))
        _row("find-churn", impl, dt / Q * 1e9,
             f"Q={Q} churn={churn.size} tombstones={dels.size} "
             f"live={s.total_live}"
             + (" interpret-mode (correctness-grade)" if uk else ""))
    return rows


def bench_restack(n: int = 1 << 16, shard_counts=(2, 4, 8),
                  eps: float = 0.7) -> list[dict]:
    """Hot-shard maintenance cost vs total shard count.

    ``restack-churn``: every insert batch lands in ONE shard (rebalancing
    off); per-round cost = the routed merge into that shard + the slice-
    cache refresh the next ``find`` pays.  With the per-shard slice cache
    the per-round maintenance work is O(touched shards) = O(1), so ns/key
    must stay ~flat as the shard count grows — the pre-PR5 ``_stacked()``
    re-padded and re-stacked every shard per mutation, scaling O(all).

    ``migrate-skew``: the same ingest with rebalancing ON; the detail
    reports incremental (delta-riding) vs full-rebuild migrations — the
    common budget-respecting case must ride the receiver's delta tier, not
    rebuild both shards from scratch.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import distributed

    base = _keys(n)
    rng = np.random.default_rng(4)
    n_leaves = max(n // 256, 16)
    rows: list[dict] = []

    def _row(op, impl, ns, detail):
        rows.append({"op": op, "impl": impl, "n_keys": int(base.size),
                     "ns_per_op": round(ns, 1), "detail": detail})
        print(f"{op:14s} {impl:14s} {ns:12.0f} ns/op  {detail}")

    prime, batch, rounds = 8192, 512, 8
    for S in shard_counts:
        if S > len(jax.devices()):
            continue
        mesh = Mesh(np.asarray(jax.devices()[:S]), ("data",))

        def _build(*, mesh=mesh, **kw):
            return distributed.ShardedDynamicIndex.build(
                jnp.asarray(base), mesh, n_leaves=n_leaves, eps=eps, **kw)

        # fresh f32-exact keys inside shard 0's range (hot for every batch)
        idx = _build(rebalance_ratio=None)
        splits0 = float(idx.splits[0])
        lo = base[0] / 2
        hot = np.setdiff1d(
            np.unique(rng.uniform(lo, splits0, prime + (rounds + 2) * batch
                                  + 20_000).astype(np.float32))
            .astype(np.float64), base)
        q = jnp.asarray(rng.choice(base, 2048))

        idx.insert_batch(hot[:prime])       # capacity ramp + jit warm
        jax.block_until_ready(idx.find(q, use_kernel=False))
        times = []
        r0_rows, r0_full = idx.restack_rows, idx.restack_full
        for r in range(rounds):
            chunk = hot[prime + r * batch: prime + (r + 1) * batch]
            t0 = time.time()
            idx.insert_batch(chunk)
            jax.block_until_ready(idx.find(q, use_kernel=False))
            times.append(time.time() - t0)
        _row("restack-churn", f"sharded-{S}",
             float(np.median(times)) / batch * 1e9,
             f"rounds={rounds} batch={batch} hot_shard=1/{S} "
             f"rows_written={idx.restack_rows - r0_rows} "
             f"full_restacks={idx.restack_full - r0_full}")

        # skewed ingest with rebalancing on: migrations must ride the
        # receiver's delta tier in the common case.  skew=1.5 because a
        # pure-insert hot shard can never exceed 2x the mean on a 2-shard
        # mesh (live_0 <= total) — 1.5 lets the 4/8-shard meshes trigger
        # within this ingest volume (the 2-shard row is a negative
        # control).
        idx = _build(rebalance_skew=1.5)
        t0 = time.time()
        for r in range(8):
            idx.insert_batch(hot[r * 2048:(r + 1) * 2048])
        jax.block_until_ready(idx.find(q, use_kernel=False))
        dt = time.time() - t0
        _row("migrate-skew", f"sharded-{S}", dt / (8 * 2048) * 1e9,
             f"ingest={8 * 2048} rebalances={idx.rebalances} "
             f"migrations_incremental={idx.migrations_incremental} "
             f"migrations_full={idx.migrations_full}")
    return rows


def bench_recover(n_values=(1 << 14, 1 << 16), eps: float = 0.7,
                  n_shards: int = 4) -> list[dict]:
    """Durability cost trajectory: snapshot cost vs index size, restore
    latency at the same width, and restore-resharded (N->2) latency — the
    elastic-restart path after host loss.  ``full_rebuilds`` in the detail
    must stay 0: resharding cuts fitted shards and rides delta merges, it
    never rebuilds from scratch.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import distributed, persist

    rows: list[dict] = []

    def _row(op, impl, n_keys, ns, detail):
        rows.append({"op": op, "impl": impl, "n_keys": int(n_keys),
                     "ns_per_op": round(ns, 1), "detail": detail})
        print(f"{op:16s} {impl:12s} {ns:12.1f} ns/key  {detail}")

    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("data",))
    mesh2 = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    rng = np.random.default_rng(7)
    for n in n_values:
        base = _keys(n)
        n_leaves = max(n // 256, 16)
        idx = distributed.ShardedDynamicIndex.build(
            jnp.asarray(base), mesh, n_leaves=n_leaves, eps=eps)
        fresh = np.setdiff1d(_keys(4 * n, seed=9), base)
        idx.insert_batch(fresh[:n // 8])
        idx.delete_batch(rng.choice(base, n // 16, replace=False))
        nk = idx.total_live
        with tempfile.TemporaryDirectory() as d:
            store = persist.SnapshotStore(d, keep=2 + REPEATS)
            persist.snapshot_sharded(store, 0, idx, blocking=True)  # warm
            step = [0]

            def _snap(idx=idx, step=step, store=store):
                step[0] += 1
                persist.snapshot_sharded(store, step[0], idx,
                                         blocking=True)

            dt = _median(_snap)
            sd = Path(store.directory) / persist._STEP_FMT.format(step[0])
            nbytes = sum(f.stat().st_size for f in sd.iterdir())
            _row("snapshot", f"sharded-{n_shards}", nk, dt / nk * 1e9,
                 f"bytes={nbytes} files={len(list(sd.iterdir()))} "
                 f"keys={nk}")

            dt = _median(
                lambda store=store: persist.restore_sharded(store, mesh))
            _row("restore", f"sharded-{n_shards}", nk, dt / nk * 1e9,
                 f"keys={nk} same-width")

            st = [None]

            def _reshard(store=store):
                _, rep = persist.restore_sharded(store, mesh2)
                st[0] = rep.reshard

            dt = _median(_reshard)
            s = st[0]
            _row("restore-reshard", f"{n_shards}to2", nk, dt / nk * 1e9,
                 f"pieces={s.pieces} delta_merges={s.delta_merges} "
                 f"moved_keys={s.moved_keys} leaf_refits={s.leaf_refits} "
                 f"full_rebuilds={s.full_rebuilds}")
    return rows


def bench_drift(n: int = 1 << 17, batches: int = 8, batch: int = 2000,
                eps: float = 0.65) -> list[dict]:
    """Drift-adaptive serving trajectory (core.drift, through the
    ``repro.api.Index`` facade).

    One workload, two modes: ``batches`` insert batches per phase of a
    stationary -> shifted-lognormal -> zipf-hot ingest.  The timed section
    is the serving-path cost only — the insert call plus a blocking probe
    find; the idle-window maintenance that the serve frontend runs between
    batches (``Index.maybe_swap`` + a delta-bloat flush) is untimed,
    exactly like ``serve.frontend._maintain``.

    ``swap`` builds with the online KS monitor + ``swap_on_drift``: the
    insert path defers all structural repair to the idle window, where the
    bound-checked pool hot-swap absorbs drift pressure (rejected leaves
    take their refit there too, off the serving path).  ``refit-only`` is
    the same index without monitoring, so every over-budget leaf pays the
    O(n) merge + refit storm inline.  The committed claim: swap-mode p99
    per-key insert latency stays ~flat across the phase shifts while
    refit-only spikes by an order of magnitude.

    The zipf phase is drawn over base-*rank* space (hot CDF slots,
    interpolated between neighbouring base keys), not raw key space — a
    raw-key hot set lands on single wide leaves in sparse regions and
    models an out-of-support workload rather than hot-key drift.
    """
    import jax
    import jax.numpy as jnp
    from repro.api import Index
    from repro.core import reuse, synth

    def f32e(a):
        # tracelint: ok[f32-cast](f32-exact key synthesis)
        return np.unique(np.sort(np.asarray(a, np.float64))
                         .astype(np.float32).astype(np.float64))

    base = f32e(np.random.default_rng(10).lognormal(0.0, 0.5, n))
    pool = reuse.build_pool(synth.generate_pool(eps, ns=256, seed=1),
                            kind="linear", m_sim=64)
    rows: list[dict] = []

    def _row(impl, phase, ns, detail):
        rows.append({"op": "drift-ingest", "impl": impl, "phase": phase,
                     "n_keys": int(base.size), "ns_per_op": round(ns, 1),
                     "detail": detail})
        print(f"drift-ingest {impl:10s} {phase:16s} {ns:12.0f} ns/key(p99)"
              f"  {detail}")

    def _phases():
        """The phase schedule, regenerated per pass (same seed -> every
        pass sees byte-identical batches, so the warm pass compiles every
        shape the measured pass will hit)."""
        rng = np.random.default_rng(11)
        nb = base.shape[0]
        slots = rng.permutation(64)

        def zipf(s):
            # Hot CDF slots: zipf over 64 rank-space slots, keys drawn by
            # interpolating between neighbouring base keys inside the slot
            # (even per-leaf pressure — the hot set spans whole leaves).
            r = slots[(rng.zipf(1.2, s) - 1) % 64]
            pos = (r + rng.uniform(0.0, 1.0, s)) * (nb - 1) / 64.0
            i = pos.astype(int)
            frac = pos - i
            return (base[i] * (1.0 - frac)
                    + base[np.minimum(i + 1, nb - 1)] * frac)

        return [("stationary", lambda s: rng.lognormal(0.0, 0.5, s)),
                ("shift-lognormal", lambda s: rng.lognormal(0.9, 0.45, s)),
                ("zipf-hot", zipf)]

    def _run(impl, drift_kw, measure):
        ix = Index.build(jnp.asarray(base), eps=eps, n_leaves=256,
                         kind="linear", **drift_kw)
        d = ix.backend

        def maintain():
            # The serve idle window: proactive swaps + deferred refits,
            # plus the delta-bloat flush both modes share.
            ix.maybe_swap()
            if d.delta_live > d.base_n // 4:
                d.flush_delta()

        for phase, draw in _phases():
            ts = []
            rb_in = rb_mnt = 0
            sw0, rj0 = d.swaps_committed, d.swap_rejects
            for _ in range(batches):
                b = f32e(draw(batch))
                probe = b[:64]
                r0 = d.rebuilds
                t0 = time.perf_counter()
                ix.insert(b)
                jax.block_until_ready(ix.find(probe, path="jnp"))
                ts.append((time.perf_counter() - t0) / b.size * 1e9)
                rb_in += d.rebuilds - r0
                r1 = d.rebuilds
                maintain()
                rb_mnt += d.rebuilds - r1
            if measure:
                score = (float(np.max(np.asarray(d.drift.score)))
                         if d.drift is not None else 0.0)
                _row(impl, phase, float(np.percentile(ts, 99)),
                     f"batches={len(ts)} batch~{batch} "
                     f"p50={np.percentile(ts, 50):.0f} "
                     f"max={max(ts):.0f} "
                     f"swaps={d.swaps_committed - sw0} "
                     f"rejects={d.swap_rejects - rj0} "
                     f"rebuilds_inline={rb_in} "
                     f"rebuilds_maint={rb_mnt} ks={score:.3f}")

    for impl, drift_kw in (
            ("refit-only", {}),
            ("swap", dict(pool=pool, drift_bins=64, drift_hi=0.02,
                          drift_lo=0.01, swap_on_drift=True))):
        _run(impl, drift_kw, measure=False)   # warm: compile every shape
        _run(impl, drift_kw, measure=True)
    return rows


def drift_quick_rows(n: int = 1 << 14) -> list[dict]:
    """CSV rows for benchmarks.run's ``drift`` suite (single-host)."""
    return [{"name": f"drift_{r['impl']}_{r['phase']}",
             "us_per_call": r["ns_per_op"] / 1e3,
             "derived": r["detail"]}
            for r in bench_drift(n, batches=4, batch=1500)]


def _sharded_rows(n_shards: int, n: int) -> list[dict]:
    """Sharded rows via the shared forced-device-count worker call
    (harness.worker_suite — the host-device count locks at first jax
    init)."""
    from . import harness
    return harness.worker_suite("benchmarks.bench_updates",
                                "--sharded-worker", n_shards, n)


def _restack_rows_worker(n_devices: int, n: int) -> list[dict]:
    """Restack/migration sweep rows (shard counts 2/4/8 share one
    8-device worker)."""
    from . import harness
    return harness.worker_suite("benchmarks.bench_updates",
                                "--restack-worker", n_devices, n)


def _recover_rows_worker(n_devices: int, n: int) -> list[dict]:
    """Durability sweep rows (snapshot / restore /
    restore-resharded-to-2)."""
    from . import harness
    return harness.worker_suite("benchmarks.bench_updates",
                                "--recover-worker", n_devices, n)


def quick_rows(n: int = 1 << 15) -> list[dict]:
    """CSV rows for benchmarks.run (name/us_per_call/derived schema)."""
    return [{"name": f"updates_{r['op']}_{r['impl']}",
             "us_per_call": r["ns_per_op"] / 1e3,
             "derived": r["detail"]} for r in bench(n, with_pool=False)]


def sharded_quick_rows(n: int = 1 << 15, n_shards: int = 4) -> list[dict]:
    """CSV rows for benchmarks.run's ``sharded`` suite (subprocess mesh)."""
    return [{"name": f"sharded_{r['op']}_{r['impl']}",
             "us_per_call": r["ns_per_op"] / 1e3,
             "derived": r["detail"]} for r in _sharded_rows(n_shards, n)]


def restack_quick_rows(n: int = 1 << 15, n_devices: int = 8) -> list[dict]:
    """CSV rows for benchmarks.run's ``restack`` suite (subprocess mesh)."""
    return [{"name": f"restack_{r['op']}_{r['impl']}",
             "us_per_call": r["ns_per_op"] / 1e3,
             "derived": r["detail"]}
            for r in _restack_rows_worker(n_devices, n)]


def recover_quick_rows(n: int = 1 << 14, n_devices: int = 4) -> list[dict]:
    """CSV rows for benchmarks.run's ``recover`` suite (subprocess mesh)."""
    return [{"name": f"recover_{r['op']}_{r['impl']}",
             "us_per_call": r["ns_per_op"] / 1e3,
             "derived": r["detail"]}
            for r in _recover_rows_worker(n_devices, n)]


def main() -> None:
    from . import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 17)
    ap.add_argument("--shards", type=int, default=4,
                    help="mesh width for the sharded rows (0 disables)")
    ap.add_argument("--sharded-worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: emit rows as JSON
    ap.add_argument("--restack-worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: emit rows as JSON
    ap.add_argument("--recover-worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: emit rows as JSON
    ap.add_argument("--out", default=str(Path(__file__).resolve().parent.parent
                                         / "BENCH_updates.json"))
    args = ap.parse_args()
    use_compile_cache()
    if args.sharded_worker:
        rows = bench_sharded(args.n, args.sharded_worker)
        print(json.dumps(rows))
        return
    if args.restack_worker:
        rows = bench_restack(args.n)
        print(json.dumps(rows))
        return
    if args.recover_worker:
        rows = bench_recover((args.n, 4 * args.n))
        print(json.dumps(rows))
        return
    rows = bench(args.n)
    # Per-PR trajectory: append keyed by (git sha, suite); the committed
    # baseline meta/rows from the seeding run stay untouched.
    harness.append_bench(
        args.out, "updates", rows,
        note="host-loop-seed rows time the pre-PR2 per-leaf host buffer "
             "implementation; two-tier rows are the device-resident "
             "delta-tier subsystem. two-tier-pallas times the Pallas "
             "interpreter (correctness-grade).")
    if args.shards:
        srows = _sharded_rows(args.shards, min(args.n, 1 << 16))
        if srows:
            harness.append_bench(
                args.out, "sharded", srows,
                note=f"ShardedDynamicIndex churn on a forced "
                     f"{args.shards}-host-device CPU mesh vs single-device "
                     f"DynamicRMI at equal total keys; pallas rows are "
                     f"interpreter (correctness-grade).")
        rrows = _restack_rows_worker(8, min(args.n, 1 << 16))
        if rrows:
            harness.append_bench(
                args.out, "restack", rrows,
                note="Hot-shard maintenance sweep at equal total keys on "
                     "one forced 8-host-device CPU mesh (2/4/8-shard "
                     "submeshes): restack-churn rows must stay ~flat in "
                     "shard count (per-shard slice cache, O(touched) "
                     "restack); migrate-skew rows report incremental "
                     "(delta-riding) vs full-rebuild migrations.")
        krows = _recover_rows_worker(4, min(args.n, 1 << 14))
        if krows:
            harness.append_bench(
                args.out, "recover", krows,
                note="Durability sweep on a forced 4-host-device CPU mesh: "
                     "snapshot cost vs index size, same-width restore, and "
                     "restore-resharded 4->2 (elastic restart); "
                     "full_rebuilds must stay 0.")
    drows = bench_drift(min(args.n, 1 << 17))
    harness.append_bench(
        args.out, "drift", drows,
        note="Drifting ingest (stationary -> shifted lognormal -> zipf) "
             "through the repro.api.Index facade: p99 per-key "
             "insert+maintenance latency, swap mode (online KS monitor + "
             "bound-checked pool hot-swaps) vs refit-only — swap-mode p99 "
             "must stay ~flat while refit-only spikes on merge storms.")


if __name__ == "__main__":
    main()
