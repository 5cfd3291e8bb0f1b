"""Batched serving front-end (repro.serve.frontend).

Covers the four contracts the module docstring promises:

  * **Coalescing**: the adaptive batcher holds a batch exactly until the
    oldest request has waited the latency budget (injectable clock — no
    wall-clock flakes) and cuts early at the key-count cap.
  * **Capacity-class padding + zero retraces**: after warming the classes
    a workload's batch sizes land in, serving any mix of batch sizes never
    retraces the stacked dispatch (``core.distributed.TRACE_COUNTS`` is the
    trace-time counter, same pattern as the update-path no-host-loop guard).
  * **Multi-tenant bit-exactness**: N tenants of different build sizes
    answered in one stacked dispatch match each tenant's own ``find``
    bit-for-bit — jnp AND kernel-interpret paths, 1/2/4-device meshes
    (subprocess per mesh size, like the other multi-device suites).
  * **Donated row scatters**: the restack/tenant-pack scatter really is
    in-place — donated input consumed (``is_deleted``) and, on CPU where
    jax exposes it, the output aliases the input buffer.
"""
import numpy as np
import pytest

from conftest import run_mesh_script

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import repro  # noqa: E402,F401
from repro.core import distributed as dist_mod  # noqa: E402
from repro.kernels.lookup import capacity_class  # noqa: E402
from repro.serve.frontend import (  # noqa: E402
    AdaptiveBatcher, BatchingFrontend, Request, ServeConfig, TenantPack)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _req(n_keys: int, arrival: float, kind: str = "find") -> Request:
    return Request(0, kind, np.arange(1, n_keys + 1, dtype=np.float64),
                   arrival)


# ---------------------------------------------------------------- batcher --
def test_batcher_coalesces_until_deadline():
    """A batch waits exactly the latency budget from the *oldest* request:
    later arrivals never extend the deadline."""
    clk = FakeClock()
    b = AdaptiveBatcher(latency_budget_s=0.010, max_batch=1000, clock=clk)
    assert not b.ready() and b.deadline() is None

    b.offer(_req(4, arrival=0.0))
    assert b.deadline() == pytest.approx(0.010)
    clk.t = 0.004
    b.offer(_req(4, arrival=clk.t))          # younger request, same deadline
    assert b.deadline() == pytest.approx(0.010)
    clk.t = 0.0099
    assert not b.ready()
    clk.t = 0.010
    assert b.ready()
    batch = b.cut()
    assert [r.keys.size for r in batch] == [4, 4]
    assert len(b) == 0 and not b.ready()


def test_batcher_cuts_early_at_key_cap():
    clk = FakeClock()
    b = AdaptiveBatcher(latency_budget_s=10.0, max_batch=8, clock=clk)
    b.offer(_req(5, 0.0))
    assert not b.ready()                     # budget far away, under cap
    b.offer(_req(3, 0.0))
    assert b.ready()                         # 8 keys >= cap: cut now
    assert len(b.cut()) == 2


# ------------------------------------------------------- donated scatters --
def test_scatter_rows_donated_is_in_place():
    dst = jnp.arange(24, dtype=jnp.float64).reshape(4, 6)
    expect = np.asarray(dst).copy()
    expect[[1, 3]] = [[-1.0] * 6, [-2.0] * 6]
    ptr = None
    if jax.default_backend() == "cpu":
        ptr = dst.unsafe_buffer_pointer()
    out = dist_mod.scatter_rows_donated(
        dst, jnp.asarray([1, 3]),
        jnp.asarray([[-1.0] * 6, [-2.0] * 6], jnp.float64))
    np.testing.assert_array_equal(np.asarray(out), expect)
    assert dst.is_deleted(), "donated input must be consumed"
    if ptr is not None:
        assert out.unsafe_buffer_pointer() == ptr, \
            "donation accepted but output does not alias the input buffer"


# ----------------------------------------------- single-device end-to-end --
def _f32keys(raw):
    return np.unique(np.sort(raw).astype(np.float32)).astype(np.float64)


def _build_tenants(seed: int = 23):
    """Two tenants of different build sizes/leaf counts on the default
    1-device mesh (multi-device variants run in subprocesses below)."""
    rng = np.random.default_rng(seed)
    mesh = jax.make_mesh((1,), ("data",))
    tenants, live, fresh = [], [], []
    for i, (n, nl) in enumerate(((4000, 64), (900, 16))):
        pool = _f32keys(rng.lognormal(0, 0.8, n * 8) * 1e3 + i * 1e7)
        base = np.sort(rng.choice(pool, n, replace=False))
        tenants.append(dist_mod.ShardedDynamicIndex.build(
            jnp.asarray(base), mesh, n_leaves=nl, eps=0.7))
        live.append(base.copy())
        fresh.append(np.setdiff1d(pool, base))
    return tenants, live, fresh


def _check(fe, live, tid, q, tag):
    q = np.asarray(q, np.float64)
    found, rank = fe.lookup(tid, q)
    np.testing.assert_array_equal(
        rank, np.searchsorted(live[tid], q, side="left"), err_msg=tag)
    np.testing.assert_array_equal(
        found, np.searchsorted(live[tid], q, side="right") >
        np.searchsorted(live[tid], q, side="left"), err_msg=tag)


def test_frontend_serves_finds_and_interleaves_updates():
    tenants, live, fresh = _build_tenants()
    rng = np.random.default_rng(3)
    with BatchingFrontend(tenants,
                          config=ServeConfig(latency_budget_s=1e-3)) as fe:
        fe.warmup((1,))
        _check(fe, live, 0, rng.choice(live[0], 40), "t0 fresh")
        _check(fe, live, 1,
               np.concatenate([rng.choice(live[1], 20), fresh[1][-4:],
                               [0.0, 1e30]]), "t1 fresh+miss")
        # updates coalesce with finds and apply before the finds dispatch
        ins = fresh[1][:48]
        assert fe.submit_insert(1, ins).result(timeout=120.0) is None
        live[1] = np.sort(np.concatenate([live[1], ins]))
        dels = rng.choice(live[0], 32, replace=False)
        fe.submit_delete(0, dels).result(timeout=120.0)
        keep = np.ones(live[0].size, bool)
        keep[np.searchsorted(live[0], np.unique(dels))] = False
        live[0] = live[0][keep]
        _check(fe, live, 1, np.concatenate([ins[:16],
                                            rng.choice(live[1], 20)]),
               "t1 after insert")
        _check(fe, live, 0, np.concatenate([dels[:8],
                                            rng.choice(live[0], 20)]),
               "t0 after delete")
        assert fe.stats.updates == 48 + 32
        assert fe.pack.pack_rows >= 1, \
            "tenant updates must refresh via in-place row scatters"


def test_frontend_pads_to_capacity_classes():
    tenants, live, _ = _build_tenants()
    rng = np.random.default_rng(5)
    cfg = ServeConfig(latency_budget_s=1e-3, batch_floor=128)
    with BatchingFrontend(tenants, config=cfg) as fe:
        fe.warmup((1, 200))
        sizes = (1, 3, 127, 128, 129, 200)
        for sz in sizes:
            _check(fe, live, 0, rng.choice(live[0], sz), f"sz={sz}")
        assert fe.stats.qcaps <= {128, 256}, fe.stats.qcaps
        for c in fe.stats.qcaps:
            assert c == capacity_class(c, cfg.batch_floor)
        # one batch per lookup: two tenant rows of its class, one live
        slots = sum(2 * capacity_class(sz, cfg.batch_floor) for sz in sizes)
        assert (fe.stats.queries, fe.stats.ranges) == (sum(sizes), 0)
        assert fe.stats.padded_slots == slots - sum(sizes)


def test_zero_retraces_after_warmup():
    """The retrace guard: once warmup has traced the capacity classes a
    workload lands in, serving any batch-size mix must not trace again —
    batch-size variation changes pad contents, never shapes."""
    tenants, live, _ = _build_tenants()
    rng = np.random.default_rng(7)
    with BatchingFrontend(tenants,
                          config=ServeConfig(latency_budget_s=1e-3)) as fe:
        fe.warmup((1, 200))                 # classes {128, 256}
        before = dist_mod.TRACE_COUNTS["tenant_find"]
        for sz in (1, 2, 17, 64, 127, 128, 129, 199, 250, 256, 5):
            tid = int(rng.integers(2))
            _check(fe, live, tid, rng.choice(live[tid], sz), f"sz={sz}")
        delta = dist_mod.TRACE_COUNTS["tenant_find"] - before
        assert delta == 0, f"hot path retraced {delta}x after warmup"


def test_submit_validation():
    """EVERY request kind rejects non-finite keys up front: +inf is the
    delta-tier pad sentinel, so a non-finite insert would silently corrupt
    later merges and a non-finite range endpoint would walk the rank
    algebra into the capacity padding (regression: the guard used to cover
    only finds)."""
    tenants, _, _ = _build_tenants()
    fe = BatchingFrontend(tenants)
    with pytest.raises(RuntimeError):       # not started
        fe.submit_find(0, [1.0])
    with fe:
        with pytest.raises(ValueError):
            fe.submit_find(2, [1.0])        # unknown tenant
        for bad in (np.inf, -np.inf, np.nan):
            with pytest.raises(ValueError):
                fe.submit_find(0, [bad])
            with pytest.raises(ValueError):
                fe.submit_insert(0, [1.0, bad])
            with pytest.raises(ValueError):
                fe.submit_delete(0, [bad])
            with pytest.raises(ValueError):
                fe.submit_range(0, [bad], [1.0])
            with pytest.raises(ValueError):
                fe.submit_range(0, [1.0], [bad])
        with pytest.raises(ValueError):     # endpoint arrays must pair up
            fe.submit_range(0, [1.0, 2.0], [3.0])
        with pytest.raises(RuntimeError):
            fe.start()                      # double start


def _check_range(fe, live, tid, lo, hi, tag):
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    rl, rh = fe.scan(tid, lo, hi)
    el = np.searchsorted(live[tid], lo, side="left")
    eh = np.maximum(np.searchsorted(live[tid], hi, side="right"), el)
    np.testing.assert_array_equal(rl, el, err_msg=tag)
    np.testing.assert_array_equal(rh, eh, err_msg=tag)


def test_frontend_serves_ranges():
    """Range requests ride the same coalesced dispatch as finds: answers
    match the flat searchsorted oracle, ranges interleave with finds and
    updates, and degenerate ranges come back empty (rank_lo == rank_hi)."""
    tenants, live, fresh = _build_tenants()
    rng = np.random.default_rng(13)
    with BatchingFrontend(tenants,
                          config=ServeConfig(latency_budget_s=1e-3)) as fe:
        fe.warmup((1, 64))
        for tid in (0, 1):
            lo = rng.choice(live[tid], 9)
            hi = (lo * (1 + rng.uniform(0, 0.02, 9))).astype(
                np.float32).astype(np.float64)
            _check_range(fe, live, tid, lo, hi, f"t{tid} fresh")
        # ranges coalesce with point finds in one batch
        rreq = fe.submit_range(0, live[0][:4], live[0][8:12])
        freq = fe.submit_find(1, rng.choice(live[1], 6))
        rl, rh = rreq.result(timeout=120.0)
        np.testing.assert_array_equal(
            rl, np.searchsorted(live[0], live[0][:4], side="left"))
        np.testing.assert_array_equal(
            rh, np.searchsorted(live[0], live[0][8:12], side="right"))
        assert freq.result(timeout=120.0)[0].all()
        # churn between range batches: answers track the live set
        ins = fresh[1][:32]
        fe.submit_insert(1, ins).result(timeout=120.0)
        live[1] = np.sort(np.concatenate([live[1], ins]))
        _check_range(fe, live, 1, ins[:8],
                     (ins[:8] * 1.01).astype(np.float32).astype(np.float64),
                     "after insert")
        # degenerates: lo > hi, fully out-of-range low/high
        span = live[0][-1] - live[0][0]
        for lo, hi in (([live[0][5]], [live[0][2]]),
                       ([live[0][0] - span], [live[0][0] - span / 2]),
                       ([live[0][-1] * 2], [live[0][-1] * 4])):
            rl, rh = fe.scan(0, lo, hi)
            assert np.array_equal(rl, rh), (lo, hi, rl, rh)
        _check_range(fe, live, 0, [live[0][0]], [live[0][-1]], "full span")
        assert fe.stats.ranges > 0


def test_zero_range_retraces_after_warmup():
    """Range batches get their own capacity classes; once warmup traced
    them, serving any mix of range batch sizes never retraces."""
    tenants, live, _ = _build_tenants()
    rng = np.random.default_rng(17)
    with BatchingFrontend(tenants,
                          config=ServeConfig(latency_budget_s=1e-3)) as fe:
        fe.warmup((1, 200))                 # classes {128, 256}
        before = dist_mod.TRACE_COUNTS["tenant_range"]
        for sz in (1, 2, 17, 127, 128, 129, 200, 256):
            tid = int(rng.integers(2))
            lo = rng.choice(live[tid], sz)
            hi = (lo * 1.001).astype(np.float32).astype(np.float64)
            _check_range(fe, live, tid, lo, hi, f"sz={sz}")
        delta = dist_mod.TRACE_COUNTS["tenant_range"] - before
        assert delta == 0, f"range path retraced {delta}x after warmup"


def test_tenant_pack_bit_exact_single_device():
    """One stacked dispatch over tenants of different build sizes matches
    each tenant's own find bit-for-bit — jnp and kernel-interpret paths."""
    tenants, live, fresh = _build_tenants()
    rng = np.random.default_rng(11)
    qcap = 256
    qmat = np.stack([
        rng.permutation(np.concatenate(
            [rng.choice(live[t], qcap - 12), fresh[t][-8:],
             [0.0, 1e30, live[t][0] / 2, live[t][-1] * 2]]))
        for t in range(2)])
    for uk in (False, True):
        pack = TenantPack(tenants, use_kernel=uk,
                          interpret=True if uk else None)
        f, r = pack.find(jnp.asarray(qmat))
        f, r = np.asarray(f), np.asarray(r)
        for t, idx in enumerate(tenants):
            ft, rt = idx.find(jnp.asarray(qmat[t]), use_kernel=uk)
            np.testing.assert_array_equal(
                f[t], np.asarray(ft), err_msg=f"found t={t} uk={uk}")
            np.testing.assert_array_equal(
                r[t], np.asarray(rt), err_msg=f"rank t={t} uk={uk}")
            lo = np.searchsorted(live[t], qmat[t], side="left")
            np.testing.assert_array_equal(r[t], lo,
                                          err_msg=f"oracle t={t} uk={uk}")


# ------------------------------------------------ base held as f32 words --
def _words_tenants(seed: int = 29):
    """Two tenants of different sizes on the 1-device mesh, keys exact in 48
    bits (integers under 2^44, some plus a half) in duplicate runs, with
    tombstones over partial and whole runs and a few buffered inserts.
    Returns (tenants, live keys per tenant, probe keys per tenant)."""
    rng = np.random.default_rng(seed)
    mesh = jax.make_mesh((1,), ("data",))
    tenants, live, probes = [], [], []
    for n, nl in ((3000, 64), (700, 16)):
        k = np.unique(rng.integers(1, 1 << 44, n)).astype(np.float64)
        k[::7] += 0.5
        keys = np.repeat(k, rng.integers(1, 4, k.size))
        t = dist_mod.ShardedDynamicIndex.build(jnp.asarray(keys), mesh,
                                               n_leaves=nl, eps=0.7)
        cur = list(keys)
        dead = rng.choice(k, 60, replace=False)
        for batch in (dead, dead[:30], dead[:30]):   # partial, then whole
            t.delete_batch(batch)
            for d in batch:
                if d in cur:
                    cur.remove(d)
        fresh = np.setdiff1d(np.unique(rng.integers(1, 1 << 44, 24)), k)
        t.insert_batch(fresh.astype(np.float64))
        cur = np.sort(np.concatenate([cur, fresh]))
        tenants.append(t)
        live.append(cur)
        probes.append(np.concatenate([
            rng.choice(cur, 150), dead, dead + 0.5, dead - 1, fresh,
            [0.0, -1e30, 1e30, cur[0] - 0.5, cur[-1] + 0.5, cur[-1] * 2]]))
    return tenants, live, probes


def _qmat(probes, width: int, rng):
    return np.stack([rng.permutation(np.resize(p, width)) for p in probes])


@pytest.mark.parametrize("kind", ["find", "range"])
def test_tenant_pack_words_match_f64(kind):
    """The stacked jnp dispatch answers bit for bit the same with the base
    held as its (hi, lo) f32 words as with f64 keys, and both match the
    sorted-array oracle: duplicate runs, tombstoned runs, +inf pads (the
    smaller tenant pads to the larger one's capacity), out-of-range keys."""
    tenants, live, probes = _words_tenants()
    rng = np.random.default_rng(5)
    f64 = TenantPack(tenants, _words=False)
    words = TenantPack(tenants, _words=True)
    assert words.words and not f64.words
    base = words._refresh()["base"]
    assert [b.dtype for b in base] == [jnp.float32, jnp.float32]
    assert capacity_class(live[1].size) < base[0].shape[-1]
    q = _qmat(probes, 512, rng)
    if kind == "find":
        a, b = f64.find(jnp.asarray(q)), words.find(jnp.asarray(q))
        want = [[np.searchsorted(live[t], q[t], side="right") >
                 np.searchsorted(live[t], q[t]) for t in range(2)],
                [np.searchsorted(live[t], q[t]) for t in range(2)]]
    else:
        hi = q + rng.choice([-3.0, 0.0, 0.5, 1e9, 1e12], q.shape)
        rmat = jnp.asarray(np.concatenate([q, hi], axis=1))
        a, b = f64.find_range(rmat), words.find_range(rmat)
        lo = [np.searchsorted(live[t], q[t]) for t in range(2)]
        want = [lo, [np.maximum(np.searchsorted(live[t], hi[t], "right"),
                                lo[t]) for t in range(2)]]
    for x, y, w in zip(a, b, want, strict=True):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(y), np.stack(w))


def test_tenant_pack_splits_base_once_per_row():
    """The words form splits each tenant's base once at cold assembly and
    again only when that tenant's row goes stale, never per dispatch; the
    f64 form (the CPU mesh's own choice) splits nothing."""
    tenants, live, probes = _words_tenants()
    rng = np.random.default_rng(6)
    q = jnp.asarray(_qmat(probes, 256, rng))
    rmat = jnp.concatenate([q, q + 1.0], axis=1)
    pack = TenantPack(tenants, _words=True)
    auto = TenantPack(tenants)
    assert not auto.words
    assert pack.base_splits == 0
    for _ in range(3):
        pack.find(q)
        pack.find_range(rmat)
        auto.find(q)
    assert pack.base_splits == 2 and auto.base_splits == 0
    fresh = np.asarray([live[1][0] + 0.25, live[1][-1] + 3.0])
    tenants[1].insert_batch(fresh)
    live[1] = np.sort(np.concatenate([live[1], fresh]))
    found, rank = pack.find(q)
    assert pack.base_splits == 3
    for _ in range(3):
        found, rank = pack.find(q)
        pack.find_range(rmat)
    assert pack.base_splits == 3
    qn = np.asarray(q)
    np.testing.assert_array_equal(
        np.asarray(rank), np.stack([np.searchsorted(live[t], qn[t])
                                    for t in range(2)]))


# --------------------------------------------------------- multi-device ---
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.core import distributed
from repro.serve.frontend import BatchingFrontend, ServeConfig, TenantPack

ndev = %(ndev)d
rng = np.random.default_rng(41 + ndev)

def f32keys(raw):
    return np.unique(np.sort(raw).astype(np.float32)).astype(np.float64)

mesh = jax.make_mesh((ndev,), ("data",))
tenants, live, fresh = [], [], []
for i, (n, nl) in enumerate(((6000, 64), (1400, 16))):
    pool = f32keys(rng.lognormal(0, 0.8, n * 8) * 1e3 + i * 1e7)
    base = np.sort(rng.choice(pool, n, replace=False))
    tenants.append(distributed.ShardedDynamicIndex.build(
        jnp.asarray(base), mesh, n_leaves=nl, eps=0.7))
    live.append(base.copy())
    fresh.append(np.setdiff1d(pool, base))

# ---- stacked dispatch bit-exact vs per-tenant find, both paths ---------
qcap = 256 * max(ndev // 2, 1)
qmat = np.stack([
    rng.permutation(np.concatenate(
        [rng.choice(live[t], qcap - 12 - (tenants[t].n_shards - 1)),
         fresh[t][-8:],
         np.asarray(tenants[t].splits, np.float64)
         if tenants[t].n_shards > 1 else np.zeros(0),
         [0.0, 1e30, live[t][0] / 2, live[t][-1] * 2]]))[:qcap]
    for t in range(2)])
ranges = []
for uk, words in ((False, False), (False, True), (True, None)):
    pack = TenantPack(tenants, use_kernel=uk,
                      interpret=True if uk else None, _words=words)
    f, r = pack.find(jnp.asarray(qmat))
    f, r = np.asarray(f), np.asarray(r)
    for t, idx in enumerate(tenants):
        ft, rt = idx.find(jnp.asarray(qmat[t]), use_kernel=uk)
        np.testing.assert_array_equal(f[t], np.asarray(ft),
                                      err_msg="found t=%%d uk=%%s" %% (t, uk))
        np.testing.assert_array_equal(r[t], np.asarray(rt),
                                      err_msg="rank t=%%d uk=%%s" %% (t, uk))
        np.testing.assert_array_equal(
            r[t], np.searchsorted(live[t], qmat[t], side="left"),
            err_msg="oracle t=%%d uk=%%s" %% (t, uk))
    if not uk:
        rmat = jnp.asarray(np.concatenate([qmat, qmat * 1.01], axis=1))
        ranges.append([np.asarray(a) for a in pack.find_range(rmat)])
for a, b in zip(*ranges):                # words form == f64 form
    np.testing.assert_array_equal(a, b, err_msg="range words")

# ---- frontend end-to-end: zero retraces, then interleaved churn --------
def check(fe, tid, q, tag):
    q = np.asarray(q, np.float64)
    found, rank = fe.lookup(tid, q)
    np.testing.assert_array_equal(
        rank, np.searchsorted(live[tid], q, side="left"), err_msg=tag)
    np.testing.assert_array_equal(
        found, np.searchsorted(live[tid], q, side="right") >
        np.searchsorted(live[tid], q, side="left"), err_msg=tag)

with BatchingFrontend(tenants,
                      config=ServeConfig(latency_budget_s=1e-3)) as fe:
    fe.warmup((1, 200))
    before = distributed.TRACE_COUNTS["tenant_find"]
    for sz in (1, 17, 128, 129, 250):
        tid = int(rng.integers(2))
        check(fe, tid, rng.choice(live[tid], sz), "sz=%%d" %% sz)
    delta = distributed.TRACE_COUNTS["tenant_find"] - before
    assert delta == 0, "hot path retraced %%d times after warmup" %% delta

    ins = fresh[1][:64]
    fe.submit_insert(1, ins).result(timeout=300.0)
    live[1] = np.sort(np.concatenate([live[1], ins]))
    dels = rng.choice(live[0], 48, replace=False)
    fe.submit_delete(0, dels).result(timeout=300.0)
    keep = np.ones(live[0].size, bool)
    keep[np.searchsorted(live[0], np.unique(dels))] = False
    live[0] = live[0][keep]
    check(fe, 1, np.concatenate([ins[:16], rng.choice(live[1], 32)]),
          "after insert")
    check(fe, 0, np.concatenate([dels[:8], rng.choice(live[0], 32)]),
          "after delete")
    assert fe.pack.pack_rows >= 1

    # ---- range requests: oracle-exact on the mesh, zero retraces -------
    rbefore = distributed.TRACE_COUNTS["tenant_range"]
    for sz in (1, 9, 130):
        tid = int(rng.integers(2))
        lo = np.sort(rng.choice(live[tid], sz))
        hi = (lo * (1 + rng.uniform(0, 0.02, sz))).astype(
            np.float32).astype(np.float64)
        rl, rh = fe.scan(tid, lo, hi)
        el = np.searchsorted(live[tid], lo, side="left")
        eh = np.maximum(np.searchsorted(live[tid], hi, side="right"), el)
        np.testing.assert_array_equal(rl, el, err_msg="range sz=%%d" %% sz)
        np.testing.assert_array_equal(rh, eh, err_msg="range sz=%%d" %% sz)
    rl, rh = fe.scan(0, [live[0][7]], [live[0][3]])     # degenerate lo > hi
    assert rl[0] == rh[0]
    rdelta = distributed.TRACE_COUNTS["tenant_range"] - rbefore
    assert rdelta == 0, "range path retraced %%d times" %% rdelta
print("SERVE_OK ndev=%(ndev)d")
"""


def _run(ndev: int):
    run_mesh_script(_SCRIPT % {"ndev": ndev}, f"SERVE_OK ndev={ndev}")


def test_serve_mesh_2dev():
    _run(2)


@pytest.mark.slow
def test_serve_mesh_4dev():
    _run(4)
