"""Two-tier (base + delta) dynamic-update subsystem tests: cross-leaf rank
accounting, tombstone semantics, pool-reuse rebuilds with measured bounds,
kernel-vs-oracle parity for the fused dynamic lookup, and the no-host-loop
guard on the jitted hot paths."""
import numpy as np
import jax.numpy as jnp
import pytest

import repro  # noqa: F401
from repro.core import reuse, rmi, synth
from repro.core import updates as updates_mod
from repro.core.updates import DynamicRMI
from repro.kernels import ref
from repro.kernels.lookup import dynamic_lookup_pallas

RNG = np.random.default_rng(7)


def _f32_keys(n, seed=0, lo=0.0, hi=1.0):
    rng = np.random.default_rng(seed)
    k = np.sort(rng.uniform(lo, hi, n))
    return np.unique(k.astype(np.float32)).astype(np.float64)


@pytest.fixture(scope="module")
def lin_pool():
    return reuse.build_pool(synth.generate_pool(0.9, limit=200),
                            kind="linear")


def _truth(d, q):
    live = d.live_keys()
    return np.isin(q, live), np.searchsorted(live, q, side="left")


def _assert_find_exact(d, q, use_kernel=False):
    tf, tr = _truth(d, np.asarray(q))
    f, r = d.find(jnp.asarray(q), use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(f), tf)
    np.testing.assert_array_equal(np.asarray(r), tr)


# ---------------------------------------------------------------------------
# Satellite: cross-leaf rank regression.
# ---------------------------------------------------------------------------
def test_rank_counts_deltas_in_earlier_leaves():
    """The seed composed base_pos + routed-leaf buffer rank only, dropping
    buffered inserts in earlier leaves; the two-tier rank must count every
    live delta key < q."""
    base = _f32_keys(20_000, seed=1)
    # eps=0.5 -> Lemma 4.1 budget == leaf size: no rebuilds, inserts stay
    # in the delta tier where the seed's bug lived.
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=16,
                         kind="linear")
    ins = _f32_keys(512, seed=2)                 # spread over all leaves
    ins = np.setdiff1d(ins, base)
    d.insert_batch(ins)
    assert d.rebuilds == 0 and d.total_buffered == ins.size
    # queries in the LAST leaf: rank must include earlier-leaf inserts
    q = np.concatenate([base[-50:], ins[-20:]])
    live = d.live_keys()
    _, r = d.find(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(r), np.searchsorted(live, q))
    # and the strictest form: rank of the largest key counts everything
    _, r_top = d.find(jnp.asarray(live[-1:]))
    assert int(r_top[0]) == live.size - 1


# ---------------------------------------------------------------------------
# Satellite: rebuild refits the model and bounds stay measured/tight.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("reuse_on_rebuild", [None, True])
def test_rebuild_refits_model_and_bounds(lin_pool, reuse_on_rebuild):
    """The seed's _rebuild_leaf only reset counters — model and bounds went
    stale.  Post-rebuild, every leaf's error bounds must cover the measured
    residuals of its members over the *merged* base (and the delta entries
    of rebuilt leaves must actually be merged)."""
    base = _f32_keys(30_000, seed=3)
    d = DynamicRMI.build(jnp.asarray(base), pool=lin_pool, eps=0.9,
                         n_leaves=64, kind="linear",
                         reuse_on_rebuild=reuse_on_rebuild)
    ins = np.setdiff1d(_f32_keys(6_000, seed=4), base)
    d.insert_batch(ins)
    assert d.rebuilds > 0
    assert d.base_n > base.size          # delta actually merged into base
    idx = d.index
    buckets = updates_mod._routed_buckets(idx.root_kind, idx.root, idx.keys,
                                          idx.n_leaves, d.route_n)
    pred = rmi._leaf_predict_all(idx.leaf_kind, idx.leaves, idx.keys,
                                 buckets)
    lo, hi = rmi.segment_residual_bounds_sorted(pred, buckets, idx.n_leaves)
    elo, ehi = np.asarray(idx.err_lo), np.asarray(idx.err_hi)
    assert (np.asarray(lo) >= elo - 1e-6).all()
    assert (np.asarray(hi) <= ehi + 1e-6).all()
    # bounds are measured (tight), not the widen-only fallback: windows stay
    # far below the sentinel full-array width
    live_rows = np.asarray(
        rmi.leaf_stats_sorted(idx.keys, buckets, idx.n_leaves)[0]) > 0
    assert (ehi - elo)[live_rows].max() < d.base_n / 4
    _assert_find_exact(d, np.concatenate([base[:500], ins[:500]]))
    if reuse_on_rebuild:                 # Algorithm-1 reuse actually fired
        assert float(np.mean(np.asarray(idx.reused_mask))) > 0.0


# ---------------------------------------------------------------------------
# Satellite: delete of a key still sitting in the delta tier.
# ---------------------------------------------------------------------------
def test_delete_clears_buffered_insert():
    base = _f32_keys(10_000, seed=5)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=16,
                         kind="linear")
    ins = np.setdiff1d(_f32_keys(200, seed=6), base)
    d.insert_batch(ins)
    victim = ins[37:38]
    f, _ = d.find(jnp.asarray(victim))
    assert bool(f[0])
    d.delete(victim[0])                  # still buffered in the delta tier
    f, _ = d.find(jnp.asarray(victim))
    assert not bool(f[0])                # seed left it live forever
    assert d.delta_live == ins.size - 1
    # rank excludes the tombstoned entry
    _assert_find_exact(d, np.concatenate([ins, base[:100]]))
    # delete of a base key, and of an absent key (no-op)
    d.delete_batch(np.concatenate([base[11:12], np.asarray([1e12])]))
    f, _ = d.find(jnp.asarray(base[11:12]))
    assert not bool(f[0])
    # re-insert after delete resurrects the key
    d.insert_batch(victim)
    f, _ = d.find(jnp.asarray(victim))
    assert bool(f[0])
    _assert_find_exact(d, np.concatenate([ins, base[:100]]))


def test_delete_only_workload_triggers_compaction():
    """ROADMAP churn item: a delete-only workload must not grow the delta
    tier's dead fraction without bound — compaction fires at the configured
    dead ratio, purges every tombstone, and leaves all live ranks (and the
    kernel path) invariant."""
    base = _f32_keys(8_192, seed=21)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=16,
                         kind="linear", compact_dead_ratio=0.25)
    ins = np.setdiff1d(_f32_keys(3_000, seed=22, lo=0.1, hi=0.9), base)
    d.insert_batch(ins)
    assert d.delta_live == ins.size and d.delta_dead_count == 0

    probe = np.concatenate([ins, base[::64]])
    victims = ins[::3]                   # delete-only from here on
    survivors = np.setdiff1d(ins, victims)
    fired = 0
    for chunk in np.array_split(victims, 10):
        before = {}
        if fired == 0 and d.delta_dead_count > 0:
            # capture state right below the threshold to check invariance
            # across the *next* compaction
            f0, r0 = d.find(jnp.asarray(probe))
            before = {"f": np.asarray(f0), "r": np.asarray(r0),
                      "live": d.live_keys()}
        d.delete_batch(chunk)
        if d.delta_compactions > fired:
            fired = d.delta_compactions
            assert d.delta_dead_count == 0          # tombstones purged
            if before:
                # live keys and every rank unchanged by the compaction
                # (modulo the chunk that was just deleted)
                live = d.live_keys()
                np.testing.assert_array_equal(
                    live, np.setdiff1d(before["live"], chunk))
    assert d.delta_compactions >= 1      # the trigger actually fired
    # dead fraction stays bounded by the ratio after every batch
    tot = d.delta_live + d.delta_dead_count
    assert tot == 0 or d.delta_dead_count < 0.25 * tot + len(victims) // 10
    assert d.delta_live == survivors.size
    _assert_find_exact(d, probe)
    _assert_find_exact(d, probe, use_kernel=True)

    # disabling the trigger preserves the old behaviour (dead fraction
    # grows until the next insert/rebuild merge)
    d2 = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=16,
                          kind="linear", compact_dead_ratio=None)
    d2.insert_batch(ins)
    d2.delete_batch(victims)
    assert d2.delta_compactions == 0
    assert d2.delta_dead_count == victims.size
    _assert_find_exact(d2, probe)


def test_delete_duplicate_runs():
    """Partially tombstoned duplicate runs: each delete retires one copy
    (tombstones form a prefix of the run), find stays True while any copy
    is live, and this holds across both tiers and the kernel path."""
    base = _f32_keys(4_096, seed=40)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=8,
                         kind="linear")
    k = base[100:101]                    # one base copy
    d.insert_batch(np.repeat(k, 2))      # + two delta copies
    for expect_live in (2, 1, 0):
        d.delete(k[0])
        f, _ = d.find(jnp.asarray(k))
        fk, _ = d.find(jnp.asarray(k), use_kernel=True)
        assert bool(f[0]) == bool(fk[0]) == (expect_live > 0)
        assert d.live_keys().size == base.size + 2 - (3 - expect_live)
    d.delete(k[0])                       # absent now: no-op
    assert d.live_keys().size == base.size - 1
    _assert_find_exact(d, np.concatenate([k, base[:50]]))


# ---------------------------------------------------------------------------
# Satellite: kernel-vs-oracle parity suite for the two-tier lookup.
# ---------------------------------------------------------------------------
def _kernel_parity(d, q):
    """Raw kernel output must be bit-identical to the jnp oracle, and the
    full wrapped find must match the f64 path exactly."""
    idx = d.index
    root, mat, vec = idx.packed_tables()
    kw = dict(n_leaves=idx.n_leaves, route_n=d.route_n,
              root_kind=idx.root_kind, leaf_kind=idx.leaf_kind,
              iters=idx.search_iters)
    qj = jnp.asarray(q)
    pk, dk = dynamic_lookup_pallas(qj, root, mat, vec, idx.keys,
                                   d.delta_keys, **kw)
    pr, dr = ref.dynamic_lookup_ref(qj, root, mat, vec, idx.keys,
                                    d.delta_keys, **kw)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    _assert_find_exact(d, q, use_kernel=True)
    _assert_find_exact(d, q, use_kernel=False)


@pytest.mark.kernel
def test_dynamic_kernel_parity_empty_delta():
    base = _f32_keys(8_192, seed=8)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.9, n_leaves=32,
                         kind="linear")
    q = np.concatenate([RNG.choice(base, 500), _f32_keys(64, seed=9, hi=2.0)])
    _kernel_parity(d, q)


@pytest.mark.kernel
def test_dynamic_kernel_parity_delta_only_leaves():
    """Leaves with no base members but live delta entries (base has a hole
    in the key range; inserts land in it)."""
    lo = _f32_keys(4_000, seed=10, lo=0.0, hi=1.0)
    hi = _f32_keys(4_000, seed=11, lo=3.0, hi=4.0)
    base = np.concatenate([lo, hi])
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=64,
                         kind="linear")
    d.budget[:] = 1 << 30          # keep the hole-leaves delta-only (empty
    ins = _f32_keys(300, seed=12, lo=1.5, hi=2.5)  # leaves have 0 budget)
    d.insert_batch(ins)
    assert d.rebuilds == 0 and d.total_buffered == ins.size
    q = np.concatenate([ins, RNG.choice(base, 300),
                        _f32_keys(50, seed=13, lo=1.0, hi=3.0)])
    _kernel_parity(d, q)


@pytest.mark.kernel
def test_dynamic_kernel_parity_duplicates_across_tiers():
    base = _f32_keys(8_192, seed=14)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=32,
                         kind="linear")
    dups = RNG.choice(base, 200, replace=False)       # re-insert base keys
    d.insert_batch(dups)
    q = np.concatenate([dups, RNG.choice(base, 300)])
    live = d.live_keys()
    assert live.size == base.size + dups.size         # multiset
    _kernel_parity(d, q)


@pytest.mark.kernel
def test_dynamic_kernel_parity_tombstoned_hits(lin_pool):
    base = _f32_keys(16_384, seed=15)
    d = DynamicRMI.build(jnp.asarray(base), pool=lin_pool, eps=0.9,
                         n_leaves=64, kind="linear")
    ins = np.setdiff1d(_f32_keys(3_000, seed=16), base)
    d.insert_batch(ins)                               # triggers rebuilds
    # tombstone a mix of base keys and still-buffered delta keys
    buffered = np.asarray(d.delta_keys)
    buffered = buffered[np.isfinite(buffered)]
    dels = np.concatenate([RNG.choice(base, 80, replace=False),
                           buffered[:20]])
    d.delete_batch(dels)
    q = np.concatenate([dels, RNG.choice(base, 300), RNG.choice(ins, 300)])
    _kernel_parity(d, q)


def test_post_rebuild_matches_fresh_build(lin_pool):
    """After Lemma 4.1 rebuilds, the dynamic index must answer exactly like
    a from-scratch build_rmi over the merged live keys."""
    base = _f32_keys(20_000, seed=17)
    d = DynamicRMI.build(jnp.asarray(base), pool=lin_pool, eps=0.9,
                         n_leaves=64, kind="linear")
    ins = np.setdiff1d(_f32_keys(4_000, seed=18), base)
    d.insert_batch(ins)
    assert d.rebuilds > 0
    live = d.live_keys()
    fresh = rmi.build_rmi(jnp.asarray(live), n_leaves=64, kind="linear",
                          pool=lin_pool)
    q = np.concatenate([RNG.choice(live, 1_000),
                        _f32_keys(100, seed=19, hi=2.0)])
    want = np.searchsorted(live, q, side="left")
    np.testing.assert_array_equal(
        np.asarray(rmi.lookup(fresh, jnp.asarray(q))), want)
    _, r = d.find(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(r), want)
    _kernel_parity(d, q)


# ---------------------------------------------------------------------------
# Satellite: tier-1 guard — no per-key host loops on the jitted paths.
# ---------------------------------------------------------------------------
def test_insert_and_find_have_no_per_key_host_loops(monkeypatch):
    """insert_batch must be O(1) jit dispatches per batch (no np.insert /
    per-leaf Python loop) and find must be exactly one jitted call
    regardless of the query count."""
    base = _f32_keys(10_000, seed=20)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.5, n_leaves=32,
                         kind="linear")

    def _boom(*a, **k):
        raise AssertionError("per-key host loop primitive called")
    monkeypatch.setattr(np, "insert", _boom)

    calls = {"find": 0, "merge": 0}
    orig_find = updates_mod._find_jit
    orig_fill = updates_mod._fill_delta_jit
    orig_clean = updates_mod._merge_delta_clean_jit
    monkeypatch.setattr(
        updates_mod, "_find_jit",
        lambda *a, **k: (calls.__setitem__("find", calls["find"] + 1),
                         orig_find(*a, **k))[1])
    monkeypatch.setattr(
        updates_mod, "_fill_delta_jit",
        lambda *a, **k: (calls.__setitem__("merge", calls["merge"] + 1),
                         orig_fill(*a, **k))[1])
    monkeypatch.setattr(
        updates_mod, "_merge_delta_clean_jit",
        lambda *a, **k: (calls.__setitem__("merge", calls["merge"] + 1),
                         orig_clean(*a, **k))[1])

    ins = np.setdiff1d(_f32_keys(2_000, seed=21), base)
    d.insert_batch(ins)                       # one merge, no np.insert
    assert calls["merge"] == 1
    for Q in (10, 10_000):                    # dispatch count is Q-invariant
        calls["find"] = 0
        q = RNG.choice(ins, Q)
        d.find(jnp.asarray(q))
        assert calls["find"] == 1
    # and the kernel path performs zero per-query host work: it is a single
    # jitted wrapper call (trace-counted via its module entry point)
    from repro.kernels import ops as kernel_ops
    kcalls = []
    orig_dyn = kernel_ops._dynamic_lookup_jit
    monkeypatch.setattr(kernel_ops, "_dynamic_lookup_jit",
                        lambda *a, **k: (kcalls.append(1),
                                         orig_dyn(*a, **k))[1])
    d.find(jnp.asarray(RNG.choice(ins, 5_000)), use_kernel=True)
    assert len(kcalls) == 1


# ---------------------------------------------------------------------------
# Serve/data integration rides the batched API.
# ---------------------------------------------------------------------------
def test_dynamic_page_table_batched_alloc_release():
    from repro.serve.kvcache import DynamicPageTable, PagedKVCache
    cache = PagedKVCache(n_pages=2048, page_size=16, n_kv_heads=2,
                         head_dim=8, n_layers=1)
    for r in range(4):
        cache.allocate_batch(r, range(64))
    t = DynamicPageTable.build(cache, eps=0.5, kind="linear")
    pages = t.allocate(4, range(32))
    f, pg = t.lookup(np.asarray([(4 << 22) | 7, (1 << 22) | 33],
                                np.float64))
    assert bool(f[0]) and bool(f[1])
    assert pg[0] == pages[7] and pg[1] == cache.table[(1, 33)]
    t.release(1)
    f, _ = t.lookup(np.asarray([(1 << 22) | 33], np.float64))
    assert not bool(f[0])
    # released pages are reusable and re-indexed through the batched API
    t.allocate(5, range(16))
    f, _ = t.lookup(np.asarray([(5 << 22) | 3], np.float64))
    assert bool(f[0])
    # empty allocation is a no-op (must not drain the free pool)
    free_before = len(cache.free)
    assert t.allocate(6, []).size == 0
    assert len(cache.free) == free_before
    # fully released table answers found=False without raising
    for r in (0, 2, 3, 4, 5):
        t.release(r)
    f, _ = t.lookup(np.asarray([(4 << 22) | 7], np.float64))
    assert not bool(f[0])


@pytest.mark.kernel
def test_dynamic_find_ref_parity():
    """ops.dynamic_find (seam-fixed kernel positions + tombstone algebra)
    must match ref.dynamic_find_ref (exact f32 searchsorted boundaries +
    the same algebra) bit-exactly on a churned index: valid kernel
    positions are pinned to the exact boundary by the seam verification."""
    from repro.kernels import ops as kernel_ops
    base = _f32_keys(12_288, seed=33)
    d = DynamicRMI.build(jnp.asarray(base), eps=0.6, n_leaves=32,
                         kind="linear")
    ins = np.setdiff1d(_f32_keys(2_000, seed=34), base)
    d.insert_batch(ins)
    d.delete_batch(np.concatenate([RNG.choice(base, 100, replace=False),
                                   ins[:40]]))
    q = jnp.asarray(np.concatenate(
        [RNG.choice(base, 400), RNG.choice(ins, 200),
         _f32_keys(64, seed=35, hi=2.0)]))
    idx = d.index
    root, mat, vec = idx.packed_tables()
    got_f, got_r = kernel_ops.dynamic_find(
        q, root, mat, vec, idx.keys, d.base_dead, d.base_psum,
        d.delta_keys, d.delta_dead, d.delta_psum, n_leaves=idx.n_leaves,
        route_n=d.route_n, root_kind=idx.root_kind, leaf_kind=idx.leaf_kind,
        iters=idx.search_iters)
    want_f, want_r = ref.dynamic_find_ref(
        q, idx.keys, d.base_dead, d.base_psum, d.delta_keys, d.delta_dead,
        d.delta_psum)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))


@pytest.mark.kernel
def test_dynamic_page_table_sharded():
    """DynamicPageTable rides the sharded dynamic index: a 1-device mesh in
    the default process exercises the full routed insert/delete/find path
    (multi-device meshes are covered by tests/test_sharded_dynamic.py)."""
    import jax
    from repro.serve.kvcache import DynamicPageTable, PagedKVCache
    cache = PagedKVCache(n_pages=1024, page_size=16, n_kv_heads=2,
                         head_dim=8, n_layers=1)
    for r in range(4):
        cache.allocate_batch(r, range(64))
    mesh = jax.make_mesh((1,), ("data",))
    t = DynamicPageTable.build(cache, mesh=mesh, eps=0.5, kind="linear")
    from repro.core.distributed import ShardedDynamicIndex
    assert isinstance(t.dyn, ShardedDynamicIndex)
    pages = t.allocate(4, range(32))
    f, pg = t.lookup(np.asarray([(4 << 22) | 7, (1 << 22) | 33],
                                np.float64))
    assert bool(f[0]) and bool(f[1])
    assert pg[0] == pages[7] and pg[1] == cache.table[(1, 33)]
    t.release(1)
    f, _ = t.lookup(np.asarray([(1 << 22) | 33], np.float64))
    assert not bool(f[0])
    t.allocate(5, range(16))
    f, _ = t.lookup(np.asarray([(5 << 22) | 3], np.float64))
    assert bool(f[0])


def test_empty_build_accepts_inserts():
    """An empty-built DynamicRMI (a sharded index's empty shard) serves
    found=False / rank 0, then absorbs inserts through the normal
    rebuild path."""
    d = DynamicRMI.build(jnp.asarray(np.zeros(0)), eps=0.5, n_leaves=16,
                         kind="linear")
    assert d.live_count == 0
    f, r = d.find(jnp.asarray([1.0, 100.0]))
    assert not np.asarray(f).any() and (np.asarray(r) == 0).all()
    ins = _f32_keys(300, seed=44)
    d.insert_batch(ins)
    _assert_find_exact(d, np.concatenate([ins[:100], [0.0, 2.0]]))
    d.delete_batch(ins[:10])
    _assert_find_exact(d, ins[:50])
    assert d.live_count == ins.size - 10


def _range_truth(d, lo, hi):
    live = d.live_keys()
    el = np.searchsorted(live, lo, side="left")
    return el, np.maximum(np.searchsorted(live, hi, side="right"), el)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_find_range_exact_under_churn(use_kernel):
    """Both find_range paths return (leftmost lo rank, rightmost hi rank)
    vs the flat live oracle across both tiers — duplicate runs included."""
    keys = _f32_keys(3000, seed=51, hi=1e6)
    d = DynamicRMI.build(jnp.asarray(keys), eps=0.7, n_leaves=64,
                         kind="linear")
    d.insert_batch(_f32_keys(500, seed=52, lo=1e5, hi=9e5))
    d.insert_batch(np.repeat(keys[100:110], 5))      # duplicate runs
    d.delete_batch(keys[400:460])
    live = d.live_keys()
    rng = np.random.default_rng(53)
    lo = rng.choice(live, 300)
    hi = (lo * (1 + rng.uniform(0, 0.05, 300))).astype(
        np.float32).astype(np.float64)
    lo[:10] = hi[:10] = np.repeat(keys[100:105], 2)  # run-point ranges
    el, eh = _range_truth(d, lo, hi)
    rl, rh = d.find_range(jnp.asarray(lo), jnp.asarray(hi),
                          use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(rl), el)
    np.testing.assert_array_equal(np.asarray(rh), eh)
    # gather_range materializes exactly live[rank_lo:rank_hi]
    for i, seg in zip(range(8), d.gather_range(rl[:8], rh[:8]), strict=True):
        np.testing.assert_array_equal(seg, live[el[i]:eh[i]])


@pytest.mark.parametrize("use_kernel", [False, True])
def test_find_range_degenerates(use_kernel):
    """Degenerate ranges come back empty (rank_lo == rank_hi) on both
    paths: lo > hi, fully out-of-range both sides, tombstoned lo == hi,
    and the n == 0 empty index."""
    keys = _f32_keys(1000, seed=61, hi=1e5)
    d = DynamicRMI.build(jnp.asarray(keys), eps=0.7, n_leaves=32,
                         kind="linear")
    d.delete_batch(keys[7:8])                        # tombstoned singleton
    live = d.live_keys()
    lo = np.asarray([keys[50], -1e9, live[-1] * 2, keys[7], keys[20]])
    hi = np.asarray([keys[10], -1e8, live[-1] * 4, keys[7], keys[20]])
    rl, rh = d.find_range(jnp.asarray(lo), jnp.asarray(hi),
                          use_kernel=use_kernel)
    rl, rh = np.asarray(rl), np.asarray(rh)
    el, eh = _range_truth(d, lo, hi)
    np.testing.assert_array_equal(rl, el)
    np.testing.assert_array_equal(rh, eh)
    assert (rl[:4] == rh[:4]).all()                  # all empty...
    assert rh[4] - rl[4] == 1                        # ...but live point hits
    assert all(s.size == 0 for s in d.gather_range(rl[:4], rh[:4]))

    empty = DynamicRMI.build(jnp.asarray(np.zeros(0)), eps=0.5,
                             n_leaves=16, kind="linear")
    rl, rh = empty.find_range(jnp.asarray([1.0]), jnp.asarray([2.0]),
                              use_kernel=use_kernel)
    assert int(rl[0]) == 0 and int(rh[0]) == 0


def test_indexed_dataset_locate_range(lin_pool):
    """Batch slicing through the dataset: ranges spanning shard boundaries
    stitch per-shard pieces in shard order and match the global oracle
    under churn; non-finite endpoints are rejected."""
    from repro.data.indexed_dataset import IndexedDataset
    ds = IndexedDataset.create(pool=lin_pool, eps=0.9, n_leaves=64)
    rng = np.random.default_rng(31)
    allk = _f32_keys(9000, seed=31, hi=3e5)
    chunks = np.array_split(allk, 3)
    for c in chunks:
        ds.add_shard(c)
    ds.delete_samples(1, rng.choice(chunks[1], 30, replace=False))
    glob = np.sort(np.concatenate(
        [ds.shards[s].dyn.live_keys() for s in range(3)]))
    lo = rng.choice(glob, 8)
    hi = (lo + rng.uniform(0, 1.5e5, 8)).astype(np.float32) \
        .astype(np.float64)
    lo = np.concatenate([lo, [4e5, -10.0, 100.0]])
    hi = np.concatenate([hi, [5e5, -5.0, 50.0]])     # oor-high / oor-low /
    res = ds.locate_range(lo, hi)                    # lo > hi
    for i, (a, b) in enumerate(zip(lo, hi, strict=True)):
        want = glob[(glob >= a) & (glob <= b)]
        got = np.concatenate([p for _, p in res[i]]) if res[i] \
            else np.zeros(0)
        np.testing.assert_array_equal(got, want, err_msg=f"range {i}")
        sids = [s for s, _ in res[i]]
        assert sids == sorted(sids)
    with pytest.raises(ValueError):
        ds.locate_range([np.inf], [1.0])
    with pytest.raises(ValueError):
        ds.locate_range([1.0, 2.0], [3.0])


def test_indexed_dataset_append_and_delete(lin_pool):
    from repro.data.indexed_dataset import IndexedDataset
    ds = IndexedDataset.create(pool=lin_pool, eps=0.9, n_leaves=64)
    rng = np.random.default_rng(23)
    for s in range(2):
        ds.add_shard(np.sort(rng.lognormal(0, 0.5, 20_000)) * 1e6 + s * 1e11)
    new = rng.lognormal(0, 0.5, 2_000) * 1e6 + 1e11
    ds.append_to_shard(1, new)
    q = rng.choice(new, 200)
    sid, off = ds.locate(q)
    assert (sid == 1).all()
    np.testing.assert_allclose(ds.shards[1].keys[off], q)
    ds.delete_samples(1, q[:50])
    sid, off = ds.locate(q[60:])
    np.testing.assert_allclose(ds.shards[1].keys[off], q[60:])
    # draining a shard completely must not crash boundary maintenance
    ds.delete_samples(1, ds.shards[1].keys)
    assert ds.shards[1].keys.size == 0


# ---------------------------------------------------------------------------
# Boundary-run shed primitives + delta flush (PR5: incremental migration).
# ---------------------------------------------------------------------------
def _churned_dyn(n=4000, seed=31, n_leaves=64, eps=0.7):
    """A DynamicRMI with live delta entries and tombstones in both tiers."""
    base = _f32_keys(n, seed=seed, lo=0.0, hi=1e6)
    extra = np.setdiff1d(_f32_keys(3 * n, seed=seed + 1, lo=0.0, hi=1e6),
                         base)
    d = DynamicRMI.build(jnp.asarray(base), eps=eps, n_leaves=n_leaves,
                         kind="linear")
    rng = np.random.default_rng(seed + 2)
    d.insert_batch(extra[:n // 4])
    live = np.sort(np.concatenate([base, extra[:n // 4]]))
    dels = rng.choice(live, n // 10, replace=False)
    d.delete_batch(dels)
    keep = np.ones(live.size, bool)
    keep[np.searchsorted(live, np.unique(dels))] = False
    return d, live[keep]


def _assert_find_matches(d, live, q):
    lo = np.searchsorted(live, q, side="left")
    hi = np.searchsorted(live, q, side="right")
    found, rank = d.find(jnp.asarray(q), use_kernel=False)
    np.testing.assert_array_equal(np.asarray(rank), lo)
    np.testing.assert_array_equal(np.asarray(found), hi > lo)


@pytest.mark.parametrize("frac", [0.3, 0.7])
def test_shed_suffix_truncates_both_tiers(frac):
    d, live = _churned_dyn()
    split = float(live[int(live.size * frac)])
    before_leaves = d.index.leaves
    d.shed_suffix(split)
    kept = live[live <= split]
    np.testing.assert_array_equal(d.live_keys(), kept)
    assert d.live_count == kept.size
    # survivor positions unchanged: models untouched, packed root cache too
    assert d.index.leaves is before_leaves
    rng = np.random.default_rng(9)
    q = np.concatenate([rng.choice(kept, 300), [split, kept[0], kept[-1]]])
    _assert_find_matches(d, kept, q)


@pytest.mark.parametrize("frac", [0.3, 0.7])
def test_shed_prefix_shifts_intercepts_exactly(frac):
    d, live = _churned_dyn(seed=47)
    split = float(live[int(live.size * frac)])
    iters_before = d.index.search_iters
    d.shed_prefix(split)
    kept = live[live > split]
    np.testing.assert_array_equal(d.live_keys(), kept)
    assert d.live_count == kept.size
    # the uniform shift is exact: bounds (hence the clamped depth) keep
    assert d.index.search_iters == iters_before
    rng = np.random.default_rng(9)
    q = np.concatenate([rng.choice(kept, 300), [split, kept[0], kept[-1]]])
    _assert_find_matches(d, kept, q)


def test_shed_roundtrip_donor_receiver():
    """A full donor/receiver hand-off: suffix-shed keys absorbed by an
    adjacent structure keep both sides exact (the sharded _migrate path,
    minus the mesh)."""
    d, live = _churned_dyn(seed=53)
    cut = live[int(live.size * 0.6)]
    moved = live[live > cut]
    recv_base = _f32_keys(500, seed=99, lo=2e6, hi=3e6)
    recv = DynamicRMI.build(jnp.asarray(recv_base), eps=0.7, n_leaves=32,
                            kind="linear")
    d.shed_suffix(float(cut))
    recv.insert_batch(moved)
    recv_live = np.sort(np.concatenate([recv_base, moved]))
    rng = np.random.default_rng(3)
    _assert_find_matches(d, live[live <= cut],
                         rng.choice(live[live <= cut], 200))
    _assert_find_matches(recv, recv_live, np.concatenate(
        [rng.choice(recv_live, 300), [moved[0], moved[-1], recv_base[0]]]))


def test_flush_delta_merges_and_localizes():
    d, live = _churned_dyn(seed=61)
    # a small batch after the bulk churn stays buffered (fresh budgets)
    extra = np.setdiff1d(_f32_keys(12_000, seed=62, lo=0.0, hi=1e6), live)
    d.insert_batch(extra[:200])
    live = np.sort(np.concatenate([live, extra[:200]]))
    assert d.delta_live > 0
    d.flush_delta()
    assert d.delta_live == 0 and d.delta_dead_count == 0
    np.testing.assert_array_equal(d.live_keys(), live)
    rng = np.random.default_rng(5)
    _assert_find_matches(d, live, rng.choice(live, 300))
    # headroom is restored for the flushed leaves (fresh Lemma 4.1 budgets)
    assert d.insertion_headroom > 0


def test_host_refit_matches_in_place_refit(monkeypatch):
    """On an accelerator, builds and leaf refits run on the host CPU backend
    and move back (``updates._host_device``).  Forcing that route here must
    leave the rebuilt and flushed state bit-identical to the in-place one."""
    import jax
    base = _f32_keys(30_000, seed=71)
    ins = np.setdiff1d(_f32_keys(6_000, seed=72), base)
    extra = np.setdiff1d(_f32_keys(400, seed=73), np.concatenate([base, ins]))

    def churned():
        d = DynamicRMI.build(jnp.asarray(base), eps=0.9, n_leaves=64,
                             kind="linear")
        d.insert_batch(ins)
        d.delete_batch(base[::7])
        d.insert_batch(extra)
        d.flush_delta()
        return d

    want = churned()
    monkeypatch.setattr(updates_mod, "_host_device",
                        lambda: jax.devices("cpu")[0])
    got = churned()
    assert got.rebuilds == want.rebuilds > 0
    for a, b in [(got.index.keys, want.index.keys),
                 (got.index.err_lo, want.index.err_lo),
                 (got.index.err_hi, want.index.err_hi),
                 (got.base_dead, want.base_dead),
                 *zip(got.index.leaves, want.index.leaves, strict=True)]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(got.budget, want.budget)
    _assert_find_exact(got, np.concatenate([base[:300], ins[:300], extra]))


def test_host_device_requires_a_cpu_backend(monkeypatch):
    """An accelerator process that hides the CPU backend gets an error
    naming the fix, not a build compiled for the accelerator."""
    import jax

    def devices(backend=None):
        raise RuntimeError(f"unknown backend {backend}")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", devices)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
        updates_mod._host_device()


def test_maintenance_stats_surface():
    from repro.serve.kvcache import DynamicPageTable, PagedKVCache
    cache = PagedKVCache(n_pages=64, page_size=8, n_kv_heads=1, head_dim=4,
                         n_layers=1)
    cache.allocate_batch(0, range(16))
    table = DynamicPageTable.build(cache, n_leaves=8)
    table.allocate(1, range(8))
    table.release(0)
    stats = table.maintenance_stats()
    assert stats["sharded"] is False
    assert stats["live"] == 8
    assert stats["rebuilds"] >= 0 and stats["buffered"] >= 0
