"""Update handling (paper §4), two-tier and device-resident.

Architecture (PR 2; replaces the per-leaf host Python buffers of the seed):
the *base* tier is the immutable sorted key array served by the RMI, and all
inserts live in a single sorted device-resident *delta* tier with a routed-
leaf table, so the update path rides the same vectorized/jit machinery as
the lookup path:

  insert_batch   one route-sort-merge on device: root-route the batch
                 (vectorized), merge it into the sorted delta tier (argsort
                 gather, tombstoned entries purged in the same pass), bump
                 per-leaf Lemma 4.1 counters with one bincount.
  delete_batch   tombstones as *bitmaps* aligned to each tier (plus exclusive
                 prefix sums for rank arithmetic), marked by one vectorized
                 scatter — a delete of a key still sitting in the delta tier
                 marks the buffered entry itself (the seed's query-value
                 tombstone set left it live forever).
  find           (found, rank) in one fused pass: base window search + delta
                 probe + tombstone mask.  ``rank`` counts *live* keys < q
                 across BOTH tiers (the seed composed base_pos with only the
                 routed leaf's buffer, dropping buffered inserts in earlier
                 leaves).  On TPU (or ``path="kernel"``) the base search is
                 one Pallas kernel call (``kernels.ops.dynamic_index_lookup``);
                 the jnp path here is its f64 oracle and the CPU fast path.
  rebuild        Lemma 4.1 budget exhaustion triggers a *batched* leaf
                 rebuild: the affected leaves' delta entries merge into the
                 base in one sorted merge, and the leaves are re-indexed via
                 pool selection (Algorithm 1 reuse first, refit on miss —
                 ``rmi.fit_leaves``).  Untouched leaves are position-shifted
                 exactly (monotone linear root) or bound-widened (MLP root),
                 and the clamped search depth is recomputed *incrementally*
                 from a maintained per-leaf window-width vector (ROADMAP
                 "Update path x clamped depth") instead of being invalidated.

  maybe_swap     drift-adaptive maintenance (PR 10; ``core.drift``): an
                 online binned KS score over inserted keys vs the build-time
                 CDF drives a ``drift_hi``/``drift_lo`` hysteresis latch.
                 When latched, at-risk leaves (pressure past a quarter of
                 their Lemma 4.1 budget) take an Algorithm-1 pool hot-swap in
                 ONE fused jit — select, adapt, bound-check, commit — where a
                 commit requires the refreshed budget to cover the buffered
                 inserts and the new window to fit the current width cap, so
                 the clamped search depth (and every jit keyed on it) is
                 untouched: zero retraces across commits.  A committed swap
                 starts a fresh budget epoch (``n_inserts`` resets — the
                 bound check paid for the buffered inserts).  In swap mode
                 (``swap_on_drift=True``) the insert path defers ALL
                 structural repair here: budget-exhausted leaves wait for the
                 idle-window maintenance pass, which sweeps them with the
                 ordinary refit when a swap cannot absorb them.

Routing is frozen at build time (``route_n``): the root model plus the
build-time key count define a pure key->leaf hash, so base merges never
remap existing keys between leaves and insert-time routing always matches
find-time routing.

Semantics notes: duplicate keys across tiers are counted as a multiset by
``rank``; ``delete`` removes one (the leftmost live) occurrence of a key.
The delta tier is stored at power-of-two capacity with +inf padding so its
shape — and therefore the jit cache — only changes on capacity doubling.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, fields, is_dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from . import drift as drift_mod
from . import models
from . import rmi as rmi_mod
from .bounds import (clamped_depth, insertion_budget, insertion_headroom,
                     window_widths)
from .paths import resolve_path
from .reuse import ModelPool

Array = jax.Array

_MIN_CAP = 128      # delta-tier floor: one kernel lane tile
_COMPACT_RATIO = 0.25   # default delta-tier dead fraction before compaction


def _pow2ceil(v: int) -> int:
    return 1 << max(int(v) - 1, 1).bit_length()


def _capacity(n: int) -> int:
    """Tier capacity bucket (kernels.lookup.capacity_class with the
    _MIN_CAP floor): shapes only change on pow2 crossings."""
    from ..kernels.lookup import capacity_class
    return capacity_class(n, floor=_MIN_CAP)


# ---------------------------------------------------------------------------
# Jitted tier primitives (module-level so tests can count dispatches).
# ---------------------------------------------------------------------------
def _compact_sorted(keys: Array, keep: Array, payloads: tuple,
                    fills: tuple) -> tuple:
    """Drop ``~keep`` entries from a sorted array, backfilling +inf /
    ``fills``: target slot of a kept entry is its index minus the dropped
    count before it (one cumsum + scatter — order, hence sortedness, is
    preserved with no sort)."""
    cap = keys.shape[0]
    drop = (~keep).astype(jnp.int32)
    tgt = jnp.arange(cap, dtype=jnp.int32) - jnp.cumsum(drop, dtype=jnp.int32) \
        + drop                                              # exclusive cumsum
    tgt = jnp.where(keep, tgt, cap)
    ck = jnp.full((cap,), jnp.inf, keys.dtype).at[tgt].set(keys, mode="drop")
    cp = tuple(jnp.full((cap,), f, p.dtype).at[tgt].set(p, mode="drop")
               for p, f in zip(payloads, fills, strict=True))
    return ck, cp


def _merge_sorted(ak: Array, bk: Array, cap_out: int, a_payloads: tuple,
                  b_payloads: tuple, fills: tuple) -> tuple:
    """Gather-merge of two sorted, +inf-padded arrays (with payloads).

    XLA's CPU sort and scatters are far too slow for the update hot path;
    since both inputs are sorted, the merged position of every ``bk`` entry
    is one searchsorted (ties: ``ak``'s equal run first), and each *output*
    slot then resolves to a pure gather: slot i holds ``bk[bl]`` if the i-th
    merged element is from ``bk`` (bl = #b-positions < i, membership via a
    second searchsorted over the sorted position list), else ``ak[i - bl]``.
    Output re-padded/truncated to ``cap_out`` (callers guarantee every
    finite entry fits).
    """
    na, nb = ak.shape[0], bk.shape[0]
    if nb == 0:                      # drop-only call: resize ak alone
        pad = max(cap_out - na, 0)
        ext = lambda x, f: jnp.concatenate(
            [x, jnp.full((pad,), f, x.dtype)])[:cap_out]
        return ext(ak, jnp.inf), tuple(
            ext(pa, f) for pa, f in zip(a_payloads, fills, strict=True))
    # One small-side searchsorted (nb queries; XLA's searchsorted costs
    # ~O(queries), so keep the big side out of the query slot), then the
    # per-slot source map comes from a bincount + cumsum over the output:
    # ind[i] = 1 iff slot i holds a b element, bl[i] = #b slots before i.
    posb = jnp.arange(nb) + jnp.searchsorted(ak, bk, side="right")  # sorted
    i = jnp.arange(cap_out)
    ind = jnp.bincount(posb, length=cap_out)          # oob posb drop (trunc)
    cum = jnp.cumsum(ind, dtype=jnp.int32)
    from_b = ind > 0
    bl = cum - ind                                    # exclusive
    ai = jnp.clip(i - bl, 0, na - 1)
    bi = jnp.clip(bl, 0, nb - 1)
    in_range = i < na + nb
    out = jnp.where(in_range & from_b, bk[bi],
                    jnp.where(in_range, ak[ai], jnp.inf))
    outp = tuple(
        jnp.where(in_range & from_b, pb[bi],
                  jnp.where(in_range, pa[ai], f))
        for pa, pb, f in zip(a_payloads, b_payloads, fills, strict=True))
    return out, outp


@functools.partial(jax.jit, static_argnames=("cap_out",))
def _merge_delta_jit(dk: Array, dleaf: Array, ddead: Array,
                     new_k: Array, new_leaf: Array, cap_out: int):
    """Sorted merge of a routed (pre-sorted) batch into the delta tier.

    Tombstoned entries are purged by the compaction pass, so the returned
    tier is all-live: callers reset the dead bitmap/prefix sum to zeros.
    Sort-free: one cumsum compaction + one searchsorted gather-merge.
    """
    ck, (cl,) = _compact_sorted(dk, jnp.isfinite(dk) & ~ddead, (dleaf,),
                                (jnp.int32(-1),))
    allk, (alll,) = _merge_sorted(
        ck, new_k.astype(jnp.float64), cap_out, (cl,),
        (new_leaf.astype(jnp.int32),), (jnp.int32(-1),))
    return allk, alll


@functools.partial(jax.jit, static_argnames=("cap_out",))
def _merge_delta_clean_jit(dk: Array, dleaf: Array, new_k: Array,
                           new_leaf: Array, cap_out: int):
    """:func:`_merge_delta_jit` fast path for a tier with no tombstones
    (the common case, tracked host-side): skips the compaction scatter."""
    allk, (alll,) = _merge_sorted(
        dk, new_k.astype(jnp.float64), cap_out, (dleaf,),
        (new_leaf.astype(jnp.int32),), (jnp.int32(-1),))
    return allk, alll


@functools.partial(jax.jit, static_argnames=("cap_out",))
def _fill_delta_jit(new_k: Array, new_leaf: Array, cap_out: int):
    """Insert into an *empty* delta tier: the sorted batch plus padding."""
    pad = cap_out - new_k.shape[0]
    return (jnp.concatenate([new_k.astype(jnp.float64),
                             jnp.full((pad,), jnp.inf, jnp.float64)]),
            jnp.concatenate([new_leaf.astype(jnp.int32),
                             jnp.full((pad,), -1, jnp.int32)]))


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def _batch_counts_sorted(lv: Array, n_leaves: int) -> Array:
    """Per-leaf counts of a routed batch under a monotone root (``lv`` is
    non-decreasing): searchsorted run lengths, no bincount scatter."""
    lid = jnp.arange(n_leaves)
    return jnp.searchsorted(lv, lid, side="right") - \
        jnp.searchsorted(lv, lid, side="left")


@jax.jit
def _moved_counts_sorted(dleaf: Array, rmask: Array) -> Array:
    """Per-leaf live delta counts restricted to ``rmask`` leaves, for a
    tombstone-free tier under a *monotone* root (leaf ids non-decreasing
    over the sorted keys): searchsorted run lengths, no bincount scatter."""
    L = rmask.shape[0]
    arr = jnp.where(dleaf >= 0, dleaf, L)
    lid = jnp.arange(L)
    cnt = jnp.searchsorted(arr, lid, side="right") - \
        jnp.searchsorted(arr, lid, side="left")
    return jnp.where(rmask, cnt, 0)


@jax.jit
def _psum(dead: Array) -> Array:
    """Exclusive prefix sum of a tombstone bitmap, length n + 1 (so a gather
    at position n yields the total dead count)."""
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(dead, dtype=jnp.int32)])


@jax.jit
def _delete_jit(base_keys: Array, base_dead: Array, dk: Array, ddead: Array,
                q: Array):
    """Mark one live occurrence of each query dead: delta tier first (the
    most recent insert), base on a delta miss.  Absent keys are no-ops.

    Duplicates: within an equal-key run tombstones always form a *prefix*
    (this function only ever kills the first live slot, and the order-
    preserving merges keep runs intact), so the first live slot of a run is
    ``run_lo + #dead-in-run`` — repeated deletes of a duplicated key retire
    one copy each.  Duplicate keys within a single batch collapse to one
    removal (same target slot); the returned per-tier counts are exact
    (bitmap population deltas, not per-query hit sums).
    """
    def mark(keys, dead, skip):
        n = keys.shape[0]
        psum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(dead, dtype=jnp.int32)])
        lo = jnp.searchsorted(keys, q, side="left")
        hi = jnp.searchsorted(keys, q, side="right")
        tgt = lo + (psum[hi] - psum[lo])
        hit = (tgt < hi) & ~skip
        return dead.at[jnp.where(hit, tgt, n)].set(True, mode="drop"), hit

    new_ddead, dhit = mark(dk, ddead, jnp.zeros(q.shape, bool))
    new_bdead, _ = mark(base_keys, base_dead, dhit)
    nb = jnp.sum(new_bdead) - jnp.sum(base_dead)
    ndel = jnp.sum(new_ddead) - jnp.sum(ddead)
    return new_bdead, new_ddead, nb, ndel


@jax.jit
def _shed_suffix_jit(keys: Array, dead: Array, cut):
    """Truncate a sorted +inf-padded tier at position ``cut``: entries
    [cut:] become +inf padding / cleared tombstones.  Survivor positions
    are unchanged.  Returns (keys, dead, #tombstones dropped)."""
    keep = jnp.arange(keys.shape[0]) < cut
    nd = dead & keep
    return jnp.where(keep, keys, jnp.inf), nd, jnp.sum(dead) - jnp.sum(nd)


@jax.jit
def _shed_suffix_delta_jit(keys: Array, leaf: Array, dead: Array, cut):
    """:func:`_shed_suffix_jit` with the routed-leaf payload."""
    keep = jnp.arange(keys.shape[0]) < cut
    nd = dead & keep
    return (jnp.where(keep, keys, jnp.inf), jnp.where(keep, leaf, -1), nd,
            jnp.sum(dead) - jnp.sum(nd))


@jax.jit
def _shed_prefix_jit(keys: Array, dead: Array, cut):
    """Drop the first ``cut`` slots of a sorted +inf-padded tier and
    compact left (one gather — order preserved, tail re-padded).  Survivor
    positions all shift down by exactly ``cut``."""
    n = keys.shape[0]
    src = jnp.arange(n) + cut
    ok = src < n
    srcc = jnp.clip(src, 0, n - 1)
    nd = jnp.where(ok, dead[srcc], False)
    return (jnp.where(ok, keys[srcc], jnp.inf), nd,
            jnp.sum(dead) - jnp.sum(nd))


@jax.jit
def _shed_prefix_delta_jit(keys: Array, leaf: Array, dead: Array, cut):
    """:func:`_shed_prefix_jit` with the routed-leaf payload."""
    n = keys.shape[0]
    src = jnp.arange(n) + cut
    ok = src < n
    srcc = jnp.clip(src, 0, n - 1)
    nd = jnp.where(ok, dead[srcc], False)
    return (jnp.where(ok, keys[srcc], jnp.inf),
            jnp.where(ok, leaf[srcc], -1), nd,
            jnp.sum(dead) - jnp.sum(nd))


def leaf_window(leaves, err_lo, err_hi, b, q, n: int, leaf_kind: str):
    """Routed-leaf predict + error-bound window clip (shared by
    :func:`_find_jit` and the sharded per-shard path in
    ``core.distributed`` — only the *routing* that produces ``b``
    differs between them)."""
    p = jax.tree.map(lambda a: a[b], leaves)
    if leaf_kind == "linear":
        pred = models.linear_predict(p, q)
    else:
        h = jax.nn.relu(q[:, None] * p.w1 + p.b1)
        pred = jnp.sum(h * p.w2, -1) + p.b2
    lo = jnp.clip(jnp.floor(pred + err_lo[b]), 0, n - 1).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + err_hi[b]) + 1, 1, n).astype(jnp.int32)
    return lo, hi


def two_tier_answer(base_keys, base_psum, dk, dpsum, q, lo, hi, iters: int):
    """The two-tier find tail, shared by :func:`_find_jit` and the sharded
    per-shard jnp path (``core.distributed``): seam-verified base window
    search, then the tombstone-mask / live-rank algebra.  A hit is any
    *live* entry in the equal-key run [pos, right): count live slots via
    the tombstone prefix sums (robust to partially tombstoned duplicate
    runs).  ``base_keys`` is in either key form (``rmi.like_keys``); ``q``
    is f64.  Returns (found, rank, base_pos)."""
    bq = rmi_mod.like_keys(base_keys, q)
    pos = rmi_mod.verified_search(base_keys, bq, lo, hi, iters=iters)
    bhi = rmi_mod.searchsorted_right(base_keys, bq)
    base_hit = (bhi - pos) > (base_psum[bhi] - base_psum[pos])
    dpos = jnp.searchsorted(dk, q, side="left").astype(jnp.int32)
    dhi = jnp.searchsorted(dk, q, side="right").astype(jnp.int32)
    delta_hit = (dhi - dpos) > (dpsum[dhi] - dpsum[dpos])
    rank = (pos - base_psum[pos]) + (dpos - dpsum[dpos])
    return base_hit | delta_hit, rank, pos


@functools.partial(jax.jit, static_argnames=(
    "root_kind", "leaf_kind", "n_leaves", "route_n", "iters"))
def _find_jit(root, leaves, err_lo, err_hi, base_keys, base_dead, base_psum,
              dk, ddead, dpsum, q, *, root_kind: str, leaf_kind: str,
              n_leaves: int, route_n: int, iters: int):
    """f64 oracle of the fused dynamic kernel: base window search + delta
    probe + tombstone mask, one jit. Returns (found, rank, base_pos)."""
    n = base_keys.shape[0]
    b = rmi_mod.root_buckets(root_kind, root, q, n_leaves, route_n)
    lo, hi = leaf_window(leaves, err_lo, err_hi, b, q, n, leaf_kind)
    return two_tier_answer(base_keys, base_psum, dk, dpsum, q, lo, hi, iters)


def two_tier_range_answer(base_keys, base_psum, dk, dpsum, q_lo, q_hi,
                          lo, hi, iters: int):
    """:func:`two_tier_answer` generalized to an endpoint pair — the
    two-tier range tail shared by :func:`_range_find_jit` and the sharded
    per-shard jnp path (``core.distributed``).  ``rank_lo`` counts live
    keys < q_lo (leftmost boundary: the learned route + verified window
    search, exactly the point path); ``rank_hi`` counts live keys <= q_hi
    (rightmost boundary under duplicates: the side='right' searchsorted
    the point path already pays for its duplicate-run hit test).  rank_hi
    is clamped to rank_lo, so degenerate ranges (q_lo > q_hi, a tombstoned
    singleton, fully out-of-range windows) return an empty [lo, lo) span
    rather than a negative width.  ``lo``/``hi`` are q_lo's error-bound
    window.  ``base_keys`` is in either key form, as in
    :func:`two_tier_answer`.  Returns (rank_lo, rank_hi)."""
    like = functools.partial(rmi_mod.like_keys, base_keys)
    blo = rmi_mod.verified_search(base_keys, like(q_lo), lo, hi, iters=iters)
    bhi = rmi_mod.searchsorted_right(base_keys, like(q_hi))
    dlo = jnp.searchsorted(dk, q_lo, side="left").astype(jnp.int32)
    dhi = jnp.searchsorted(dk, q_hi, side="right").astype(jnp.int32)
    rank_lo = (blo - base_psum[blo]) + (dlo - dpsum[dlo])
    rank_hi = (bhi - base_psum[bhi]) + (dhi - dpsum[dhi])
    return rank_lo, jnp.maximum(rank_hi, rank_lo)


@functools.partial(jax.jit, static_argnames=(
    "root_kind", "leaf_kind", "n_leaves", "route_n", "iters"))
def _range_find_jit(root, leaves, err_lo, err_hi, base_keys, base_dead,
                    base_psum, dk, ddead, dpsum, q_lo, q_hi, *,
                    root_kind: str, leaf_kind: str, n_leaves: int,
                    route_n: int, iters: int):
    """f64 oracle of the fused range kernel (``ops.range_lookup``): route
    q_lo, window-search its left boundary, exact right boundary of q_hi,
    live-rank both.  Returns (rank_lo, rank_hi)."""
    n = base_keys.shape[0]
    b = rmi_mod.root_buckets(root_kind, root, q_lo, n_leaves, route_n)
    lo, hi = leaf_window(leaves, err_lo, err_hi, b, q_lo, n, leaf_kind)
    return two_tier_range_answer(base_keys, base_psum, dk, dpsum, q_lo, q_hi,
                                 lo, hi, iters)


@functools.partial(jax.jit, static_argnames=("root_kind", "n_leaves",
                                             "route_n"))
def _routed_buckets(root_kind: str, root, keys: Array, n_leaves: int,
                    route_n: int) -> Array:
    """Frozen-scale routing that sends +inf capacity padding to the dump
    bucket ``n_leaves`` (segment ops drop it; an unmasked inf saturates to
    INT32_MAX and would clip into the last live leaf)."""
    b = rmi_mod.root_buckets(root_kind, root, keys, n_leaves, route_n)
    return jnp.where(jnp.isfinite(keys), b, n_leaves)


@jax.jit
def _gather_moved(dk: Array, dleaf: Array, ddead: Array, rmask: Array):
    """Live delta entries routed to rebuilt leaves: (sorted keys with +inf
    backfill — a cumsum compaction of the already-sorted tier, no sort —
    membership mask, per-leaf moved counts)."""
    L = rmask.shape[0]
    move = (dleaf >= 0) & ~ddead & rmask[jnp.clip(dleaf, 0, L - 1)]
    mk, _ = _compact_sorted(dk, move, (), ())
    mcnt = jnp.bincount(jnp.where(move, dleaf, L), length=L + 1)[:L]
    return mk, move, mcnt


@functools.partial(jax.jit, static_argnames=("leaf_kind",))
def _compose_rebuild_jit(old_leaves, old_lo, old_hi, old_reused, old_sim,
                         new_leaves, new_lo, new_hi, new_reused, new_sim,
                         new_count, rmask, shift, widen, eps,
                         *, leaf_kind: str):
    """Assemble the post-rebuild leaf state in one jit: exact intercept
    shift (or widen) for untouched leaves, row-select of the refit results,
    and the full Lemma 4.1 budget vector."""
    if leaf_kind == "linear":
        shifted = old_leaves._replace(b=old_leaves.b + shift)
    else:
        shifted = old_leaves._replace(b2=old_leaves.b2 + shift)
    sel = lambda a, o: jnp.where(
        jnp.expand_dims(rmask, tuple(range(1, a.ndim))), a, o)
    leaves = jax.tree.map(sel, new_leaves, shifted)
    err_lo = jnp.where(rmask, new_lo, old_lo - widen)
    err_hi = jnp.where(rmask, new_hi, old_hi + widen)
    reused = jnp.where(rmask, new_reused, old_reused)
    sim = jnp.where(rmask, new_sim, old_sim)
    budget = insertion_budget(new_sim, eps, new_count)
    return leaves, err_lo, err_hi, reused, sim, budget


@functools.partial(jax.jit, static_argnames=("cap_out", "has_dead"))
def _merge_base_jit(base_keys: Array, base_dead: Array, moved: Array,
                    cap_out: int, has_dead: bool = True):
    """One sorted gather-merge of the moved delta entries into the base
    tier.  Both inputs carry +inf capacity padding which sorts past every
    live key; the output is re-padded to ``cap_out`` (the quantized base
    capacity), so base-tier shapes — and every jit specialization over them
    — only change on capacity crossings, not on every merge.  Tombstone
    flags ride the same gather map (skipped when the tier has no tombstones
    yet, ``has_dead=False``).
    """
    if not has_dead:
        allk, _ = _merge_sorted(base_keys, moved, cap_out, (), (), ())
        return allk, jnp.zeros((cap_out,), bool)
    allk, (dead,) = _merge_sorted(
        base_keys, moved, cap_out, (base_dead,),
        (jnp.zeros(moved.shape, bool),), (False,))
    return allk, dead


# ---------------------------------------------------------------------------
# The dynamic index.
# ---------------------------------------------------------------------------
def _host_device():
    """The CPU device that builds and leaf refits run on, or None when the
    default backend is the CPU itself.  On an accelerator these programs
    are f64 prefix scans (per-leaf moments, segmented residual min/max),
    which XLA:TPU does not compile in useful time; serving and updates only
    need gathers, searches and int32 prefix sums, which it does."""
    if jax.default_backend() == "cpu":
        return None
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "index builds and leaf refits run on the host CPU backend, "
            "which JAX does not see: add cpu to JAX_PLATFORMS (for "
            "example JAX_PLATFORMS=tpu,cpu)") from e


def _to_default_device(obj):
    """``obj`` with every jax array inside it — through pytrees and
    dataclass fields (but not a shared ``pool``) — copied to the default
    device, uncommitted like the arrays jnp creates there."""
    def move(v):
        if isinstance(v, jax.Array):
            return jnp.asarray(np.asarray(v))
        if is_dataclass(v) and not isinstance(v, type):
            return replace(v, **{f.name: jax.tree.map(move, getattr(v, f.name))
                                 for f in fields(v) if f.name != "pool"})
        return v
    return jax.tree.map(move, obj)


def _on_host(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, on the host CPU backend when there is one
    to use (:func:`_host_device`): array arguments are copied there and
    the result moves back to the default device."""
    host = _host_device()
    if host is None:
        return fn(*args, **kwargs)
    args = [np.asarray(a) if isinstance(a, jax.Array) else a for a in args]
    with jax.default_device(host):
        out = fn(*args, **kwargs)
    return _to_default_device(out)


def _refit(idx: rmi_mod.RMIIndex, keys: Array, route_n: int,
           **fit_kwargs) -> rmi_mod.LeafFit:
    """``rmi.fit_leaves`` over ``keys`` routed by ``idx``'s frozen root."""
    b = _routed_buckets(idx.root_kind, idx.root, keys, idx.n_leaves, route_n)
    return rmi_mod.fit_leaves(keys, b, idx.n_leaves, **fit_kwargs)


@dataclass
class DynamicRMI:
    """RMI base tier + sorted device delta tier + Lemma 4.1 rebuild policy.

    All hot-path state (both tiers, tombstone bitmaps, prefix sums) is
    device-resident; the host keeps only per-leaf counters (numpy) and the
    incremental search-depth bookkeeping.
    """
    index: rmi_mod.RMIIndex
    pool: ModelPool | None
    eps: float
    route_n: int = 0                    # frozen key->leaf routing scale
    # delta tier (pow2 capacity, +inf padded, sorted ascending)
    delta_keys: Array = None            # (cap,) f64
    delta_leaf: Array = None            # (cap,) i32 routed leaf, -1 pads
    delta_dead: Array = None            # (cap,) bool
    delta_psum: Array = None            # (cap+1,) i32 exclusive dead psum
    delta_live: int = 0                 # live (finite & not dead) entries
    delta_dead_count: int = 0           # tombstoned delta entries (gates
                                        # the compaction-free merge path)
    # Dead-ratio-triggered delta compaction (ROADMAP "delta-tier churn
    # under sustained deletes"): tombstones are purged opportunistically by
    # the next insert/rebuild merge, but a delete-only workload has no such
    # merge — when the dead fraction of the tier exceeds this ratio,
    # delete_batch compacts the tier in place (one cumsum compaction).
    # None disables the trigger.
    compact_dead_ratio: float | None = _COMPACT_RATIO
    delta_compactions: int = 0          # compaction passes run
    # base tier bookkeeping (keys live inside ``index``, +inf padded to
    # pow2 capacity so rebuild merges don't retrace every jit consumer)
    base_n: int = 0                     # finite base keys (incl tombstoned)
    base_dead: Array = None             # (cap,) bool
    base_psum: Array = None             # (cap+1,) i32
    base_dead_count: int = 0            # tombstoned base entries
    # Lemma 4.1 accounting (host)
    n_inserts: np.ndarray = None        # per leaf, since last rebuild
    budget: np.ndarray = None
    rebuilds: int = 0
    deleted: int = 0
    capacity_shrinks: int = 0           # tier capacity step-downs taken
    # maybe_swap route cache: (keys ref, slice len, base, buckets) — valid
    # while the base keys array object is unchanged (rebuild/flush replace
    # it); keeps the maintenance pass O(selection), not O(base scan)
    _swap_route: tuple | None = None
    # Rebuild re-indexing policy: None (auto) runs Algorithm-1 pool
    # selection only when a leaf refit requires *training* (MLP leaves) —
    # for linear leaves the closed-form segment refit is free, optimal, and
    # earns the maximal Lemma 4.1 budget (sim = 1), so reuse could only
    # lose.  True forces pool selection (the paper's Algorithm 1 verbatim);
    # False disables it.
    reuse_on_rebuild: bool | None = None
    build_kwargs: dict = field(default_factory=dict)
    # Online drift monitoring + hot-swap reuse (core.drift; None = off so
    # the seed behavior — and every existing caller — is untouched).
    drift: drift_mod.DriftState | None = None
    swap_on_drift: bool = False         # try pool swaps before refits when
                                        # the drift latch is set
    swaps_committed: int = 0            # leaves hot-swapped (bound held)
    swap_rejects: int = 0               # swap attempts that fell back
    _win: np.ndarray = None             # per-leaf window widths (depth calc)
    _delta_f32: bool | None = None
    _kroot: Array = None                # packed kernel root (frozen: the
                                        # root model and route_n never
                                        # change after build)

    @classmethod
    def build(cls, keys, **kwargs) -> "DynamicRMI":
        """Build over sorted ``keys`` (options: :meth:`_build`).

        On an accelerator the build runs on the host's CPU backend
        (:func:`_host_device`) and the finished state moves to the default
        device."""
        return _on_host(cls._build, keys, **kwargs)

    @classmethod
    def _build(cls, keys, pool=None, eps: float = 0.9,
               reuse_on_rebuild: bool | None = None,
               compact_dead_ratio: float | None = _COMPACT_RATIO,
               drift_bins: int = 0, drift_hi: float = 0.15,
               drift_lo: float = 0.05, swap_on_drift: bool = False,
               **rmi_kwargs):
        """``drift_bins > 0`` turns on the online drift monitor
        (``core.drift``) at that histogram resolution, with the
        [drift_lo, drift_hi] hysteresis band; ``swap_on_drift`` addition-
        ally lets budget-exhausted leaves try an Algorithm-1 pool swap
        before the refit while the drift latch is set."""
        idx = rmi_mod.build_rmi(keys, pool=pool, **rmi_kwargs)
        n = idx.n
        # Frozen routing scale: floor at 1 so an empty build (a sharded
        # index's empty shard) keeps a well-defined key->leaf hash — its
        # zero root sends everything to leaf 0, which stays consistent
        # between insert- and find-time routing.
        route_n = max(n, 1)
        counts = np.bincount(
            np.asarray(rmi_mod.root_buckets(idx.root_kind, idx.root, idx.keys,
                                            idx.n_leaves, route_n)),
            minlength=idx.n_leaves)
        budget = np.array(insertion_budget(
            jnp.asarray(idx.leaf_sim), jnp.float64(eps),
            jnp.asarray(counts, jnp.float64)), copy=True)
        # Quantize the base tier to pow2 capacity with +inf padding: pads
        # sort past every live key and route to the dump bucket, so rebuild
        # merges change shapes (and retrace jits) only on capacity doubling.
        from ..kernels.lookup import pad_capacity
        cap = _capacity(n)
        drift = drift_mod.init_drift(idx.keys, m=drift_bins,
                                     thresh_hi=drift_hi,
                                     thresh_lo=drift_lo) \
            if drift_bins else None
        padded = pad_capacity(idx.keys, cap)
        idx = replace(idx, keys=padded, _f32_exact=None, _packed=None)
        d = cls(index=idx, pool=pool, eps=eps, route_n=route_n, base_n=n,
                reuse_on_rebuild=reuse_on_rebuild,
                compact_dead_ratio=compact_dead_ratio,
                drift=drift, swap_on_drift=swap_on_drift,
                delta_keys=jnp.full((_MIN_CAP,), jnp.inf, jnp.float64),
                delta_leaf=jnp.full((_MIN_CAP,), -1, jnp.int32),
                delta_dead=jnp.zeros((_MIN_CAP,), bool),
                delta_psum=jnp.zeros((_MIN_CAP + 1,), jnp.int32),
                base_dead=jnp.zeros((cap,), bool),
                base_psum=jnp.zeros((cap + 1,), jnp.int32),
                n_inserts=np.zeros(idx.n_leaves, np.int64),
                budget=budget, build_kwargs=rmi_kwargs)
        d._win = window_widths(idx.err_lo, idx.err_hi)
        idx._iters = clamped_depth(d._win, cap)
        return d

    # -- mutation ----------------------------------------------------------
    def insert(self, key: float) -> None:
        self.insert_batch(np.asarray([key], np.float64))

    def insert_batch(self, keys: np.ndarray) -> None:
        """Bulk insert: one vectorized route-sort-merge on device, one host
        sync for the Lemma 4.1 counters, batched rebuild of any leaves whose
        budget is exhausted."""
        keys = np.asarray(keys, np.float64).ravel()
        if keys.size == 0:
            return
        idx = self.index
        k = jnp.asarray(np.sort(keys))        # host sort: batches are host-
        lv = rmi_mod.root_buckets(idx.root_kind, idx.root, k, idx.n_leaves,
                                  self.route_n)  # born, np.sort >> XLA sort
        cap = max(self.delta_keys.shape[0],
                  _capacity(self.delta_live + keys.size))
        if self.delta_live == 0 and self.delta_dead_count == 0:
            self.delta_keys, self.delta_leaf = _fill_delta_jit(
                k, lv, cap_out=cap)
        elif self.delta_dead_count == 0:
            self.delta_keys, self.delta_leaf = _merge_delta_clean_jit(
                self.delta_keys, self.delta_leaf, k, lv, cap_out=cap)
        else:
            self.delta_keys, self.delta_leaf = _merge_delta_jit(
                self.delta_keys, self.delta_leaf, self.delta_dead, k, lv,
                cap_out=cap)
            self.delta_dead_count = 0
        self.delta_dead = jnp.zeros((cap,), bool)
        self.delta_psum = jnp.zeros((cap + 1,), jnp.int32)
        self.delta_live += keys.size
        self._delta_f32 = None
        if self.drift is not None:
            self.drift = drift_mod.update_drift(self.drift, k)
        cnt = np.asarray(_batch_counts_sorted(lv, idx.n_leaves)
                         if idx.root_kind == "linear"
                         else jnp.bincount(lv, length=idx.n_leaves))
        self.n_inserts += cnt
        over = np.flatnonzero(self.n_inserts > self.budget)
        if over.size and self.swap_on_drift and self.drift is not None \
                and self.pool is not None:
            # Drift-adaptive serving mode: structural repair is deferred
            # to the next idle-window maintenance pass (``maybe_swap``
            # sweeps budget-exhausted leaves — hot-swap when the bound
            # holds, refit otherwise).  Queries stay exact meanwhile:
            # buffered inserts live in the delta tier, which find/gather
            # search directly, so the insert path itself never pays an
            # O(n) merge or an O(pool) swap pass.
            return
        if over.size:
            self._rebuild_leaves(over)

    def delete(self, key: float) -> None:
        self.delete_batch(np.asarray([key], np.float64))

    def delete_batch(self, keys: np.ndarray) -> None:
        """§4 deletions as tombstone *bitmaps*: one vectorized scatter marks
        the leftmost live occurrence in the delta tier (buffered inserts die
        here — the seed left them live), else in the base tier.

        Duplicate keys *within one batch* collapse to a single removal
        (they resolve to the same tombstone slot); to retire several copies
        of the same key, issue sequential delete calls/batches."""
        q = jnp.asarray(np.asarray(keys, np.float64).ravel())
        if q.shape[0] == 0:
            return
        self.base_dead, self.delta_dead, nb, ndel = _delete_jit(
            self.index.keys, self.base_dead, self.delta_keys,
            self.delta_dead, q)
        self.base_psum = _psum(self.base_dead)
        self.delta_live -= int(ndel)
        self.delta_dead_count += int(ndel)
        self.base_dead_count += int(nb)
        self.deleted += int(nb) + int(ndel)
        if (self.compact_dead_ratio is not None and self.delta_dead_count
                and self.delta_dead_count >= self.compact_dead_ratio
                * (self.delta_live + self.delta_dead_count)):
            self._compact_delta()       # resets the delta psum to zeros
        else:
            self.delta_psum = _psum(self.delta_dead)

    def _compact_delta(self) -> None:
        """Purge tombstoned delta entries in place (one cumsum compaction +
        re-pad — the same pass insert/rebuild merges run, without merging
        anything).  Live entries, their order, and both tiers' live ranks
        are unchanged; only the dead fraction drops to zero."""
        cap = self.delta_keys.shape[0]
        self.delta_keys, self.delta_leaf = _merge_delta_jit(
            self.delta_keys, self.delta_leaf, self.delta_dead,
            jnp.zeros((0,), jnp.float64), jnp.zeros((0,), jnp.int32),
            cap_out=cap)
        self.delta_dead = jnp.zeros((cap,), bool)
        self.delta_psum = jnp.zeros((cap + 1,), jnp.int32)
        self.delta_dead_count = 0
        self.delta_compactions += 1
        self._delta_f32 = None          # tier contents changed

    # -- boundary-run migration primitives (sharded rebalancer) ------------
    def shed_suffix(self, split: float) -> None:
        """Drop every entry with key > ``split`` from both tiers — the
        donor half of an incremental migration to the *right* neighbour.
        Survivor positions are unchanged (a suffix truncation shifts
        nothing), so every model, error bound, packed kernel table, and the
        clamped search depth stay valid as-is.  ``split`` must land on an
        equal-key run boundary (callers snap it) so duplicate runs — and
        their tombstone-prefix invariant — move or stay whole."""
        cut_b = int(jnp.searchsorted(self.index.keys, jnp.float64(split),
                                     side="right"))
        if cut_b < self.base_n:
            keys, dead, shed_dead = _shed_suffix_jit(
                self.index.keys, self.base_dead, cut_b)
            # keys only lose finite entries to +inf padding: the packed
            # tables (models only) and f32-exactness survive untouched.
            self.index = replace(self.index, keys=keys)
            self.base_dead = dead
            self.base_dead_count -= int(shed_dead)
            self.base_psum = jnp.zeros((keys.shape[0] + 1,), jnp.int32) \
                if self.base_dead_count == 0 else _psum(dead)
            self.base_n = cut_b
        cut_d = int(jnp.searchsorted(self.delta_keys, jnp.float64(split),
                                     side="right"))
        nf = self.delta_live + self.delta_dead_count
        if cut_d < nf:
            dk, dleaf, ddead, sdead = _shed_suffix_delta_jit(
                self.delta_keys, self.delta_leaf, self.delta_dead, cut_d)
            self.delta_keys, self.delta_leaf, self.delta_dead = dk, dleaf, \
                ddead
            self.delta_dead_count -= int(sdead)
            self.delta_live -= (nf - cut_d) - int(sdead)
            self.delta_psum = _psum(ddead)

    def shed_prefix(self, split: float) -> None:
        """Drop every entry with key <= ``split`` — the donor half of an
        incremental migration to the *left* neighbour.  Both tiers compact
        left and every leaf intercept shifts down by exactly the number of
        removed base entries: the shift is uniform (all removals happen
        left of every survivor), so it is exact for either leaf kind under
        any root, and error bounds / clamped depth stay tight.  Routing is
        untouched (the frozen root model maps keys, not positions)."""
        cut_b = int(jnp.searchsorted(self.index.keys, jnp.float64(split),
                                     side="right"))
        if cut_b > 0:
            keys, dead, shed_dead = _shed_prefix_jit(
                self.index.keys, self.base_dead, cut_b)
            if self.index.leaf_kind == "linear":
                leaves = self.index.leaves._replace(
                    b=self.index.leaves.b - cut_b)
            else:
                leaves = self.index.leaves._replace(
                    b2=self.index.leaves.b2 - cut_b)
            # leaf intercepts changed: packed kernel tables go stale (the
            # cached packed *root* on ``_kroot`` stays — roots are frozen).
            self.index = replace(self.index, keys=keys, leaves=leaves,
                                 _packed=None)
            self.base_dead = dead
            self.base_dead_count -= int(shed_dead)
            self.base_psum = jnp.zeros((keys.shape[0] + 1,), jnp.int32) \
                if self.base_dead_count == 0 else _psum(dead)
            self.base_n -= cut_b
        cut_d = int(jnp.searchsorted(self.delta_keys, jnp.float64(split),
                                     side="right"))
        if cut_d > 0:
            dk, dleaf, ddead, sdead = _shed_prefix_delta_jit(
                self.delta_keys, self.delta_leaf, self.delta_dead, cut_d)
            self.delta_keys, self.delta_leaf, self.delta_dead = dk, dleaf, \
                ddead
            self.delta_dead_count -= int(sdead)
            self.delta_live -= (cut_d - int(sdead))
            self.delta_psum = _psum(ddead)

    def clone(self) -> "DynamicRMI":
        """Independent handle over the same (immutable) device arrays.

        Mutating methods rebind fields or mutate host numpy in place — the
        only in-place device-adjacent mutation is ``_rebuild_leaves``
        assigning ``self.index._iters`` — so a clone needs fresh host
        containers and a fresh ``RMIIndex`` wrapper, nothing deeper.  The
        elastic resharder cuts several pieces out of one source shard via
        clones."""
        d = replace(self, index=replace(self.index),
                    n_inserts=self.n_inserts.copy(),
                    budget=self.budget.copy(),
                    build_kwargs=dict(self.build_kwargs))
        if self.drift is not None:
            # Device arrays are immutable and updates rebind a fresh
            # DriftState, so a shallow copy fully decouples the clones.
            d.drift = replace(self.drift)
        d._win = self._win.copy()
        return d

    def shrink_capacity(self, hysteresis: int = 4) -> bool:
        """Step either tier's capacity class back down — the inverse of the
        grow-only policy in ``insert_batch``/``_rebuild_leaves``, for after
        migration sheds or delete-heavy churn.  Hysteresis band: a tier
        shrinks only when its capacity is ≥ ``hysteresis`` times the
        smallest class that fits, and steps down to ``hysteresis/2`` times
        that class
        — so a shrink always leaves a doubling of headroom and regrowing
        needs ≥ 2 doublings (no thrash at a class boundary, and a batch
        smaller than the tier's population can never re-cross one).  Finite
        entries occupy each tier's prefix, so a shrink is a pure slice:
        positions, fitted models, error bounds, packed kernel tables
        (models-only), and f32-exactness are untouched; only the clamped
        search depth is recomputed for the smaller capacity.  Returns True
        if any tier shrank."""
        hold = max(hysteresis // 2, 1)
        shrank = False
        idx = self.index
        cap_b = idx.keys.shape[0]
        want_b = _capacity(self.base_n) * hold
        if cap_b >= hysteresis * _capacity(self.base_n) and cap_b > want_b:
            keys = idx.keys[:want_b]
            self.base_dead = self.base_dead[:want_b]
            self.base_psum = jnp.zeros((want_b + 1,), jnp.int32) \
                if self.base_dead_count == 0 else _psum(self.base_dead)
            self.index = replace(idx, keys=keys)
            self.index._iters = clamped_depth(self._win, want_b)
            self.capacity_shrinks += 1
            shrank = True
        cap_d = self.delta_keys.shape[0]
        nf_d = self.delta_live + self.delta_dead_count
        want_d = _capacity(nf_d) * hold
        if cap_d >= hysteresis * _capacity(nf_d) and cap_d > want_d:
            self.delta_keys = self.delta_keys[:want_d]
            self.delta_leaf = self.delta_leaf[:want_d]
            self.delta_dead = self.delta_dead[:want_d]
            self.delta_psum = jnp.zeros((want_d + 1,), jnp.int32) \
                if self.delta_dead_count == 0 else _psum(self.delta_dead)
            self.capacity_shrinks += 1
            shrank = True
        return shrank

    def flush_delta(self) -> None:
        """Merge every live delta entry into the base tier now, refitting
        only the leaves that actually hold delta entries (the rest take
        :meth:`_rebuild_leaves`'s exact intercept shift) — the incremental
        answer to a delta-hot shard, replacing the old from-scratch shard
        rebuild."""
        if self.delta_live == 0:
            if self.delta_dead_count:
                self._compact_delta()
            return
        L = self.index.n_leaves
        livem = jnp.isfinite(self.delta_keys) & ~self.delta_dead
        cnt = jnp.bincount(jnp.where(livem, self.delta_leaf, L),
                           length=L + 1)[:L]
        lid = np.flatnonzero(np.asarray(cnt))
        if lid.size:
            self._rebuild_leaves(lid)
        # Full merge event: every buffered insert is now part of the base
        # tier and its leaves were refitted on it, so the drift baseline
        # absorbs the accumulated histogram and the latch clears
        # (core.drift lifecycle; partial per-leaf rebuilds do NOT
        # rebaseline — the global score keeps tracking the workload shift
        # until an explicit flush accepts it).
        if self.drift is not None:
            self.drift = drift_mod.rebaseline(self.drift)

    @property
    def insertion_headroom(self) -> float:
        """Aggregate Lemma 4.1 headroom (``bounds.insertion_headroom``):
        how many more inserts the current leaf budgets can absorb."""
        return insertion_headroom(self.budget, self.n_inserts)

    def packed_root(self, route_leaves: int | None = None) -> Array:
        """Packed kernel root block with the frozen routing scale folded in
        (``lookup.pack_root(route_scale=route_leaves / route_n)``), cached
        for the life of the structure — root model and ``route_n`` are
        frozen at build, so there is no invalidation path.  Callers must
        pass a consistent ``route_leaves`` (the sharded dispatch always
        uses its uniform ``n_leaves``)."""
        if self._kroot is None:
            from ..kernels import lookup as _lk
            scale = 1.0 if route_leaves is None \
                else route_leaves / self.route_n
            self._kroot = _lk.pack_root(self.index.root_kind,
                                        self.index.root, route_scale=scale)
        return self._kroot

    # -- rebuild -----------------------------------------------------------
    def _rebuild_leaves(self, leaf_ids: np.ndarray) -> None:
        """Batched Lemma 4.1 rebuild: merge the leaves' delta entries into
        the base tier (one sorted merge) and re-index them via pool
        selection — Algorithm 1 reuse first, refit on miss (``fit_leaves``)
        — with measured post-merge error bounds.  Untouched leaves get an
        exact intercept shift (monotone linear root) or a sound ±m widen
        (MLP root); depth and budgets update incrementally."""
        idx = self.index
        L = idx.n_leaves
        leaf_ids = np.asarray(leaf_ids, np.int64).ravel()
        self.rebuilds += int(leaf_ids.size)
        rmask_np = np.zeros(L, bool)
        rmask_np[leaf_ids] = True
        rmask = jnp.asarray(rmask_np)

        cap = self.delta_keys.shape[0]
        clean = self.delta_dead_count == 0
        if clean and idx.root_kind == "linear":
            # Monotone routing + no tombstones: per-leaf counts are run
            # lengths of the (sorted) routed-leaf table — no scatters.
            mcnt = np.asarray(_moved_counts_sorted(self.delta_leaf, rmask))
            m = int(mcnt.sum())
            if m == self.delta_live:
                # Whole-tier merge (the bulk regime): the sorted tier IS the
                # moved array; just reset the delta afterwards.
                mk = self.delta_keys
                self.delta_keys = jnp.full((cap,), jnp.inf, jnp.float64)
                self.delta_leaf = jnp.full((cap,), -1, jnp.int32)
            else:
                mk, move, _ = _gather_moved(self.delta_keys, self.delta_leaf,
                                            self.delta_dead, rmask)
                self.delta_keys, self.delta_leaf = _merge_delta_jit(
                    self.delta_keys, self.delta_leaf, move,
                    jnp.zeros((0,), jnp.float64), jnp.zeros((0,), jnp.int32),
                    cap_out=cap)
        else:
            mk, move, mcnt_d = _gather_moved(self.delta_keys,
                                             self.delta_leaf,
                                             self.delta_dead, rmask)
            mcnt = np.asarray(mcnt_d)
            m = int(mcnt.sum())
            self.delta_keys, self.delta_leaf = _merge_delta_jit(
                self.delta_keys, self.delta_leaf, self.delta_dead | move,
                jnp.zeros((0,), jnp.float64), jnp.zeros((0,), jnp.int32),
                cap_out=cap)
            self.delta_dead_count = 0
        self.delta_dead = jnp.zeros((cap,), bool)
        self.delta_psum = jnp.zeros((cap + 1,), jnp.int32)
        self.delta_live -= m

        self.base_n += m
        cap_new = max(idx.n, _capacity(self.base_n))
        # Trim the moved array to its finite prefix (pow2-stepped so shapes
        # stay cache-friendly) before the base merge.
        mp = min(_capacity(m), mk.shape[0])
        new_base, new_bdead = _merge_base_jit(
            idx.keys, self.base_dead, mk[:mp], cap_out=cap_new,
            has_dead=self.base_dead_count > 0)

        # Re-index the rebuilt leaves over the merged base (capacity pads
        # route to the dump bucket and drop out of every segment op).  The
        # fit only sees the finite prefix — sliced at a quantized boundary
        # so the O(n) fit passes skip the capacity padding without
        # multiplying jit cache entries — and runs where builds run.
        sl = min(cap_new, -(-self.base_n // 8192) * 8192)
        want_reuse = self.reuse_on_rebuild if self.reuse_on_rebuild \
            is not None else idx.leaf_kind != "linear"
        fit = _on_host(
            _refit, idx, new_base[:sl], self.route_n, kind=idx.leaf_kind,
            pool=self.pool if want_reuse else None, paper_bounds=False,
            train_steps=self.build_kwargs.get("train_steps", 300),
            refit_mask=rmask, sorted_buckets=idx.root_kind == "linear")

        # Position accounting for untouched leaves: with a monotone (linear)
        # root every base key right of a rebuilt leaf shifts by exactly the
        # number of keys merged left of it — fold the shift into the model
        # intercepts, bounds stay tight.  A non-monotone (MLP) root only
        # bounds the shift by m, so widen instead (paper §4's "+1 per
        # insert", batched).
        shift = jnp.asarray(np.concatenate([[0.0], np.cumsum(mcnt)[:-1]]))
        widen = 0.0 if idx.root_kind == "linear" else float(m)
        leaves, err_lo, err_hi, reused, sim, budget = _compose_rebuild_jit(
            idx.leaves, idx.err_lo, idx.err_hi, idx.reused_mask,
            idx.leaf_sim, fit.leaves, fit.err_lo, fit.err_hi, fit.reused,
            fit.sim, fit.count, rmask, shift, jnp.float64(widen),
            jnp.float64(self.eps), leaf_kind=idx.leaf_kind)
        self.index = replace(
            idx, keys=new_base, leaves=leaves, err_lo=err_lo, err_hi=err_hi,
            reused_mask=reused, leaf_sim=sim,
            _iters=None, _packed=None, _f32_exact=None)

        # Incremental clamped depth: update only the touched width rows.
        if widen:
            self._win[~rmask_np] += 2.0 * widen
        err_np = np.asarray(jnp.stack([fit.err_lo, fit.err_hi]))
        self._win[leaf_ids] = window_widths(
            err_np[0, leaf_ids], err_np[1, leaf_ids])
        self.index._iters = clamped_depth(self._win, cap_new)

        self.base_dead = new_bdead
        self.base_psum = jnp.zeros((cap_new + 1,), jnp.int32) \
            if self.base_dead_count == 0 else _psum(new_bdead)

        # Lemma 4.1: fresh budgets for the rebuilt leaves (sim = 1 - dist on
        # a pool hit, 1 on a fresh fit).
        self.budget[leaf_ids] = np.asarray(budget)[leaf_ids]
        self.n_inserts[leaf_ids] = 0

    # -- drift-triggered hot swap ------------------------------------------
    def maybe_swap(self, leaf_ids=None) -> int:
        """Attempt an Algorithm-1 pool hot-swap on ``leaf_ids`` (default:
        every leaf with buffered inserts): one fused jit selects, adapts,
        bound-checks, and commits per-leaf — see ``core.drift`` for the
        commit gate.  Returns the number of leaves swapped.  Requires a
        monotone (linear) root and a kind-matched pool; otherwise (or with
        drift monitoring off) it is a no-op and callers fall through to
        the ordinary refit path."""
        idx = self.index
        if (self.drift is None or self.pool is None
                or self.pool.kind != idx.leaf_kind
                or idx.root_kind != "linear"):
            return 0
        if leaf_ids is None:
            # Maintenance-style call (facade / serve idle window).
            # Proactive swaps only fire when the drift latch is set,
            # mirroring the sharded pass; explicit leaf_ids (tests,
            # targeted callers) skip the gate — the caller already
            # decided to attempt.
            swaps = 0
            if bool(self.drift.drifted):
                # Only at-risk leaves: pressure within a quarter
                # Lemma-4.1 budget of forcing a merge.  A committed swap
                # resets their budget from the pool fit; swapping
                # low-pressure leaves would only shrink budgets (pool
                # sim < fresh-fit sim) and churn the packed tables.
                at_risk = np.flatnonzero(
                    self.n_inserts >= np.maximum(self.budget * 0.25, 1.0))
                if at_risk.size:
                    swaps = self.maybe_swap(at_risk)
            # Deferred-refit sweep (latched or not): ``insert_batch`` in
            # swap mode leaves budget-exhausted leaves for this idle
            # window — leaves a swap could not absorb (bound-check
            # reject, or no drift latch) take the ordinary refit here,
            # off the insert path.
            over = np.flatnonzero(self.n_inserts > self.budget)
            if over.size:
                self._rebuild_leaves(over)
            return swaps
        leaf_ids = np.asarray(leaf_ids, np.int64).ravel()
        if leaf_ids.size == 0:
            return 0
        if self.pool.sel_a is None:
            self.pool._refresh_tables()
        rp = 1 << max(int(leaf_ids.size) - 1, 0).bit_length()
        pad_ids = np.concatenate(
            [leaf_ids, np.full(rp - leaf_ids.size, leaf_ids[0])])
        cap = idx.keys.shape[0]
        sl = min(cap, -(-self.base_n // 8192) * 8192)
        rc = self._swap_route
        if rc is None or rc[0] is not idx.keys or rc[1] != sl:
            base = idx.keys[:sl]
            buckets = _routed_buckets(idx.root_kind, idx.root, base,
                                      idx.n_leaves, self.route_n)
            self._swap_route = rc = (idx.keys, sl, base, buckets)
        base, buckets = rc[2], rc[3]
        out = drift_mod.swap_leaves_jit(
            base, buckets, self.delta_keys, self.delta_leaf,
            jnp.asarray(pad_ids.astype(np.int32)),
            idx.leaves, idx.err_lo, idx.err_hi, idx.leaf_sim,
            idx.reused_mask, self.pool.sel_a, self.pool.sel_ps,
            self.pool.params, self.pool.domains,
            jnp.asarray(self.n_inserts[pad_ids], jnp.float64),
            jnp.float64(float(self._win.max())), jnp.float64(self.eps),
            leaf_kind=idx.leaf_kind, m=self.pool.m, n_leaves=idx.n_leaves)
        leaves, err_lo, err_hi, sim, reused, commit, nbud, nw, _ = out
        # One maintenance-path sync of the commit verdicts; the table
        # writes themselves already happened asynchronously on device.
        commit_np = np.asarray(commit)[:leaf_ids.size]
        nc = int(commit_np.sum())
        self.swap_rejects += int(leaf_ids.size) - nc
        if nc == 0:
            return 0
        self.index = replace(idx, leaves=leaves, err_lo=err_lo,
                             err_hi=err_hi, leaf_sim=sim,
                             reused_mask=reused, _packed=None)
        # Commit gate bounds every new window by the current width cap, so
        # the clamped search depth — and every jit keyed on it — is
        # untouched: zero retraces across swap commits.
        cid = leaf_ids[commit_np]
        self.budget[cid] = np.asarray(nbud)[:leaf_ids.size][commit_np]
        self._win[cid] = np.asarray(nw)[:leaf_ids.size][commit_np]
        # The committed window covers the leaf's buffered inserts (that is
        # what the bound check verified), so the swap starts a fresh
        # budget epoch: pending pressure is paid for, the new budget
        # meters future inserts.  Without this, pressure accumulates
        # across swaps and every leaf still ends in a merge.
        self.n_inserts[cid] = 0
        self.swaps_committed += nc
        self.pool.reuse_count += nc
        return nc

    # -- queries -----------------------------------------------------------
    @property
    def f32_exact(self) -> bool:
        """Both tiers round-trip through f32 (kernel-path precondition)."""
        if self._delta_f32 is None:
            d32 = self.delta_keys.astype(jnp.float32).astype(jnp.float64)
            self._delta_f32 = bool(jnp.all(d32 == self.delta_keys))
        return self.index.f32_exact and self._delta_f32

    def find(self, queries: Array, *, path: str = "auto",
             use_kernel: bool | None = None) -> tuple[Array, Array]:
        """(found, rank) per query. ``found`` is True iff a live (non-
        tombstoned) copy of the key exists in either tier; ``rank`` counts
        live keys < q across both tiers.  ``path`` selects the execution
        path (``core.paths.resolve_path``, same policy as ``rmi.lookup``);
        ``use_kernel`` is the deprecated bool shim."""
        idx = self.index
        q = jnp.asarray(queries, jnp.float64)
        if resolve_path(path, f32_exact=lambda: self.f32_exact,
                        use_kernel=use_kernel):
            from ..kernels import ops as kernel_ops
            root, mat, vec = idx.packed_tables()
            return kernel_ops.dynamic_find(
                q, root, mat, vec, idx.keys, self.base_dead, self.base_psum,
                self.delta_keys, self.delta_dead, self.delta_psum,
                n_leaves=idx.n_leaves, route_n=self.route_n,
                root_kind=idx.root_kind, leaf_kind=idx.leaf_kind,
                iters=idx.search_iters)
        found, rank, _ = _find_jit(
            idx.root, idx.leaves, idx.err_lo, idx.err_hi, idx.keys,
            self.base_dead, self.base_psum, self.delta_keys, self.delta_dead,
            self.delta_psum, q, root_kind=idx.root_kind,
            leaf_kind=idx.leaf_kind, n_leaves=idx.n_leaves,
            route_n=self.route_n, iters=idx.search_iters)
        return found, rank

    def find_range(self, q_lo: Array, q_hi: Array, *, path: str = "auto",
                   use_kernel: bool | None = None) -> tuple[Array, Array]:
        """(rank_lo, rank_hi) live ranks of the inclusive key ranges
        ``[q_lo[i], q_hi[i]]``: rank_lo is the leftmost live rank of q_lo,
        rank_hi the rightmost live rank of q_hi (duplicates included,
        tombstones excluded), so ``live_keys()[rank_lo:rank_hi]`` is
        exactly the range's content (:meth:`gather_range`).  rank_hi is
        clamped to rank_lo — degenerate ranges come back empty, never
        negative-width.  Path selection matches :meth:`find`."""
        idx = self.index
        ql = jnp.asarray(q_lo, jnp.float64)
        qh = jnp.asarray(q_hi, jnp.float64)
        if resolve_path(path, f32_exact=lambda: self.f32_exact,
                        use_kernel=use_kernel):
            from ..kernels import ops as kernel_ops
            root, mat, vec = idx.packed_tables()
            return kernel_ops.range_lookup(
                ql, qh, root, mat, vec, idx.keys, self.base_dead,
                self.base_psum, self.delta_keys, self.delta_dead,
                self.delta_psum, n_leaves=idx.n_leaves, route_n=self.route_n,
                root_kind=idx.root_kind, leaf_kind=idx.leaf_kind,
                iters=idx.search_iters)
        return _range_find_jit(
            idx.root, idx.leaves, idx.err_lo, idx.err_hi, idx.keys,
            self.base_dead, self.base_psum, self.delta_keys, self.delta_dead,
            self.delta_psum, ql, qh, root_kind=idx.root_kind,
            leaf_kind=idx.leaf_kind, n_leaves=idx.n_leaves,
            route_n=self.route_n, iters=idx.search_iters)

    def gather_range(self, rank_lo, rank_hi) -> list[np.ndarray]:
        """Materialize :meth:`find_range` spans: per-range sorted live keys
        (host numpy — ``live_keys()`` is computed once and sliced)."""
        live = self.live_keys()
        lo = np.asarray(rank_lo).ravel()
        hi = np.asarray(rank_hi).ravel()
        return [live[int(a):int(b)] for a, b in zip(lo, hi, strict=True)]

    def live_keys(self) -> np.ndarray:
        """Sorted live keys across both tiers (host numpy; ``find``'s rank
        indexes into exactly this array)."""
        bk = np.asarray(self.index.keys)
        bk = bk[np.isfinite(bk) & ~np.asarray(self.base_dead)]
        dk = np.asarray(self.delta_keys)
        dk = dk[np.isfinite(dk) & ~np.asarray(self.delta_dead)]
        return np.sort(np.concatenate([bk, dk]))

    @property
    def total_buffered(self) -> int:
        return int(self.delta_live)

    @property
    def live_count(self) -> int:
        """Live keys across both tiers (what ``find``'s rank indexes) —
        host counters only, no device sync."""
        return self.base_n - self.base_dead_count + self.delta_live

    @property
    def dead_fraction(self) -> float:
        """Tombstoned fraction of all stored (finite) entries — the sharded
        index's rebalance trigger reads this."""
        stored = self.base_n + self.delta_live + self.delta_dead_count
        return (self.base_dead_count + self.delta_dead_count) / max(stored, 1)


# ---------------------------------------------------------------------------
# The seed implementation (host per-leaf Python buffers), kept verbatim as
# the benchmark baseline for BENCH_updates.json before/after rows and as a
# throughput reference.  Known semantic defects (fixed above, retained here
# for fidelity to the measured baseline): find's rank only counts the routed
# leaf's buffer; delete never clears buffered entries; _rebuild_leaf resets
# counters without refitting the leaf model.
# ---------------------------------------------------------------------------
@dataclass
class HostBufferDynamicRMI:
    """Seed DynamicRMI: per-leaf host insert buffers + tombstone set."""
    index: rmi_mod.RMIIndex
    pool: ModelPool | None
    eps: float
    buffers: list[np.ndarray] = field(default_factory=list)     # per leaf
    n_inserts: np.ndarray = None                                # per leaf
    budget: np.ndarray = None                                   # Lemma 4.1
    tombstones: set = field(default_factory=set)
    rebuilds: int = 0
    build_kwargs: dict = field(default_factory=dict)

    @classmethod
    def build(cls, keys, pool=None, eps: float = 0.9, **rmi_kwargs):
        idx = rmi_mod.build_rmi(keys, pool=pool, **rmi_kwargs)
        counts = np.bincount(
            np.asarray(rmi_mod.root_buckets(idx.root_kind, idx.root, idx.keys,
                                            idx.n_leaves, idx.n)),
            minlength=idx.n_leaves)
        budget = np.array(insertion_budget(
            jnp.asarray(idx.leaf_sim), jnp.float64(eps),
            jnp.asarray(counts, jnp.float64)), copy=True)
        return cls(index=idx, pool=pool, eps=eps,
                   buffers=[np.empty((0,)) for _ in range(idx.n_leaves)],
                   n_inserts=np.zeros(idx.n_leaves, np.int64),
                   budget=budget, build_kwargs=rmi_kwargs)

    def insert(self, key: float) -> None:
        idx = self.index
        leaf = int(rmi_mod.root_buckets(idx.root_kind, idx.root,
                                        jnp.asarray([key], jnp.float64),
                                        idx.n_leaves, idx.n)[0])
        buf = self.buffers[leaf]
        self.buffers[leaf] = np.insert(buf, np.searchsorted(buf, key), key)
        self.n_inserts[leaf] += 1
        if self.n_inserts[leaf] > self.budget[leaf]:
            self._rebuild_leaf(leaf)

    def insert_batch(self, keys: np.ndarray) -> None:
        idx = self.index
        leaves = np.asarray(rmi_mod.root_buckets(
            idx.root_kind, idx.root, jnp.asarray(keys, jnp.float64),
            idx.n_leaves, idx.n))
        for leaf in np.unique(leaves):
            ks = keys[leaves == leaf]
            self.buffers[leaf] = np.sort(
                np.concatenate([self.buffers[leaf], ks]))
            self.n_inserts[leaf] += ks.size
            if self.n_inserts[leaf] > self.budget[leaf]:
                self._rebuild_leaf(leaf)

    def delete(self, key: float) -> None:
        self.tombstones.add(float(key))

    def _rebuild_leaf(self, leaf: int) -> None:
        self.rebuilds += 1
        self.n_inserts[leaf] = 0
        idx = self.index
        counts = np.bincount(np.asarray(rmi_mod.root_buckets(
            idx.root_kind, idx.root, idx.keys, idx.n_leaves, idx.n)),
            minlength=idx.n_leaves)
        n_leaf = counts[leaf] + self.buffers[leaf].size
        self.budget[leaf] = float(insertion_budget(
            jnp.float64(1.0), jnp.float64(self.eps), jnp.float64(n_leaf)))

    def find(self, queries: Array) -> tuple[Array, Array]:
        idx = self.index
        q = jnp.asarray(queries, jnp.float64)
        base_pos = rmi_mod.lookup(idx, q)
        leaves = rmi_mod.root_buckets(idx.root_kind, idx.root, q,
                                      idx.n_leaves, idx.n)
        base_hit = (base_pos < idx.n) & \
            (idx.keys[jnp.clip(base_pos, 0, idx.n - 1)] == q)
        qn = np.asarray(q)
        buf_hit = np.zeros(qn.shape, bool)
        buf_rank = np.zeros(qn.shape, np.int64)
        for i, (qq, lf) in enumerate(zip(qn, np.asarray(leaves), strict=True)):
            b = self.buffers[lf]
            j = np.searchsorted(b, qq)
            buf_rank[i] = j
            buf_hit[i] = j < b.size and b[j] == qq
        found = (np.asarray(base_hit) | buf_hit)
        if self.tombstones:
            dead = np.asarray([qq in self.tombstones for qq in qn])
            found &= ~dead
        return jnp.asarray(found), base_pos + jnp.asarray(buf_rank)

    @property
    def total_buffered(self) -> int:
        return int(self.n_inserts.sum())
