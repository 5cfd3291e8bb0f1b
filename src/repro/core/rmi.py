"""Two-layer RMI (Kraska et al. 2018) with optional agile model reuse
(paper §3 "Learned indices with agile model reuse", Fig. 3).

Variants (matching the paper's experiment roster):
  RMI        root + leaves linear, fresh fits          build_rmi(kind="linear")
  RMI-NN     root linear, leaves 1x4 MLP, fresh        build_rmi(kind="mlp")
  RMI-MR     linear leaves, pool reuse                 build_rmi(..., pool=linear_pool)
  RMI-NN-MR  MLP leaves, pool reuse                    build_rmi(..., pool=mlp_pool)

TPU adaptation: every per-leaf operation is batched across ALL leaves —
segment closed-form fits, per-leaf similarity histograms, pool selection,
affine adaptation, residual bounds — so a build is a handful of jit calls
regardless of leaf count, instead of the paper's per-leaf Python loop.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import models
from .adapt import DomainSpec, adapt_linear, adapt_mlp
from .bounds import reuse_err_bounds
from .paths import resolve_path
from .reuse import ModelPool, PoolSelection, select_from_pool_batch

Array = jax.Array


# ---------------------------------------------------------------------------
# Batched per-leaf machinery (shared with RMRT).
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_leaves",))
def leaf_stats(keys: Array, buckets: Array, n_leaves: int):
    """Per-leaf (count, key_min, key_max, pos_min, pos_max) via segment ops."""
    n = keys.shape[0]
    pos = jnp.arange(n, dtype=jnp.float64)
    ones = jnp.ones((n,), jnp.float64)
    count = jax.ops.segment_sum(ones, buckets, n_leaves)
    kmin = jax.ops.segment_min(keys, buckets, n_leaves)
    kmax = jax.ops.segment_max(keys, buckets, n_leaves)
    pmin = jax.ops.segment_min(pos, buckets, n_leaves)
    pmax = jax.ops.segment_max(pos, buckets, n_leaves)
    empty = count == 0
    kmin = jnp.where(empty, 0.0, kmin)
    kmax = jnp.where(empty, 1.0, kmax)
    pmin = jnp.where(empty, 0.0, pmin)
    pmax = jnp.where(empty, 0.0, pmax)
    return count, kmin, kmax, pmin, pmax


@functools.partial(jax.jit, static_argnames=("n_leaves", "m"))
def leaf_histograms(keys: Array, buckets: Array, n_leaves: int, m: int,
                    kmin: Array, kmax: Array) -> Array:
    """(n_leaves, m) leaf-normalized similarity histograms, one bincount."""
    span = jnp.maximum(kmax - kmin, jnp.finfo(jnp.float64).tiny)
    x = (keys - kmin[buckets]) / span[buckets]
    b = jnp.clip(jnp.ceil(x * m).astype(jnp.int32) - 1, 0, m - 1)
    flat = buckets * m + b
    counts = jnp.zeros((n_leaves * m,), jnp.float64).at[flat].add(1.0)
    counts = counts.reshape(n_leaves, m)
    tot = jnp.maximum(counts.sum(1, keepdims=True), 1.0)
    return counts / tot


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def segment_linear_fit(keys: Array, buckets: Array, n_leaves: int):
    """Closed-form least-squares (pos on key) per leaf, all leaves at once.
    jnp oracle for the Pallas kernel in ``repro.kernels.linfit``."""
    n = keys.shape[0]
    x = keys.astype(jnp.float64)
    y = jnp.arange(n, dtype=jnp.float64)
    seg = lambda v: jax.ops.segment_sum(v, buckets, n_leaves)
    cnt, sx, sy = seg(jnp.ones_like(x)), seg(x), seg(y)
    sxx, sxy = seg(x * x), seg(x * y)
    denom = cnt * sxx - sx * sx
    a = jnp.where(jnp.abs(denom) > 1e-30, (cnt * sxy - sx * sy) / denom, 0.0)
    b = jnp.where(cnt > 0, (sy - a * sx) / jnp.maximum(cnt, 1.0), 0.0)
    return models.LinearParams(a=a, b=b)


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def segment_residual_bounds(pred: Array, buckets: Array, n_leaves: int):
    """Per-leaf (min, max) of (true position - prediction), batched."""
    n = pred.shape[0]
    r = jnp.arange(n, dtype=jnp.float64) - pred
    lo = jax.ops.segment_min(r, buckets, n_leaves)
    hi = jax.ops.segment_max(r, buckets, n_leaves)
    cnt = jax.ops.segment_sum(jnp.ones((n,)), buckets, n_leaves)
    lo = jnp.where(cnt > 0, lo, 0.0)
    hi = jnp.where(cnt > 0, hi, 0.0)
    return lo, hi


# ---------------------------------------------------------------------------
# Sorted-bucket fast paths.  XLA's CPU scatters make jax.ops.segment_* cost
# ~20ms per op at 10^5 keys — far too slow for the dynamic-update rebuild
# path, which runs these per insert batch.  With a *monotone* (linear) root
# the bucket array over sorted keys is itself sorted, so every per-leaf
# reduction has a scatter-free form: boundaries via searchsorted, sums via
# cumulative-sum differences, min/max via a segmented associative scan.
# Out-of-range buckets (the dynamic index's +inf capacity padding routes to
# the dump bucket ``n_leaves``) sort to the tail and drop out naturally.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_leaves",))
def leaf_stats_sorted(keys: Array, buckets: Array, n_leaves: int):
    """:func:`leaf_stats` for non-decreasing ``buckets`` (no scatters)."""
    n = keys.shape[0]
    lid = jnp.arange(n_leaves)
    start = jnp.searchsorted(buckets, lid, side="left")
    end = jnp.searchsorted(buckets, lid, side="right")
    count = (end - start).astype(jnp.float64)
    empty = count == 0
    s = jnp.clip(start, 0, n - 1)
    e = jnp.clip(end - 1, 0, n - 1)
    kmin = jnp.where(empty, 0.0, keys[s])
    kmax = jnp.where(empty, 1.0, keys[e])
    pmin = jnp.where(empty, 0.0, start.astype(jnp.float64))
    pmax = jnp.where(empty, 0.0, e.astype(jnp.float64))
    return count, kmin, kmax, pmin, pmax


def _segsum(v: Array, start: Array, end: Array) -> Array:
    """Per-leaf sums of ``v`` over contiguous [start, end) ranges via one
    cumulative sum (exclusive prefix, diff at the boundaries)."""
    c = jnp.concatenate([jnp.zeros((1,), v.dtype), jnp.cumsum(v)])
    return c[end] - c[start]


@functools.partial(jax.jit, static_argnames=("m",))
def leaf_histograms_ranges(keys: Array, buckets: Array, rid: Array, m: int,
                           kmin: Array, kmax: Array) -> Array:
    """:func:`leaf_histograms` for a compacted subset of leaves (rows
    ``rid``), non-decreasing ``buckets``: per-leaf bin populations via
    searchsorted at the bin edges — cost scales with R*m, not n.  Same
    right-closed binning as the scatter version."""
    start = jnp.searchsorted(buckets, rid, side="left")
    end = jnp.searchsorted(buckets, rid, side="right")
    span = jnp.maximum(kmax - kmin, jnp.finfo(jnp.float64).tiny)
    frac = jnp.arange(1, m, dtype=jnp.float64) / m
    edges = kmin[:, None] + span[:, None] * frac[None, :]
    pos = jnp.searchsorted(keys, edges.reshape(-1), side="right") \
        .reshape(rid.shape[0], m - 1)
    pos = jnp.clip(pos, start[:, None], end[:, None])
    bounds = jnp.concatenate([start[:, None], pos, end[:, None]], 1)
    counts = (bounds[:, 1:] - bounds[:, :-1]).astype(jnp.float64)
    tot = jnp.maximum(counts.sum(1, keepdims=True), 1.0)
    return counts / tot


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def segment_linear_fit_sorted(keys: Array, buckets: Array, n_leaves: int):
    """:func:`segment_linear_fit` for non-decreasing ``buckets``: two-pass
    cumsum-diff moments (pass 1 per-leaf means, pass 2 centered products —
    the same centering the Pallas linfit wrapper uses for stability).
    Non-finite keys (capacity padding) contribute zero to every moment."""
    n = keys.shape[0]
    lid = jnp.arange(n_leaves)
    start = jnp.searchsorted(buckets, lid, side="left")
    end = jnp.searchsorted(buckets, lid, side="right")
    finite = jnp.isfinite(keys)
    x = jnp.where(finite, keys.astype(jnp.float64), 0.0)
    y = jnp.arange(n, dtype=jnp.float64)
    cnt = (end - start).astype(jnp.float64)
    nn = jnp.maximum(cnt, 1.0)
    mx = _segsum(x, start, end) / nn
    # y is consecutive positions: its per-leaf mean is closed-form.
    my = (start + end - 1).astype(jnp.float64) / 2.0
    bc = jnp.clip(buckets, 0, n_leaves - 1)
    xc = jnp.where(finite, x - mx[bc], 0.0)
    yc = jnp.where(finite, y - my[bc], 0.0)
    sxy = _segsum(xc * yc, start, end)
    sxx = _segsum(xc * xc, start, end)
    a = jnp.where(jnp.abs(sxx) > 1e-30, sxy / sxx, 0.0)
    b = jnp.where(cnt > 0, my - a * mx, 0.0)
    return models.LinearParams(a=a, b=b)


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def segment_residual_bounds_sorted(pred: Array, buckets: Array,
                                   n_leaves: int):
    """:func:`segment_residual_bounds` for non-decreasing ``buckets``:
    segmented min/max via one associative scan each (flag-reset combine),
    gathered at each leaf's last element."""
    n = pred.shape[0]
    r = jnp.arange(n, dtype=jnp.float64) - pred
    first = jnp.concatenate(
        [jnp.ones((1,), bool), buckets[1:] != buckets[:-1]])

    def combine(a, b):
        mn = jnp.where(b[2], b[0], jnp.minimum(a[0], b[0]))
        mx = jnp.where(b[2], b[1], jnp.maximum(a[1], b[1]))
        return mn, mx, a[2] | b[2]

    run_min, run_max, _ = jax.lax.associative_scan(combine, (r, r, first))
    lid = jnp.arange(n_leaves)
    end = jnp.searchsorted(buckets, lid, side="right")
    empty = jnp.searchsorted(buckets, lid, side="left") == end
    e = jnp.clip(end - 1, 0, n - 1)
    return (jnp.where(empty, 0.0, run_min[e]),
            jnp.where(empty, 0.0, run_max[e]))


# ---------------------------------------------------------------------------
# The index structure.
# ---------------------------------------------------------------------------
@dataclass
class RMIIndex:
    keys: Array                      # (n,) sorted
    root_kind: str                   # "linear" | "mlp"
    root: models.LinearParams | models.MLPParams
    leaf_kind: str
    leaves: models.LinearParams | models.MLPParams   # stacked (B, ...)
    err_lo: Array                    # (B,)
    err_hi: Array                    # (B,)
    n_leaves: int
    # provenance / reuse accounting (build-time diagnostics)
    reused_mask: Array               # (B,) bool
    leaf_sim: Array                  # (B,) build-time similarity (Lemma 4.1 input)
    # lazily-derived serving state (host-side caches, not build outputs)
    _iters: int | None = None        # error-window search depth
    _packed: tuple | None = None     # (root, mat, vec) kernel tables
    _f32_exact: bool | None = None   # keys round-trip through f32

    @property
    def n(self) -> int:
        return int(self.keys.shape[0])

    @property
    def reuse_fraction(self) -> float:
        return float(jnp.mean(self.reused_mask.astype(jnp.float64)))

    @property
    def search_iters(self) -> int:
        """Static per-query search depth bounded by the error window (§4)."""
        if self._iters is None:
            from ..kernels.lookup import search_iters
            self._iters = search_iters(self.err_lo, self.err_hi, self.n)
        return self._iters

    @property
    def f32_exact(self) -> bool:
        """True when every key round-trips through f32 — the precondition
        for the Pallas kernel path, which searches (and seam-verifies) in
        f32: distinct f64 keys that collide in f32 would resolve to wrong
        positions undetectably."""
        if self._f32_exact is None:
            k32 = self.keys.astype(jnp.float32).astype(jnp.float64)
            self._f32_exact = bool(jnp.all(k32 == self.keys))
        return self._f32_exact

    def packed_tables(self) -> tuple:
        """(root, mat, vec) VMEM-layout tables for the fused Pallas kernel."""
        if self._packed is None:
            from ..kernels import lookup as _lk
            root = _lk.pack_root(self.root_kind, self.root)
            w1, b1, w2, b2 = _leaf_table_arrays(self.leaf_kind, self.leaves,
                                                self.n_leaves)
            mat, vec = _lk.pack_leaves(w1, b1, w2, b2, self.err_lo,
                                       self.err_hi)
            self._packed = (root, mat, vec)
        return self._packed


def _leaf_table_arrays(kind: str, leaves, n_leaves: int):
    """Uniform (L, H)/(L,) leaf tables for either leaf kind (linear models
    ride in w1[:, 0] / b2, mirroring the kernel's linear fast path)."""
    if kind == "linear":
        w1 = jnp.zeros((n_leaves, models.HIDDEN),
                       jnp.float32).at[:, 0].set(leaves.a.astype(jnp.float32))
        zeros = jnp.zeros((n_leaves, models.HIDDEN), jnp.float32)
        return w1, zeros, zeros, leaves.b
    return leaves.w1, leaves.b1, leaves.w2, leaves.b2


def _root_predict(kind, params, keys):
    return (models.linear_predict if kind == "linear"
            else models.mlp_predict)(params, keys)


@functools.partial(jax.jit, static_argnames=("kind", "n_leaves", "n"))
def root_buckets(kind: str, params, keys: Array, n_leaves: int, n: int) -> Array:
    pred = _root_predict(kind, params, keys)
    return jnp.clip((pred * n_leaves / n).astype(jnp.int32), 0, n_leaves - 1)


class LeafFit(NamedTuple):
    """Batched per-leaf fit result (all leaves; see :func:`fit_leaves`)."""
    leaves: Any          # stacked params, (L, ...) per field
    reused: Array        # (L,) bool — Algorithm 1 pool hit
    err_lo: Array        # (L,) sound bounds (sentinel window on empty leaves)
    err_hi: Array        # (L,)
    sim: Array           # (L,) build-time similarity (Lemma 4.1 input)
    count: Array         # (L,) member counts


def fit_leaves(
    keys: Array,
    buckets: Array,
    n_leaves: int,
    kind: str = "linear",
    pool: Optional[ModelPool] = None,
    paper_bounds: bool = False,
    train_steps: int = 300,
    seed: int = 0,
    refit_mask=None,
    sorted_buckets: bool = False,
) -> LeafFit:
    """Fit every leaf of an RMI layer in a handful of batched jit calls:
    Algorithm-1 pool reuse first (batched selection + affine adaptation),
    fresh fits on the misses, residual bounds in one batched predict.

    Shared by :func:`build_rmi` (all leaves) and the dynamic-update rebuild
    path (``core.updates.DynamicRMI._rebuild_leaves``), which passes
    ``refit_mask`` to restrict *training* cost to the leaves being rebuilt —
    rows outside the mask are still populated (one cheap segment fit) but
    callers keep their existing models for them. A ``pool`` whose kind does
    not match ``kind`` is ignored (cross-kind params cannot be merged).
    ``sorted_buckets`` (sound only for a monotone root, i.e. linear) selects
    the scatter-free segment reductions above.
    """
    stats = leaf_stats_sorted if sorted_buckets else leaf_stats
    count, kmin, kmax, pmin, pmax = stats(keys, buckets, n_leaves)
    if pool is not None and pool.kind != kind:
        pool = None
    if pool is not None:
        if pool.sel_a is None:
            pool._refresh_tables()
        if refit_mask is not None and sorted_buckets:
            # Rebuild path: Algorithm-1 selection only for the leaves being
            # re-indexed — histograms via per-range searchsorted and a
            # compacted (pow2-padded) selection batch, scattered back.
            import numpy as np
            rid = np.flatnonzero(np.asarray(refit_mask))
            rp = 1 << max(int(rid.size) - 1, 0).bit_length()
            rid_p = jnp.asarray(np.concatenate(
                [rid, np.full(rp - rid.size, rid[0] if rid.size else 0)])
                .astype(np.int32))
            sel = _select_compact_jit(keys, buckets, rid_p, kmin, kmax,
                                      pool.sel_a, pool.sel_ps,
                                      jnp.float32(pool.eps), m=pool.m,
                                      n_leaves=n_leaves)
        else:
            hists = leaf_histograms(keys, buckets, n_leaves, pool.m, kmin,
                                    kmax)
            sel = select_from_pool_batch(pool.sel_a, pool.sel_ps, hists,
                                         jnp.float32(pool.eps))
        found = sel.found & (count > 1)
        if refit_mask is not None:
            found = found & refit_mask
    else:
        found = jnp.zeros((n_leaves,), bool)

    # ---- fresh fits for missing leaves (batched over all leaves) ---------
    if kind == "linear":
        fit_fn = segment_linear_fit_sorted if sorted_buckets \
            else segment_linear_fit
        fresh = fit_fn(keys, buckets, n_leaves)
    else:
        skip = None
        if pool is not None or refit_mask is not None:
            skip = found if refit_mask is None else found | ~refit_mask
        fresh = _batched_leaf_mlp(keys, buckets, n_leaves, count, kmin, kmax,
                                  pmin, train_steps, seed, skip_mask=skip)

    # ---- merge reused + fresh, derive bounds (one fused jit) --------------
    if pool is not None:
        leaves, err_lo, err_hi, sim = _pool_merge_measure_jit(
            keys, buckets, fresh, found, sel.index, sel.dist, pool.params,
            pool.domains, pool.err_lo, pool.err_hi, count, kmin, kmax, pmin,
            pmax, kind=kind, n_leaves=n_leaves, paper_bounds=paper_bounds,
            sorted_buckets=sorted_buckets)
    else:
        leaves = fresh
        err_lo, err_hi = _measure_bounds_jit(
            keys, buckets, fresh, count, kind=kind, n_leaves=n_leaves,
            sorted_buckets=sorted_buckets)
        sim = jnp.ones((n_leaves,), jnp.float64)
    return LeafFit(leaves=leaves, reused=found, err_lo=err_lo, err_hi=err_hi,
                   sim=sim, count=count)


@functools.partial(jax.jit, static_argnames=("m", "n_leaves"))
def _select_compact_jit(keys, buckets, rid_p, kmin, kmax, sel_a, sel_ps,
                        eps, *, m: int, n_leaves: int):
    """Compacted Algorithm-1 selection (rebuild path): range histograms +
    fused selection for the padded leaf-row batch, scattered back to full
    (L,) selection arrays — one dispatch.  Padding rows repeat a real leaf
    id, so they scatter an identical value onto that row (harmless) and the
    true row count never enters the jit cache key."""
    hist_c = leaf_histograms_ranges(keys, buckets, rid_p, m,
                                    kmin[rid_p], kmax[rid_p])
    sel_c = select_from_pool_batch(sel_a, sel_ps, hist_c, eps)
    return PoolSelection(
        found=jnp.zeros((n_leaves,), bool).at[rid_p].set(sel_c.found),
        index=jnp.zeros((n_leaves,), jnp.int32).at[rid_p].set(sel_c.index),
        dist=jnp.zeros((n_leaves,), jnp.float64).at[rid_p].set(sel_c.dist))


def _sentinel_bounds(err_lo, err_hi, count, n: int):
    """Empty leaves are reachable by out-of-distribution queries: give them
    a sound full-array window (plain binary search fallback)."""
    return (jnp.where(count > 0, err_lo, -float(n)),
            jnp.where(count > 0, err_hi, float(n)))


@functools.partial(jax.jit, static_argnames=("kind", "n_leaves",
                                             "sorted_buckets"))
def _measure_bounds_jit(keys, buckets, leaves, count, *, kind: str,
                        n_leaves: int, sorted_buckets: bool):
    pred = _leaf_predict_all(kind, leaves, keys, buckets)
    bounds_fn = segment_residual_bounds_sorted if sorted_buckets \
        else segment_residual_bounds
    meas_lo, meas_hi = bounds_fn(pred, buckets, n_leaves)
    return _sentinel_bounds(meas_lo, meas_hi, count, keys.shape[0])


@functools.partial(jax.jit, static_argnames=("kind", "n_leaves",
                                             "paper_bounds",
                                             "sorted_buckets"))
def _pool_merge_measure_jit(keys, buckets, fresh, found, sel_index, sel_dist,
                            p_params, p_domains, p_errlo, p_errhi, count,
                            kmin, kmax, pmin, pmax, *, kind: str,
                            n_leaves: int, paper_bounds: bool,
                            sorted_buckets: bool):
    """Adapt the selected pool models (Lemma 3.2 folds), merge with the
    fresh fits, measure residual bounds — the whole tail of fit_leaves in
    one jit (it used to be ~100 eager dispatches on the rebuild path)."""
    src = jax.tree.map(lambda a: a[sel_index], p_domains)
    tgt = DomainSpec(x_start=kmin, x_end=jnp.where(kmax > kmin, kmax, kmin + 1.0),
                     y_start=pmin, y_end=jnp.maximum(pmax, pmin + 1.0))
    pool_params = jax.tree.map(lambda a: a[sel_index], p_params)
    adapt = adapt_linear if kind == "linear" else adapt_mlp
    adapted = jax.vmap(adapt)(pool_params, src, tgt)
    merge = lambda a, f: jnp.where(
        jnp.expand_dims(found, tuple(range(1, a.ndim))), a, f)
    leaves = jax.tree.map(merge, adapted, fresh)

    pred = _leaf_predict_all(kind, leaves, keys, buckets)
    bounds_fn = segment_residual_bounds_sorted if sorted_buckets \
        else segment_residual_bounds
    meas_lo, meas_hi = bounds_fn(pred, buckets, n_leaves)
    if paper_bounds:
        s_dy = (tgt.y_end - tgt.y_start) / (src.y_end - src.y_start)
        thm_lo, thm_hi = reuse_err_bounds(p_errlo[sel_index],
                                          p_errhi[sel_index],
                                          sel_dist, count, s_dy)
        err_lo = jnp.where(found, thm_lo, meas_lo)
        err_hi = jnp.where(found, thm_hi, meas_hi)
    else:
        err_lo, err_hi = meas_lo, meas_hi
    err_lo, err_hi = _sentinel_bounds(err_lo, err_hi, count, keys.shape[0])
    sim = jnp.where(found, 1.0 - sel_dist, 1.0)
    return leaves, err_lo, err_hi, sim


def build_rmi(
    keys: Array,
    n_leaves: int = 1024,
    kind: str = "linear",
    root_kind: str = "linear",
    pool: Optional[ModelPool] = None,
    paper_bounds: bool = False,
    train_steps: int = 300,
    root_subsample: int = 1 << 16,
    seed: int = 0,
) -> RMIIndex:
    """Build a two-layer RMI over a sorted key array.

    With ``pool`` given, every leaf first attempts agile model reuse
    (batched Algorithm 1 across all leaves); only missing leaves are trained.
    ``paper_bounds`` selects Theorem 3.3 bounds verbatim; the default also
    measures residuals (sound and tighter; one batched predict).
    """
    keys = jnp.asarray(keys, jnp.float64)
    n = keys.shape[0]
    if n == 0:
        # Empty partition: sharded builds produce empty shards when n is
        # smaller than the shard count or when equal-count boundaries snap
        # to duplicate-run edges (core.distributed.shard_bounds).  Return a
        # trivial index with zero models and one-slot error windows: every
        # key slot a consumer pads in is +inf, so any finite query resolves
        # to position 0 and seam verification never fires.  Shapes match a
        # real build exactly, so per-shard stacking stays uniform.
        if root_kind != "linear":
            raise ValueError("build_rmi on an empty key array requires a "
                             "linear root (nothing to train an MLP root on)")
        zero = jnp.zeros((), jnp.float64)
        if kind == "linear":
            leaves = models.LinearParams(a=jnp.zeros((n_leaves,), jnp.float64),
                                         b=jnp.zeros((n_leaves,), jnp.float64))
        else:
            leaves = jax.tree.map(
                lambda a: jnp.zeros((n_leaves,) + a.shape, jnp.float64),
                models.mlp_init(jax.random.PRNGKey(0)))
        ones = jnp.ones((n_leaves,), jnp.float64)
        return RMIIndex(keys=keys, root_kind=root_kind,
                        root=models.LinearParams(a=zero, b=zero),
                        leaf_kind=kind, leaves=leaves,
                        err_lo=-ones, err_hi=ones, n_leaves=n_leaves,
                        reused_mask=jnp.zeros((n_leaves,), bool),
                        leaf_sim=ones)
    pos = jnp.arange(n, dtype=jnp.float64)

    # ---- root -----------------------------------------------------------
    if root_kind == "linear":
        root = models.linear_fit(keys, pos)
    else:
        stride = max(1, n // root_subsample)
        sub, subpos = keys[::stride], pos[::stride]
        norm = (sub - keys[0]) / (keys[-1] - keys[0])
        p = models.mlp_train(jax.random.PRNGKey(seed), norm, subpos,
                             steps=train_steps)
        span = keys[-1] - keys[0]
        root = models.MLPParams(w1=p.w1 / span, b1=p.b1 - p.w1 * keys[0] / span,
                                w2=p.w2, b2=p.b2)
    buckets = root_buckets(root_kind, root, keys, n_leaves, n)

    fit = fit_leaves(keys, buckets, n_leaves, kind=kind, pool=pool,
                     paper_bounds=paper_bounds, train_steps=train_steps,
                     seed=seed, sorted_buckets=root_kind == "linear")
    return RMIIndex(keys=keys, root_kind=root_kind, root=root, leaf_kind=kind,
                    leaves=fit.leaves, err_lo=fit.err_lo, err_hi=fit.err_hi,
                    n_leaves=n_leaves, reused_mask=fit.reused,
                    leaf_sim=fit.sim)


def _batched_leaf_mlp(keys, buckets, n_leaves, count, kmin, kmax, pmin,
                      train_steps: int, seed: int, skip_mask=None):
    """Train leaf MLPs, batched. With ``skip_mask`` (reused leaves), only the
    *missing* leaves are compacted into the training batch — this is where
    agile reuse actually saves build time. Host wrapper: padding capacity and
    compaction are data-dependent, so they are materialized here and passed
    static to the jitted trainer (sizes rounded to powers of two to keep the
    jit cache small)."""
    import numpy as np

    def _pow2(v):
        return 1 << max(int(v) - 1, 1).bit_length()

    if skip_mask is None:
        miss = np.arange(n_leaves)
    else:
        miss = np.where(~np.asarray(skip_mask))[0]
    zero = jax.tree.map(
        lambda a: jnp.zeros((n_leaves,) + a.shape, jnp.float64),
        models.mlp_init(jax.random.PRNGKey(0)))
    if miss.size == 0:
        return zero
    K = _pow2(miss.size)
    # Dense leaves are *subsampled* to TRAIN_CAP points for training — a
    # 13-parameter model doesn't need 30k points, and error bounds are
    # measured on the full data afterwards, so correctness is unaffected.
    # This bounds the padded batch at (K, TRAIN_CAP) regardless of skew.
    TRAIN_CAP = 1024
    cap = min(_pow2(max(int(jnp.max(count[miss])), 2)), TRAIN_CAP)
    # Remap buckets: missing leaf -> compact slot; others -> dump slot K.
    slot_of = np.full((n_leaves,), K, np.int32)
    slot_of[miss] = np.arange(miss.size, dtype=np.int32)
    take = lambda a: jnp.concatenate(
        [a[miss], jnp.zeros((K + 1 - miss.size,), a.dtype)])
    p = _padded_leaf_mlp_train(
        keys, jnp.asarray(slot_of)[buckets], K + 1, cap,
        take(kmin), take(jnp.where(kmax > kmin, kmax, kmin + 1.0)),
        take(pmin), take(count), train_steps, seed)
    scat = lambda z, t: z.at[jnp.asarray(miss)].set(t[:miss.size])
    return jax.tree.map(scat, zero, p)


@functools.partial(jax.jit,
                   static_argnames=("n_leaves", "cap", "train_steps", "seed"))
def _padded_leaf_mlp_train(keys, buckets, n_leaves: int, cap: int,
                           kmin, kmax, pmin, count, train_steps: int,
                           seed: int):
    n = keys.shape[0]
    pos = jnp.arange(n, dtype=jnp.float64)
    # Exact within-leaf rank (cumcount) — correct even for non-monotone MLP
    # roots where a leaf's members are not a contiguous key range.
    order = jnp.argsort(buckets, stable=True)
    sb = buckets[order]
    run_start = jnp.searchsorted(sb, jnp.arange(n_leaves))
    offs_sorted = jnp.arange(n, dtype=jnp.int32) - run_start[sb].astype(jnp.int32)
    offs = jnp.zeros((n,), jnp.int32).at[order].set(offs_sorted)
    # Decimate leaves bigger than cap: slot = offs * cap / count (collisions
    # overwrite — still ~cap near-uniformly spaced training points).
    cnt_b = jnp.maximum(count[buckets], 1.0)
    slot = jnp.where(cnt_b > cap,
                     (offs.astype(jnp.float64) * cap / cnt_b).astype(jnp.int32),
                     offs)
    flat = buckets * cap + jnp.clip(slot, 0, cap - 1)
    span = jnp.where(kmax > kmin, kmax - kmin, 1.0)  # single-key leaf guard
    xn = (keys - kmin[buckets]) / span[buckets]              # leaf-normalized
    X = jnp.zeros((n_leaves * cap,), jnp.float64).at[flat].set(xn)
    Y = jnp.zeros((n_leaves * cap,), jnp.float64).at[flat].set(pos)
    M = jnp.zeros((n_leaves * cap,), jnp.float64).at[flat].set(1.0)
    X, Y, M = (v.reshape(n_leaves, cap) for v in (X, Y, M))
    rng = jax.random.split(jax.random.PRNGKey(seed), n_leaves)
    p = jax.vmap(lambda k, x, y, m: models.mlp_train(
        k, x, y, steps=train_steps, mask=m))(rng, X, Y, M)
    # Fold leaf normalization so leaves consume raw keys like pool models do.
    return models.MLPParams(
        w1=p.w1 / span[:, None],
        b1=p.b1 - p.w1 * (kmin / span)[:, None],
        w2=p.w2, b2=p.b2)


@functools.partial(jax.jit, static_argnames=("kind",))
def _leaf_predict_all(kind: str, leaves, keys: Array, buckets: Array) -> Array:
    """Predict every key with its own leaf's model (gather params, elementwise)."""
    p = jax.tree.map(lambda a: a[buckets], leaves)
    if kind == "linear":
        return models.linear_predict(p, keys)
    h = jax.nn.relu(keys[:, None] * p.w1 + p.b1)
    return jnp.sum(h * p.w2, -1) + p.b2


# ---------------------------------------------------------------------------
# Lookup: root -> leaf -> bounded branchless binary search.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("root_kind", "leaf_kind",
                                             "n_leaves", "n", "iters"))
def rmi_lookup(root_kind: str, root, leaf_kind: str, leaves, err_lo, err_hi,
               keys: Array, queries: Array, n_leaves: int, n: int,
               iters: int | None = None) -> Array:
    """Positions of ``queries`` in ``keys`` (first index with key >= query).

    jnp oracle for the Pallas serving kernel (``repro.kernels.lookup``):
    predict, clamp the window to the leaf's error bounds, then a fixed-
    iteration branchless binary search inside the window. ``iters`` clamps
    the search depth to the index's error window (RMIIndex.search_iters);
    None falls back to the classic ceil(log2 n) + 1.
    """
    b = root_buckets(root_kind, root, queries, n_leaves, n)
    p = jax.tree.map(lambda a: a[b], leaves)
    if leaf_kind == "linear":
        pred = models.linear_predict(p, queries)
    else:
        h = jax.nn.relu(queries[:, None] * p.w1 + p.b1)
        pred = jnp.sum(h * p.w2, -1) + p.b2
    lo = jnp.clip(jnp.floor(pred + err_lo[b]), 0, n - 1).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + err_hi[b]) + 1, 1, n).astype(jnp.int32)
    return verified_search(keys, queries, lo, hi, iters=iters)


# ---------------------------------------------------------------------------
# Key forms.  A sorted tier is searched in one of two forms: f64 keys, or
# ``(hi, lo)`` f32 words with hi = f32(x) and lo = f32(x - hi) — the split
# XLA:TPU's f64 emulation makes of every f64 operand on each call.  Holding
# a tier as its words lets a program gather two words per probe instead of
# splitting the whole tier first.  Words compare lexicographically, which
# orders keys exactly when each key is hi + lo exactly (48 significant
# bits); an infinite hi gets lo = 0, so +inf pads stay comparable.
# ---------------------------------------------------------------------------
def split_words(x: Array) -> tuple:
    """``(hi, lo)`` f32 words of f64 ``x``."""
    hi = x.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi), (x - hi.astype(x.dtype)), 0.0)
    return hi, lo.astype(jnp.float32)


def like_keys(keys, queries: Array):
    """f64 ``queries`` in the form of ``keys``: as they are for f64 keys,
    their words for a tier held as words."""
    return split_words(queries) if isinstance(keys, tuple) else queries


def key_value(keys) -> Array:
    """The f64 value of keys in either form (hi + lo for words)."""
    if isinstance(keys, tuple):
        return keys[0].astype(jnp.float64) + keys[1].astype(jnp.float64)
    return keys


def tier_size(keys) -> int:
    return jax.tree.leaves(keys)[0].shape[0]


def _at(keys, i: Array):
    return jax.tree.map(lambda a: a[i], keys)


def _lt(a, b) -> Array:
    if isinstance(a, tuple):
        return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
    return a < b


def _ge(a, b) -> Array:
    return ~_lt(a, b) if isinstance(a, tuple) else a >= b


def _le(a, b) -> Array:
    return ~_lt(b, a) if isinstance(a, tuple) else a <= b


def searchsorted_right(keys, queries) -> Array:
    """First position with ``keys[p] > q`` over the whole tier, ``queries``
    in the form of ``keys`` (:func:`like_keys`)."""
    if isinstance(keys, tuple):
        z = jnp.zeros_like(queries[0], jnp.int32)
        return bounded_search(keys, queries, z, z + tier_size(keys),
                              side="right")
    return jnp.searchsorted(keys, queries, side="right").astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("iters",))
def verified_search(keys: Array, queries: Array, lo: Array, hi: Array,
                    iters: int | None = None) -> Array:
    """Bounded search + seam verification. Error bounds are measured on the
    indexed keys, so *member* lookups always land; a non-member query routed
    near a leaf boundary can fall outside its leaf's window (and with a
    clamped ``iters`` a query in a sentinel full-array window cannot converge
    in depth). Verify the left-boundary invariant and re-search the full
    array at full depth for the (rare) violations — total lookups stay sound
    for any query distribution.  ``keys`` and ``queries`` are in either
    key form, both in the same one."""
    n = tier_size(keys)
    r = bounded_search(keys, queries, lo, hi, iters=iters)
    rc = jnp.clip(r, 0, n - 1)
    valid = ((r == 0) | _lt(_at(keys, jnp.clip(r - 1, 0, n - 1)), queries)) \
        & ((r == n) | _ge(_at(keys, rc), queries))

    def _fallback(_):
        full = bounded_search(keys, queries, jnp.zeros_like(lo),
                              jnp.full_like(hi, n))
        return jnp.where(valid, r, full)

    return jax.lax.cond(jnp.all(valid), lambda _: r, _fallback, None)


@functools.partial(jax.jit, static_argnames=("iters", "side"))
def bounded_search(keys: Array, queries: Array, lo: Array, hi: Array,
                   iters: int | None = None, side: str = "left") -> Array:
    """Branchless binary search of each query in keys[lo:hi] (left boundary:
    first position with keys[p] >= q; ``side="right"``: first with
    keys[p] > q). Fixed iteration count so it vectorizes with no
    data-dependent control flow; ``iters`` defaults to the full
    ceil(log2 n) + 1 and can be clamped to the caller's window bound.
    ``keys`` and ``queries`` are in either key form, both in the same one."""
    n = tier_size(keys)
    if iters is None:
        import math as _math
        iters = _math.ceil(_math.log2(max(n, 2))) + 1
    before = _lt if side == "left" else _le

    def body(_, lh):
        lo, hi = lh
        active = hi - lo > 0
        mid = (lo + hi) // 2
        below = before(_at(keys, jnp.clip(mid, 0, n - 1)), queries)
        new_lo = jnp.where(below, mid + 1, lo)
        new_hi = jnp.where(below, hi, mid)
        return (jnp.where(active, new_lo, lo), jnp.where(active, new_hi, hi))

    lo, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def lookup(index: RMIIndex, queries: Array, *, path: str = "auto",
           use_kernel: bool | None = None,
           clamp_iters: bool = True) -> Array:
    """Serving lookup. ``path`` selects the execution path (see
    ``core.paths.resolve_path``): ``"kernel"`` is the fused Pallas kernel,
    ``"jnp"`` the CPU fast path / kernel oracle / f64 fallback, and
    ``"auto"`` picks the kernel on TPU backends when the key space is
    exactly f32-representable. Note the kernel path's left boundary is
    defined in f32 key space even for f32-exact keys: a non-member f64
    query within one f32 ulp of a key rounds onto it and returns that
    key's position, where the f64 jnp path returns the position after it.
    ``clamp_iters`` bounds the search depth by the index's error window
    instead of log2(n). ``use_kernel`` is the deprecated bool shim."""
    iters = index.search_iters if clamp_iters else None
    if resolve_path(path, f32_exact=lambda: index.f32_exact,
                    use_kernel=use_kernel):
        from ..kernels import ops as kernel_ops
        from ..kernels.lookup import full_iters
        root, mat, vec = index.packed_tables()
        return kernel_ops.index_lookup(
            jnp.asarray(queries, jnp.float64), root, mat, vec, index.keys,
            n_leaves=index.n_leaves, root_kind=index.root_kind,
            leaf_kind=index.leaf_kind,
            iters=iters if iters is not None else full_iters(index.n))
    return rmi_lookup(index.root_kind, index.root, index.leaf_kind,
                      index.leaves, index.err_lo, index.err_hi, index.keys,
                      jnp.asarray(queries, jnp.float64), index.n_leaves,
                      index.n, iters=iters)
