"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True off-TPU (this container is CPU-only; the
kernel bodies execute in Python via the Pallas interpreter, which is the
validation mode) and to False on real TPU backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import hist as _hist
from . import ksdist as _ksdist
from . import linfit as _linfit
from . import lookup as _lookup


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def histogram(keys: jax.Array, m: int, lo, hi, interpret: bool | None = None):
    """Streaming m-bin relative-frequency histogram (unsorted keys)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _hist.hist_pallas(keys, m, lo, hi, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ksdist_matrix(tgt_hists, pool_a, pool_ps, interpret: bool | None = None):
    """(L, P) Algorithm-2 distance matrix (targets x pool)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _ksdist.ksdist_pallas(tgt_hists, pool_a, pool_ps,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def segment_linfit(x, y, buckets, n_buckets: int,
                   interpret: bool | None = None):
    """Per-bucket least-squares (slope, intercept): (n_buckets, 2) f64.

    Two kernel passes for f32 moment stability: pass 1 accumulates
    (count, Sum x, Sum y) -> per-bucket means; inputs are then centered *per
    bucket* in f64 (within-bucket dynamic range is tiny, so the f32 kernel
    moments of pass 2 are exact enough) and pass 2 accumulates the centered
    cross moments. Global standardization alone cancels catastrophically
    when buckets are narrow slices of the key range.
    """
    interpret = _default_interpret() if interpret is None else interpret
    x64 = x.astype(jnp.float64)
    y64 = y.astype(jnp.float64)
    # pass 1 on globally standardized coords (safe for means)
    mu_x, sd_x = jnp.mean(x64), jnp.maximum(jnp.std(x64), 1e-30)
    mu_y, sd_y = jnp.mean(y64), jnp.maximum(jnp.std(y64), 1e-30)
    xs = ((x64 - mu_x) / sd_x).astype(jnp.float32)
    ys = ((y64 - mu_y) / sd_y).astype(jnp.float32)
    s1 = _linfit.linfit_sums_pallas(xs, ys, buckets, n_buckets,
                                    interpret=interpret)
    n = s1[:, 0].astype(jnp.float64)
    nn = jnp.maximum(n, 1.0)
    bmu_x = s1[:, 1].astype(jnp.float64) / nn       # in standardized coords
    bmu_y = s1[:, 2].astype(jnp.float64) / nn
    # pass 2: per-bucket centered
    xc = (((x64 - mu_x) / sd_x) - bmu_x[buckets]).astype(jnp.float32)
    yc = (((y64 - mu_y) / sd_y) - bmu_y[buckets]).astype(jnp.float32)
    s2 = _linfit.linfit_sums_pallas(xc, yc, buckets, n_buckets,
                                    interpret=interpret)
    sxy = s2[:, 3].astype(jnp.float64)
    sxx = s2[:, 4].astype(jnp.float64)
    a_s = jnp.where(sxx > 1e-20, sxy / sxx, 0.0)
    # map back: y = a x + b in raw coordinates
    a = a_s * sd_y / sd_x
    b = (bmu_y * sd_y + mu_y) - a * (bmu_x * sd_x + mu_x)
    return jnp.stack([a, jnp.where(n > 0, b, 0.0)], 1)


def index_lookup(queries, root, mat, vec, keys, *, n_leaves: int,
                 root_kind: str = "linear", leaf_kind: str = "linear",
                 iters: int | None = None, tile: int | None = None,
                 interpret: bool | None = None, seam_budget: int = 1024):
    """Fused serving lookup (route -> predict -> window -> clamped search)
    with the XLA-side sparse seam verification.

    ``root``/``mat``/``vec`` are the packed tables from
    lookup.pack_root / lookup.pack_leaves. ``iters`` is the static
    error-window search depth; when None it is derived host-side from the
    (concrete) bound rows of ``vec`` via lookup.search_iters.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if iters is None:
        if isinstance(vec, jax.core.Tracer):
            # under an outer jit/vmap the bounds aren't concrete; fall back
            # to the sound full depth (callers wanting the clamped depth
            # pass iters=index.search_iters, which is static)
            iters = _lookup.full_iters(keys.shape[0])
        else:
            import numpy as np
            L = min(n_leaves, vec.shape[1])
            vec_np = np.asarray(vec)          # concrete at call time
            iters = _lookup.search_iters(vec_np[1, :L], vec_np[2, :L],
                                         keys.shape[0])
    return _index_lookup_jit(queries, root, mat, vec, keys,
                             n_leaves=n_leaves, root_kind=root_kind,
                             leaf_kind=leaf_kind, iters=iters, tile=tile,
                             interpret=interpret, seam_budget=seam_budget)


def _seam_fix(r, kf, qf, seam_budget: int, right: bool = False):
    """Seam verification in f32 space (kernel semantics). Misses are rare —
    boundary queries outside their leaf's window, or queries routed to a
    sentinel (empty-leaf) window deeper than the clamped search depth — so
    the fallback re-searches only the invalid positions (compacted to a
    static ``seam_budget``); the dense full-Q re-search runs only if the
    miss count exceeds the budget.  ``right=True`` checks the mirrored
    right-boundary invariant (kf[r-1] <= q < kf[r]) for the range kernel's
    hi endpoints, with a side='right' searchsorted fallback."""
    n = kf.shape[0]
    rc = jnp.clip(r, 0, n - 1)
    side = "right" if right else "left"
    prev = kf[jnp.clip(r - 1, 0, n - 1)]
    if right:
        valid = ((r == 0) | (prev <= qf)) & ((r == n) | (kf[rc] > qf))
    else:
        valid = ((r == 0) | (prev < qf)) & ((r == n) | (kf[rc] >= qf))
    n_bad = jnp.sum(~valid)
    budget = min(seam_budget, qf.shape[0])

    def _sparse(_):
        # jnp.nonzero(~valid, size=budget, fill_value=0) in int32: its x64
        # default (an int64 cumsum) does not compile for the TPU.
        bad = (~valid).astype(jnp.int32)
        slot = jnp.where(valid, budget, jnp.cumsum(bad, dtype=jnp.int32) - 1)
        idx = jnp.zeros((budget,), jnp.int32).at[slot].set(
            jnp.arange(bad.shape[0], dtype=jnp.int32), mode="drop")
        sub = jnp.searchsorted(kf, qf[idx], side=side).astype(r.dtype)
        return r.at[idx].set(jnp.where(valid[idx], r[idx], sub))

    def _dense(_):
        full = jnp.searchsorted(kf, qf, side=side).astype(r.dtype)
        return jnp.where(valid, r, full)

    def _fix(_):
        return jax.lax.cond(n_bad <= budget, _sparse, _dense, None)

    return jax.lax.cond(n_bad == 0, lambda _: r, _fix, None)


@functools.partial(jax.jit, static_argnames=(
    "n_leaves", "root_kind", "leaf_kind", "iters", "tile", "interpret",
    "seam_budget"))
def _index_lookup_jit(queries, root, mat, vec, keys, *, n_leaves, root_kind,
                      leaf_kind, iters, tile, interpret, seam_budget):
    r = _lookup.lookup_pallas(queries, root, mat, vec, keys,
                              n_leaves=n_leaves, root_kind=root_kind,
                              leaf_kind=leaf_kind, iters=iters, tile=tile,
                              interpret=interpret)
    return _seam_fix(r, keys.astype(jnp.float32),
                     queries.astype(jnp.float32), seam_budget)


def rmrt_lookup(queries, mat, vec, keys, *, fanout: int, depth: int,
                kind: str = "linear", iters: int | None = None,
                tile: int | None = None, interpret: bool | None = None,
                seam_budget: int = 1024):
    """Fused RMRT serving lookup: fixed-depth descent over the
    packed node tables (lookup.pack_rmrt) + error-window-clamped tiled
    search, with the same XLA-side sparse seam verification as
    :func:`index_lookup`.

    ``iters`` is the static error-window search depth; when None it is
    derived host-side from the (concrete) bound rows of ``vec`` — internal
    nodes carry zero-width rows and sentinel (empty-leaf) windows are
    excluded by the live mask, exactly like the RMI path.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if iters is None:
        if isinstance(vec, jax.core.Tracer):
            iters = _lookup.full_iters(keys.shape[0])
        else:
            import numpy as np
            vec_np = np.asarray(vec)
            iters = _lookup.search_iters(vec_np[1], vec_np[2],
                                         keys.shape[0])
    return _rmrt_lookup_jit(queries, mat, vec, keys, fanout=fanout,
                            depth=depth, kind=kind, iters=iters, tile=tile,
                            interpret=interpret, seam_budget=seam_budget)


@functools.partial(jax.jit, static_argnames=(
    "fanout", "depth", "kind", "iters", "tile", "interpret", "seam_budget"))
def _rmrt_lookup_jit(queries, mat, vec, keys, *, fanout, depth, kind, iters,
                     tile, interpret, seam_budget):
    r = _lookup.rmrt_lookup_pallas(queries, mat, vec, keys, fanout=fanout,
                                   depth=depth, kind=kind, iters=iters,
                                   tile=tile, interpret=interpret)
    return _seam_fix(r, keys.astype(jnp.float32),
                     queries.astype(jnp.float32), seam_budget)


def dynamic_index_lookup(queries, root, mat, vec, keys, base_dead, base_psum,
                         delta_keys, delta_dead, delta_psum, *, n_leaves: int,
                         route_n: int, root_kind: str = "linear",
                         leaf_kind: str = "linear", iters: int | None = None,
                         tile: int | None = None,
                         interpret: bool | None = None,
                         seam_budget: int = 1024):
    """Fused two-tier serving find for the dynamic index: one Pallas kernel
    (base window search) and the full-depth delta probe, then O(Q) jitted
    gathers for the tombstone mask and the two-tier live rank.  Zero
    per-query host Python.

    ``keys``/``delta_keys`` are the sorted base/delta tiers (delta +inf
    padded to its storage capacity); ``*_dead`` the tombstone bitmaps and
    ``*_psum`` their exclusive prefix sums (length n+1).  ``route_n`` is the
    frozen routing scale of ``core.updates.DynamicRMI``.  Returns
    (found, rank, base_pos, delta_pos): ``found`` is True iff a live copy of
    the query exists in either tier; ``rank`` counts live keys < q across
    both tiers.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if iters is None:
        if isinstance(vec, jax.core.Tracer):
            iters = _lookup.full_iters(keys.shape[0])
        else:
            import numpy as np
            L = min(n_leaves, vec.shape[1])
            # tracelint: ok[hot-sync](iters=None convenience path only; serve callers pass iters)
            vec_np = np.asarray(vec)
            iters = _lookup.search_iters(vec_np[1, :L], vec_np[2, :L],
                                         keys.shape[0])
    return _dynamic_lookup_jit(queries, root, mat, vec, keys, base_dead,
                               base_psum, delta_keys, delta_dead, delta_psum,
                               n_leaves=n_leaves, route_n=route_n,
                               root_kind=root_kind, leaf_kind=leaf_kind,
                               iters=iters, tile=tile, interpret=interpret,
                               seam_budget=seam_budget)


def dynamic_find(queries, root, mat, vec, keys, base_dead, base_psum,
                 delta_keys, delta_dead, delta_psum, **kw):
    """The two-tier serving answer alone: (found, rank) of
    :func:`dynamic_index_lookup`, without the positional diagnostics.
    Shared by ``core.updates.DynamicRMI.find`` and the per-shard dispatch of
    ``core.distributed.ShardedDynamicIndex`` (which packs per-shard routing
    scales into the root block — ``lookup.pack_root(route_scale=...)`` — and
    traces this once with a uniform static ``route_n``)."""
    found, rank, _, _ = dynamic_index_lookup(
        queries, root, mat, vec, keys, base_dead, base_psum, delta_keys,
        delta_dead, delta_psum, **kw)
    return found, rank


@functools.partial(jax.jit, static_argnames=(
    "n_leaves", "route_n", "root_kind", "leaf_kind", "iters", "tile",
    "interpret", "seam_budget"))
def _dynamic_lookup_jit(queries, root, mat, vec, keys, base_dead, base_psum,
                        delta_keys, delta_dead, delta_psum, *, n_leaves,
                        route_n, root_kind, leaf_kind, iters, tile, interpret,
                        seam_budget):
    pos, dpos = _lookup.dynamic_lookup_pallas(
        queries, root, mat, vec, keys, delta_keys, n_leaves=n_leaves,
        route_n=route_n, root_kind=root_kind, leaf_kind=leaf_kind,
        iters=iters, tile=tile, interpret=interpret)
    kf = keys.astype(jnp.float32)
    qf = queries.astype(jnp.float32)
    # Base tier: seam-verify the window-clamped positions, then tombstone
    # mask.  The delta probe ran at full depth over the whole (small)
    # tier, so its boundary is already exact — no seam pass.  A hit is any
    # *live* entry in the equal-key run [left, right): count live slots via
    # the tombstone prefix sums (robust to partially tombstoned duplicate
    # runs); the right boundaries are one O(Q log n) searchsorted each.
    pos = _seam_fix(pos, kf, qf, seam_budget)
    bhi = jnp.searchsorted(kf, qf, side="right").astype(pos.dtype)
    base_hit = (bhi - pos) > (base_psum[bhi] - base_psum[pos])
    df = _lookup.pad_delta(delta_keys)
    nd = df.shape[0]
    dhi = jnp.searchsorted(df, qf, side="right").astype(dpos.dtype)
    dpsum = jnp.pad(delta_psum, (0, nd + 1 - delta_psum.shape[0]),
                    mode="edge")
    delta_hit = (dhi - dpos) > (dpsum[dhi] - dpsum[dpos])
    # Live rank across both tiers: positions minus tombstones left of them.
    rank = (pos - base_psum[pos]) + (dpos - dpsum[dpos])
    return base_hit | delta_hit, rank, pos, dpos


def range_lookup(q_lo, q_hi, root, mat, vec, keys, base_dead, base_psum,
                 delta_keys, delta_dead, delta_psum, *, n_leaves: int,
                 route_n: int, root_kind: str = "linear",
                 leaf_kind: str = "linear", iters: int | None = None,
                 tile: int | None = None, interpret: bool | None = None,
                 seam_budget: int = 1024):
    """Fused two-tier range answer: (rank_lo, rank_hi) live ranks of the
    inclusive key range ``[q_lo, q_hi]`` — rank_lo counts live keys < q_lo
    (leftmost rank under duplicates), rank_hi counts live keys <= q_hi
    (rightmost rank), so the range holds exactly rank_hi - rank_lo live
    entries.  One Pallas pass routes BOTH endpoints (lookup.
    dynamic_range_pallas), each boundary is seam-verified with its own
    side, and rank_hi is clamped to rank_lo so degenerate inputs (lo > hi,
    a tombstoned singleton, a fully out-of-range window) return an empty
    range instead of a negative width.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if iters is None:
        if isinstance(vec, jax.core.Tracer):
            iters = _lookup.full_iters(keys.shape[0])
        else:
            import numpy as np
            L = min(n_leaves, vec.shape[1])
            # tracelint: ok[hot-sync](iters=None convenience path only; serve callers pass iters)
            vec_np = np.asarray(vec)
            iters = _lookup.search_iters(vec_np[1, :L], vec_np[2, :L],
                                         keys.shape[0])
    return _range_lookup_jit(q_lo, q_hi, root, mat, vec, keys, base_psum,
                             delta_keys, delta_psum, n_leaves=n_leaves,
                             route_n=route_n, root_kind=root_kind,
                             leaf_kind=leaf_kind, iters=iters, tile=tile,
                             interpret=interpret, seam_budget=seam_budget)


@functools.partial(jax.jit, static_argnames=(
    "n_leaves", "route_n", "root_kind", "leaf_kind", "iters", "tile",
    "interpret", "seam_budget"))
def _range_lookup_jit(q_lo, q_hi, root, mat, vec, keys, base_psum,
                      delta_keys, delta_psum, *, n_leaves, route_n,
                      root_kind, leaf_kind, iters, tile, interpret,
                      seam_budget):
    blo, bhi, dlo, dhi = _lookup.dynamic_range_pallas(
        q_lo, q_hi, root, mat, vec, keys, delta_keys, n_leaves=n_leaves,
        route_n=route_n, root_kind=root_kind, leaf_kind=leaf_kind,
        iters=iters, tile=tile, interpret=interpret)
    kf = keys.astype(jnp.float32)
    qlf = q_lo.astype(jnp.float32)
    qhf = q_hi.astype(jnp.float32)
    # Seam-verify each base boundary with its own side; the delta probes ran
    # at full depth over the VMEM-sized tier so they are already exact.
    blo = _seam_fix(blo, kf, qlf, seam_budget)
    bhi = _seam_fix(bhi, kf, qhf, seam_budget, right=True)
    nd = _lookup.pad_delta(delta_keys).shape[0]
    dpsum = jnp.pad(delta_psum, (0, nd + 1 - delta_psum.shape[0]),
                    mode="edge")
    rank_lo = (blo - base_psum[blo]) + (dlo - dpsum[dlo])
    rank_hi = (bhi - base_psum[bhi]) + (dhi - dpsum[dhi])
    return rank_lo, jnp.maximum(rank_hi, rank_lo)
