"""Pure-jnp oracles for every Pallas kernel (same dtypes/semantics).

Each function mirrors its kernel's contract exactly (f32 math where the
kernel computes in f32) so tests can assert_allclose across shape/dtype
sweeps in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def hist_ref(keys: jax.Array, m: int, lo, hi) -> jax.Array:
    """Oracle for hist.hist_pallas (f32, right-closed bins)."""
    k = keys.astype(jnp.float32)
    x = (k - jnp.float32(lo)) / (jnp.float32(hi) - jnp.float32(lo))
    b = jnp.clip(jnp.ceil(x * m).astype(jnp.int32) - 1, 0, m - 1)
    counts = jnp.zeros((m,), jnp.float32).at[b].add(1.0)
    return counts / jnp.float32(keys.shape[0])


def ksdist_ref(tgt_hists: jax.Array, pool_a: jax.Array,
               pool_ps: jax.Array) -> jax.Array:
    """Oracle for ksdist.ksdist_pallas: (L, P) Algorithm-2 distances."""
    ht = tgt_hists.astype(jnp.float32)
    pt = jnp.concatenate(
        [jnp.zeros((ht.shape[0], 1), jnp.float32), jnp.cumsum(ht, 1)[:, :-1]], 1)
    up = jnp.max(pool_a[None, :, :] - pt[:, None, :], axis=2)
    dn = jnp.max((ht + pt)[:, None, :] - pool_ps[None, :, :], axis=2)
    return jnp.maximum(up, dn)


def linfit_sums_ref(x: jax.Array, y: jax.Array, buckets: jax.Array,
                    n_buckets: int) -> jax.Array:
    """Oracle for linfit.linfit_sums_pallas: (n_buckets, 5) moment sums."""
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    seg = lambda v: jax.ops.segment_sum(v, buckets, n_buckets)
    return jnp.stack([seg(jnp.ones_like(x)), seg(x), seg(y), seg(x * y),
                      seg(x * x)], axis=1)


def lookup_ref(queries, root, mat, vec, keys, *, n_leaves: int,
               root_kind: str = "linear", leaf_kind: str = "linear",
               iters: int | None = None, tile: int | None = None,
               route_n: int | None = None) -> jax.Array:
    """Oracle for lookup.lookup_pallas: same packed-table contract, same f32
    arithmetic, same per-key-tile clamped search and min-merge — bit-identical
    in interpret mode (including the deliberate non-convergence of queries
    whose window exceeds the static depth; the ops wrapper's verification owns
    those).  ``route_n`` is the frozen routing scale of the dynamic kernel
    (defaults to the key count — static-index behaviour)."""
    from . import lookup as _lk

    q = queries.astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    S = kf.shape[0]
    lp = mat.shape[1]
    if route_n is None:
        route_n = S
    if tile is None:
        tile = min(_lk.TILE_MAX, _lk._pow2ceil(max(S, 128)))
    if iters is None:
        iters = _lk.full_iters(S)
    tile_iters = min(iters, _lk.full_iters(tile))
    nk = -(-S // tile)
    kp = jnp.pad(kf, (0, nk * tile - S), constant_values=jnp.inf)

    lo, hi = _route_window_ref(q, root, mat, vec, n_leaves=n_leaves,
                               route_n=route_n, root_kind=root_kind,
                               leaf_kind=leaf_kind, S=S, lp=lp)
    return _tiled_window_search(q, kp, lo, hi, S=S, tile=tile,
                                tile_iters=tile_iters)


def _route_window_ref(q, root, mat, vec, *, n_leaves: int, route_n: int,
                      root_kind: str, leaf_kind: str, S: int, lp: int):
    """The kernels' stages 1-3, mirrored: root routing -> leaf predict ->
    error-bound window clamped to [0, S].  Same f32 op ordering as
    ``lookup._route_window``."""
    from . import lookup as _lk

    if root_kind == "linear":
        rpred = root[0, 0] * q + root[3, 0]
    else:
        h = jnp.maximum(q[:, None] * root[0, :_lk.H] + root[1, :_lk.H], 0.0)
        rpred = jnp.sum(h * root[2, :_lk.H], axis=1) + root[3, 0]
    b = jnp.clip((rpred * (n_leaves / route_n)).astype(jnp.int32),
                 0, n_leaves - 1)

    matf = mat.reshape(-1)
    vecf = vec.reshape(-1)
    row = lambda flat, r: jnp.take(flat, b + r * lp)
    if leaf_kind == "linear":
        pred = row(matf, 0) * q + row(vecf, 0)
    else:
        pred = row(vecf, 0)
        for k in range(_lk.H):
            hk = jnp.maximum(q * row(matf, k) + row(matf, _lk.H + k), 0.0)
            pred = pred + hk * row(matf, 2 * _lk.H + k)

    lo = jnp.clip(jnp.floor(pred + row(vecf, 1)), 0, S - 1).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + row(vecf, 2)) + 1.0, 1, S).astype(jnp.int32)
    return lo, hi


def _tiled_window_search(q, kp, lo, hi, *, S: int, tile: int,
                         tile_iters: int, right: bool = False):
    """The kernels' stage 4, mirrored: per-key-tile clamped branchless
    search with min-merge across tiles.  ``kp`` is the +inf-padded f32 key
    array (length a ``tile`` multiple).  ``right=True`` mirrors the range
    kernel's right-boundary search (first position with key > q)."""
    nk = kp.shape[0] // tile
    out = hi
    for j in range(nk):
        base = j * tile
        tlo = jnp.clip(lo - base, 0, tile)
        thi = jnp.clip(hi - base, 0, tile)
        ktile = jax.lax.dynamic_slice_in_dim(kp, base, tile)

        def body(_, lh, ktile=ktile):
            l, h2 = lh
            active = h2 - l > 0
            mid = (l + h2) // 2
            kv = jnp.take(ktile, jnp.clip(mid, 0, tile - 1))
            below = kv <= q if right else kv < q
            nl = jnp.where(below, mid + 1, l)
            nh = jnp.where(below, h2, mid)
            return (jnp.where(active, nl, l), jnp.where(active, nh, h2))

        l, _ = jax.lax.fori_loop(0, tile_iters, body, (tlo, thi))
        out = jnp.minimum(out, jnp.where(l < thi, base + l, S))
    return out


def rmrt_lookup_ref(queries, mat, vec, keys, *, fanout: int, depth: int,
                    kind: str = "linear", iters: int | None = None,
                    tile: int | None = None) -> jax.Array:
    """Oracle for lookup.rmrt_lookup_pallas: same packed node-table contract
    (pack_rmrt layout), same f32 arithmetic — the depth-D masked descent is
    reimplemented here (independent of the kernel body) with identical op
    ordering, then the shared tiled clamped search.  Bit-identical in
    interpret mode."""
    from . import lookup as _lk

    q = queries.astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    S = kf.shape[0]
    npad = mat.shape[1]
    if tile is None:
        tile = min(_lk.TILE_MAX, _lk._pow2ceil(max(S, 128)))
    if iters is None:
        iters = _lk.full_iters(S)
    tile_iters = min(iters, _lk.full_iters(tile))
    nk = -(-S // tile)
    kp = jnp.pad(kf, (0, nk * tile - S), constant_values=jnp.inf)

    matf = mat.reshape(-1)
    vecf = vec.reshape(-1)
    row = lambda flat, r, idx: jnp.take(flat, idx + r * npad)

    def predict(node):
        if kind == "linear":
            return row(matf, 0, node) * q + row(vecf, 0, node)
        pred = row(vecf, 0, node)
        for k in range(_lk.H):
            hk = jnp.maximum(q * row(matf, k, node)
                             + row(matf, _lk.H + k, node), 0.0)
            pred = pred + hk * row(matf, 2 * _lk.H + k, node)
        return pred

    node = jnp.zeros(q.shape, jnp.int32)
    for _ in range(depth):
        pred = predict(node)
        ys = row(vecf, 3, node)
        span = row(vecf, 4, node) - ys
        child = jnp.clip(((pred - ys) * fanout / span).astype(jnp.int32),
                         0, fanout - 1)
        nxt = row(vecf, 5, node).astype(jnp.int32) + child
        node = jnp.where(row(vecf, 6, node) > 0.5, node, nxt)

    pred = predict(node)
    lo = jnp.clip(jnp.floor(pred + row(vecf, 1, node)), 0, S - 1
                  ).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + row(vecf, 2, node)) + 1.0, 1, S
                  ).astype(jnp.int32)
    return _tiled_window_search(q, kp, lo, hi, S=S, tile=tile,
                                tile_iters=tile_iters)


def dynamic_lookup_ref(queries, root, mat, vec, keys, delta_keys, *,
                       n_leaves: int, route_n: int | None = None,
                       root_kind: str = "linear", leaf_kind: str = "linear",
                       iters: int | None = None,
                       tile: int | None = None) -> tuple:
    """Oracle for lookup.dynamic_lookup_pallas: (base_pos, delta_pos).
    The base tier is exactly :func:`lookup_ref` with the frozen ``route_n``
    routing scale (one oracle — no drift between the static and dynamic
    base-search semantics); the delta probe mirrors the lookup's full-depth
    search of the +inf-padded tier.  Bit-identical in interpret mode."""
    from . import lookup as _lk

    out = lookup_ref(queries, root, mat, vec, keys, n_leaves=n_leaves,
                     root_kind=root_kind, leaf_kind=leaf_kind, iters=iters,
                     tile=tile, route_n=route_n)

    q = queries.astype(jnp.float32)
    dk = _lk.pad_delta(delta_keys)
    nd = dk.shape[0]
    dl = jnp.zeros(q.shape, jnp.int32)
    dh = jnp.full(q.shape, nd, jnp.int32)

    def dbody(_, lh):
        l, h2 = lh
        active = h2 - l > 0
        mid = (l + h2) // 2
        kv = jnp.take(dk, jnp.clip(mid, 0, nd - 1))
        below = kv < q
        nl = jnp.where(below, mid + 1, l)
        nh = jnp.where(below, h2, mid)
        return (jnp.where(active, nl, l), jnp.where(active, nh, h2))

    dl, _ = jax.lax.fori_loop(0, _lk.full_iters(nd), dbody, (dl, dh))
    return out, dl


def dynamic_range_ref(q_lo, q_hi, root, mat, vec, keys, delta_keys, *,
                      n_leaves: int, route_n: int | None = None,
                      root_kind: str = "linear", leaf_kind: str = "linear",
                      iters: int | None = None,
                      tile: int | None = None) -> tuple:
    """Oracle for lookup.dynamic_range_pallas: (base_lo, base_hi, delta_lo,
    delta_hi).  Left boundary of ``q_lo`` and right boundary of ``q_hi``
    against both tiers, with the same routing/window/tiled-search f32 op
    ordering as the fused kernel — bit-identical in interpret mode."""
    from . import lookup as _lk

    ql = q_lo.astype(jnp.float32)
    qh = q_hi.astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    S = kf.shape[0]
    lp = mat.shape[1]
    if route_n is None:
        route_n = S
    if tile is None:
        tile = min(_lk.TILE_MAX, _lk._pow2ceil(max(S, 128)))
    if iters is None:
        iters = _lk.full_iters(S)
    tile_iters = min(iters, _lk.full_iters(tile))
    nk = -(-S // tile)
    kp = jnp.pad(kf, (0, nk * tile - S), constant_values=jnp.inf)

    win = lambda q: _route_window_ref(
        q, root, mat, vec, n_leaves=n_leaves, route_n=route_n,
        root_kind=root_kind, leaf_kind=leaf_kind, S=S, lp=lp)
    lo, hi = win(ql)
    blo = _tiled_window_search(ql, kp, lo, hi, S=S, tile=tile,
                               tile_iters=tile_iters)
    lo, hi = win(qh)
    bhi = _tiled_window_search(qh, kp, lo, hi, S=S, tile=tile,
                               tile_iters=tile_iters, right=True)

    dk = _lk.pad_delta(delta_keys)
    nd = dk.shape[0]

    def probe(q, right):
        dl = jnp.zeros(q.shape, jnp.int32)
        dh = jnp.full(q.shape, nd, jnp.int32)

        def dbody(_, lh):
            l, h2 = lh
            active = h2 - l > 0
            mid = (l + h2) // 2
            kv = jnp.take(dk, jnp.clip(mid, 0, nd - 1))
            below = kv <= q if right else kv < q
            nl = jnp.where(below, mid + 1, l)
            nh = jnp.where(below, h2, mid)
            return (jnp.where(active, nl, l), jnp.where(active, nh, h2))

        dl, _ = jax.lax.fori_loop(0, _lk.full_iters(nd), dbody, (dl, dh))
        return dl

    return blo, bhi, probe(ql, False), probe(qh, True)


def dynamic_range_find_ref(q_lo, q_hi, keys, base_psum, delta_keys,
                           delta_psum) -> tuple:
    """Oracle for ops.range_lookup's (rank_lo, rank_hi): exact searchsorted
    boundaries (side='left' for lo, side='right' for hi) composed through
    the two-tier live-rank algebra, with rank_hi clamped to rank_lo so
    degenerate ranges (lo > hi, tombstoned singletons, fully out-of-range)
    collapse to an empty [rank_lo, rank_lo) window."""
    from . import lookup as _lk

    kf = keys.astype(jnp.float32)
    qlf = q_lo.astype(jnp.float32)
    qhf = q_hi.astype(jnp.float32)
    blo = jnp.searchsorted(kf, qlf, side="left").astype(jnp.int32)
    bhi = jnp.searchsorted(kf, qhf, side="right").astype(jnp.int32)
    df = _lk.pad_delta(delta_keys)
    nd = df.shape[0]
    dlo = jnp.searchsorted(df, qlf, side="left").astype(jnp.int32)
    dhi = jnp.searchsorted(df, qhf, side="right").astype(jnp.int32)
    dpsum = jnp.pad(delta_psum, (0, nd + 1 - delta_psum.shape[0]),
                    mode="edge")
    rank_lo = (blo - base_psum[blo]) + (dlo - dpsum[dlo])
    rank_hi = (bhi - base_psum[bhi]) + (dhi - dpsum[dhi])
    return rank_lo, jnp.maximum(rank_hi, rank_lo)


def dynamic_find_ref(queries, keys, base_dead, base_psum, delta_keys,
                     delta_dead, delta_psum) -> tuple:
    """Oracle for ops.dynamic_find's (found, rank): the same f32 tombstone /
    two-tier live-rank algebra as ``ops._dynamic_lookup_jit``, with exact
    searchsorted boundaries in place of the kernel positions — the seam
    verification pins every valid kernel position to exactly this boundary,
    so ops.dynamic_find must match bit-for-bit on f32-exact tiers.  Model
    tables don't enter: routing only picks the (seam-verified) window."""
    from . import lookup as _lk

    kf = keys.astype(jnp.float32)
    qf = queries.astype(jnp.float32)
    pos = jnp.searchsorted(kf, qf, side="left").astype(jnp.int32)
    bhi = jnp.searchsorted(kf, qf, side="right").astype(jnp.int32)
    base_hit = (bhi - pos) > (base_psum[bhi] - base_psum[pos])
    df = _lk.pad_delta(delta_keys)
    nd = df.shape[0]
    dpos = jnp.searchsorted(df, qf, side="left").astype(jnp.int32)
    dhi = jnp.searchsorted(df, qf, side="right").astype(jnp.int32)
    dpsum = jnp.pad(delta_psum, (0, nd + 1 - delta_psum.shape[0]),
                    mode="edge")
    delta_hit = (dhi - dpos) > (dpsum[dhi] - dpsum[dpos])
    rank = (pos - base_psum[pos]) + (dpos - dpsum[dpos])
    return base_hit | delta_hit, rank
