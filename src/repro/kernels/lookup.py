"""Pallas TPU kernel: window-clamped learned-index lookup — the serving
hot path.

Each query runs the paper's four stages (root routing -> leaf predict ->
error-bound window -> bounded search):

  1-3. routing and window: the root model (linear or tiny MLP) buckets each
       query into its leaf, the leaf's model predicts a position, and the
       leaf's error bounds clamp it to a window.  The compact per-leaf
       tables — (3H, Lp) model params and (8, Lp) scalars, lane-major — are
       gathered per query by XLA in the jitted wrapper (``_route_window``;
       RMRT: ``_rmrt_route_window``).
  4.   the bounded search, in the Pallas kernel: a branchless binary search
       with a *static iteration count derived from the index's error
       window* (paper §4: the reuse bound caps the search range), not from
       log2(n_keys).

Memory layout: queries are tiled TQ at a time (grid dim 0) and the key shard
is BlockSpec-tiled TILE keys at a time as (TILE/128, 128) rows (grid dim 1,
innermost), so the VMEM working set is TQ + TILE regardless of shard size.
Each (i, j) grid step searches query tile i's windows restricted to key
tile j and min-merges the candidate into the revisited output block;
left-boundary results compose across tiles because positions increase with
j.  Queries whose window misses tile j contribute nothing, and a step whose
tile no window touches skips its work.

The search without a gather.  Mosaic gathers only inside one vreg (8
sublanes or 128 lanes), so the kernel cannot probe ``keys[mid]`` per query.
It needs only the count ``c`` of tile keys below the query instead: the tile
is sorted, so ``keys[mid] < q`` holds exactly when ``mid < c``, and the
bisection replayed on integers with that predicate visits the same midpoints
and stops at the same position as the probing search — including queries
whose window is wider than the static depth (their non-converged position is
the one ``kernels.ref`` computes, and the ops wrapper's seam verification
owns them).  ``c`` comes in two steps (``_count_below``): the row whose head
is the last one below q, by comparing q with the 1-per-row heads; then that
row, fetched for every query at once by a one-hot matmul on the MXU over the
byte planes of the key bits (bytes are exact in bf16, and each output sums a
single nonzero product), so the fetched keys are bit-exact, +inf included.

RMRT node-table packing (``pack_rmrt``): the flat level-synchronous node
arrays of ``core.rmrt.RMRTIndex`` pack into the same lane-major layout,
nodes on the 128-lane axis padded to Np = 128-multiple:

  mat (3H, Np) f32   rows [0, H)   w1 (linear models ride in w1[:, 0])
                     rows [H, 2H)  b1
                     rows [2H, 3H) w2
  vec (8, Np)  f32   row 0 b2 / b          row 4 y_end
                     row 1 err_lo          row 5 child_base (int, f32-exact:
                     row 2 err_hi                 node count << 2^24)
                     row 3 y_start         row 6 is_leaf (0.0 / 1.0)

so the fixed-depth masked descent (``_rmrt_route_window``) is a per-level
gather + predict + re-bucket.  Internal nodes carry err rows of 0 and leaves
carry child_base -1; neither is ever consumed on the other branch of the
``is_leaf`` select.  Padded lanes are unreachable (descent starts at node 0
and child ids stay < num_nodes).

Semantics match core.rmi.bounded_search on the same window/iters; the seam
verification (sparse re-check of the rare misses) stays in the ops wrapper,
keeping the kernel single-pass.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .call import pallas_call

LANES = 128
TQ = 1024           # queries per grid step
TILE_MAX = 1 << 16  # keys per VMEM tile (256 KiB f32, 512 rows of 128)
H = 4               # paper's hidden width
ROOT_ROWS = 8       # packed root block: rows [w1, b1, w2, b2|meta] x 128 lanes


def search_iters(err_lo, err_hi, n_keys: int) -> int:
    """Static binary-search depth for an index with the given leaf bounds.

    The paper's §4 bound: a lookup only ever searches a window of
    ceil(err_hi) - floor(err_lo) positions (+3 for the clamp/rounding slack),
    so the branchless search needs ceil(log2(max window)) + 1 iterations, not
    ceil(log2(n_keys)) + 1. Sentinel windows (empty leaves carry a sound
    full-array window) are excluded — queries routed there are caught by the
    seam verification and re-searched at full depth.
    """
    from ..core.bounds import clamped_depth, window_widths
    return clamped_depth(window_widths(err_lo, err_hi), n_keys)


def full_iters(n_keys: int) -> int:
    """Unclamped depth: the classic ceil(log2(n)) + 1."""
    return int(math.ceil(math.log2(max(n_keys, 2)))) + 1


def pack_root(root_kind: str, params, route_scale: float = 1.0) -> jax.Array:
    """(ROOT_ROWS, 128) f32 block holding the root model.

    linear: [0,0]=a, [3,0]=b.   mlp: rows 0/1/2 = w1/b1/w2 (H lanes), [3,0]=b2.

    ``route_scale`` folds a routing rescale into the packed model (the
    *output* layer for the MLP), so callers whose frozen routing scale
    differs per table — the sharded dynamic path stacks shards with
    different ``route_n`` under one statically-traced kernel — can pack
    scale = kernel_route_n / shard_route_n and trace a single kernel with
    ``route_n = kernel_route_n``.  The same fold generalizes per *tenant*
    (serve.frontend): a tenant built with ``L_t`` leaves packs
    scale = L_t / tenant_route_n, its leaf tables re-pad to the widest
    tenant's lane count (:func:`pad_packed_leaves`), and one kernel traced
    with static ``n_leaves = route_n = max_t L_t`` serves every tenant —
    routing overshoot past ``L_t - 1`` lands on a replicated last leaf,
    i.e. the same window the tenant's own clip would have produced.
    Routing runs in f32 either way and every final position is
    seam-verified, so the fold never changes results.
    """
    s = jnp.float64(route_scale)
    blk = jnp.zeros((ROOT_ROWS, 128), jnp.float32)
    if root_kind == "linear":
        blk = blk.at[0, 0].set((params.a * s).astype(jnp.float32))
        blk = blk.at[3, 0].set((params.b * s).astype(jnp.float32))
    else:
        blk = blk.at[0, :H].set(params.w1.astype(jnp.float32))
        blk = blk.at[1, :H].set(params.b1.astype(jnp.float32))
        blk = blk.at[2, :H].set((params.w2 * s).astype(jnp.float32))
        blk = blk.at[3, 0].set((params.b2 * s).astype(jnp.float32))
    return blk


def pack_leaves(w1, b1, w2, b2, err_lo, err_hi):
    """Lane-major leaf tables: (3H, Lp) params + (8, Lp) scalars, Lp = 128-pad.

    w1/b1/w2: (L, H); b2/err_lo/err_hi: (L,). Padded lanes are never gathered
    (buckets are clipped to L-1).
    """
    L = w1.shape[0]
    lp = -(-L // 128) * 128
    padT = lambda a: jnp.pad(a.astype(jnp.float32).T, ((0, 0), (0, lp - L)))
    mat = jnp.concatenate([padT(w1), padT(b1), padT(w2)], axis=0)  # (3H, Lp)
    vec = jnp.zeros((8, lp), jnp.float32)
    for row, a in ((0, b2), (1, err_lo), (2, err_hi)):
        vec = vec.at[row, :L].set(a.astype(jnp.float32))
    return mat, vec


def pad_packed_leaves(mat, vec, n_live: int, lp_to: int):
    """Re-pad packed lane-major leaf tables (``pack_leaves`` layout, lane
    count on the last axis) to a wider lane count, replicating the last
    *live* leaf into every lane past ``n_live - 1``.

    This is the per-tenant half of the ``route_scale`` fold: a tenant with
    ``L_t = n_live`` leaves stacked under a kernel traced with a wider
    static ``n_leaves`` can see routing buckets in ``[L_t, n_leaves - 1]``
    (its packed scale maps predictions past the end there, where its own
    trace would have clipped to ``L_t - 1``).  Replicated lanes carry the
    last leaf's params *and* error bounds, so an overshot bucket yields the
    exact window the tenant's own clip produces — downstream search and
    seam verification then match bit-for-bit.  Leading axes (e.g. a shard
    stack) broadcast through.
    """
    lane = jnp.minimum(jnp.arange(lp_to), max(n_live - 1, 0))
    return mat[..., lane], vec[..., lane]


def _route_window(root, mat, vec, q, *, n_keys: int, n_leaves: int, lp: int,
                  route_n: int, root_kind: str, leaf_kind: str):
    """Stages 1-3 on f32 queries (XLA, shared by the static and dynamic
    lookups): root routing (scaled by ``route_n``, the build-time key count
    the routing is frozen at), per-query leaf fetch from the flattened
    lane-major tables, error-bound window clamped to the *current* key
    count ``n_keys``."""
    # ---- stage 1: root routing -------------------------------------------
    if root_kind == "linear":
        rpred = root[0, 0] * q + root[3, 0]
    else:
        h = jnp.maximum(q[:, None] * root[0, :H] + root[1, :H], 0.0)
        rpred = jnp.sum(h * root[2, :H], axis=1) + root[3, 0]
    b = jnp.clip((rpred * (n_leaves / route_n)).astype(jnp.int32),
                 0, n_leaves - 1)

    # ---- stage 2: leaf fetch ---------------------------------------------
    row = lambda flat, r: jnp.take(flat, b + r * lp)       # (Q,) per row
    if leaf_kind == "linear":
        pred = row(mat, 0) * q + row(vec, 0)
    else:
        pred = row(vec, 0)
        for k in range(H):
            hk = jnp.maximum(q * row(mat, k) + row(mat, H + k), 0.0)
            pred = pred + hk * row(mat, 2 * H + k)

    # ---- stage 3: error-bound window ---------------------------------------
    lo = jnp.clip(jnp.floor(pred + row(vec, 1)), 0, n_keys - 1
                  ).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + row(vec, 2)) + 1.0, 1, n_keys
                  ).astype(jnp.int32)
    return lo, hi


# ---------------------------------------------------------------------------
# Stage 4: the window-clamped search kernel, shared by every lookup.
# ---------------------------------------------------------------------------
def _byte_planes(keys):
    """(R, 4 * 128) bf16 of a (R, 128) f32 tile: the four bytes of each
    key's bit pattern, lane-concatenated low byte first.  Each is an integer
    in [0, 255], exact in bf16."""
    bits = jax.lax.bitcast_convert_type(keys, jnp.int32)
    return jnp.concatenate(
        [jnp.bitwise_and(jax.lax.shift_right_logical(bits, 8 * k), 0xFF)
         .astype(jnp.float32).astype(jnp.bfloat16) for k in range(4)],
        axis=1)


def _count_below(keys, planes, q, *, right: bool):
    """(1, N) int32: for each query of the row ``q`` (1, N), how many keys
    of the sorted tile ``keys`` (R, 128) compare below it (``<``, or ``<=``
    when ``right``).  ``planes`` is ``_byte_planes(keys)``."""
    below = (lambda k: k <= q) if right else (lambda k: k < q)
    cmp = below(keys[:, 0:1])                                   # (R, N)
    rc = jnp.sum(cmp.astype(jnp.int32), axis=0, keepdims=True)  # rows below
    onehot = (jax.lax.broadcasted_iota(jnp.int32, cmp.shape, 0) == rc - 1
              ).astype(jnp.bfloat16)
    got = jax.lax.dot_general(planes, onehot, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    b = got.astype(jnp.int32)                                   # (512, N)
    bits = (b[:LANES] | (b[LANES:2 * LANES] << 8)
            | (b[2 * LANES:3 * LANES] << 16) | (b[3 * LANES:] << 24))
    row = jax.lax.bitcast_convert_type(bits, jnp.float32)  # [l, i]: row rc-1
    cnt = jnp.sum(below(row).astype(jnp.int32), axis=0, keepdims=True)
    return jnp.where(rc > 0, LANES * (rc - 1) + cnt, 0)


def _bisect(l, h, c, iters: int):
    """The branchless window search with ``keys[mid] < q`` replaced by the
    equivalent ``mid < c`` (module docstring): ``iters`` steps over
    [l, h), returning the final left end."""
    for _ in range(iters):
        active = h - l > 0
        mid = (l + h) >> 1
        below = mid < c
        l = jnp.where(active & below, mid + 1, l)
        h = jnp.where(active & ~below, mid, h)
    return l


def _search_kernel(*refs, sides: tuple, n_keys: int, tile: int,
                   tile_iters: int):
    """One (query tile i, key tile j) step for each endpoint set e (the
    range lookup searches two: left bounds of lo, right bounds of hi).

    refs: q x E, lo x E, hi x E, keys, out x E."""
    E = len(sides)
    q_refs, lo_refs, hi_refs = refs[:E], refs[E:2 * E], refs[2 * E:3 * E]
    keys_ref = refs[3 * E]
    out_refs = refs[3 * E + 1:]
    load = lambda ref: ref[...].reshape(1, TQ)
    j = pl.program_id(1)
    base = j * tile

    @pl.when(j == 0)
    def _():
        for e in range(E):
            out_refs[e][...] = hi_refs[e][...]

    wins = [(jnp.clip(load(lo_refs[e]) - base, 0, tile),
             jnp.clip(load(hi_refs[e]) - base, 0, tile)) for e in range(E)]
    width = functools.reduce(jnp.maximum,
                             [jnp.max(thi - tlo) for tlo, thi in wins])

    @pl.when(width > 0)
    def _():
        keys = keys_ref[...].reshape(tile // LANES, LANES)
        planes = _byte_planes(keys)
        for e, right in enumerate(sides):
            tlo, thi = wins[e]
            c = _count_below(keys, planes, load(q_refs[e]), right=right)
            l = _bisect(tlo, thi, c, tile_iters)
            cand = jnp.where(l < thi, base + l, n_keys)
            out = jnp.minimum(load(out_refs[e]), cand)
            out_refs[e][...] = out.reshape(out_refs[e].shape)


def _pow2ceil(v: int) -> int:
    return 1 << max(int(v) - 1, 1).bit_length()


def capacity_class(n: int, floor: int = 128) -> int:
    """Pow2 capacity bucket shared by tier storage and the sharded slice
    cache: a tier of ``n`` finite entries is stored +inf-padded to this
    capacity, so array shapes — and with them every jit specialization,
    packed-table layout, and stacked per-shard slice — change only when the
    entry count crosses a power of two.  The 128 floor is one kernel lane
    tile."""
    return max(_pow2ceil(max(int(n), 1)), floor)


def pad_capacity(keys: jax.Array, cap: int) -> jax.Array:
    """+inf-pad a sorted tier (or tier slice) to its capacity class — pads
    sort past every live key, route to the dump bucket, and never win a
    left-boundary search.  A tier already at ``cap`` comes back as is (no
    copy: at 2^27 keys a copy is 1 GiB of device memory)."""
    if keys.shape[0] == cap:
        return keys
    return jnp.pad(keys, (0, cap - keys.shape[0]), constant_values=jnp.inf)


def _tiling(S: int, iters: int | None, tile: int | None) -> tuple:
    """(tile, per-tile search depth) for ``S`` keys: ``tile`` defaults to
    the pow2 ceiling of S capped at TILE_MAX; ``iters`` to the full depth."""
    if tile is None:
        tile = min(TILE_MAX, _pow2ceil(max(S, 128)))
    assert tile % 128 == 0, "key tile must be a multiple of 128 lanes"
    if iters is None:
        iters = full_iters(S)
    return tile, min(iters, full_iters(tile))


def _window_search(qs, los, his, keys, *, sides: tuple, tile: int | None,
                   iters: int | None, interpret: bool):
    """Stage 4 for E endpoint sets in one pass over the key tiles: for each
    e, the window-clamped boundary of ``qs[e]`` in the sorted ``keys``
    within ``[los[e], his[e])`` (left boundary, or right when
    ``sides[e]``).  Every key tile is streamed through VMEM once and
    searched for every set.  Returns one (Q,) int32 array per set."""
    Q = qs[0].shape[0]
    S = keys.shape[0]
    tile, tile_iters = _tiling(S, iters, tile)
    q_pad = -(-Q // TQ) * TQ
    nk = -(-S // tile)
    blk = lambda a: jnp.pad(a, (0, q_pad - Q)).reshape(-1, 1, TQ)
    kp = jnp.pad(keys.astype(jnp.float32), (0, nk * tile - S),
                 constant_values=jnp.inf).reshape(nk, tile // LANES, LANES)
    E = len(sides)
    qspec = pl.BlockSpec((1, 1, TQ), lambda i, j: (i, 0, 0))
    outs = pallas_call(
        functools.partial(_search_kernel, sides=tuple(sides), n_keys=S,
                          tile=tile, tile_iters=tile_iters),
        *[blk(q.astype(jnp.float32)) for q in qs],
        *[blk(lo.astype(jnp.int32)) for lo in los],
        *[blk(hi.astype(jnp.int32)) for hi in his], kp,
        grid=(q_pad // TQ, nk),
        in_specs=[qspec] * (3 * E) + [
            pl.BlockSpec((1, tile // LANES, LANES), lambda i, j: (j, 0, 0))],
        out_specs=[qspec] * E,
        out_shape=[jax.ShapeDtypeStruct((q_pad // TQ, 1, TQ), jnp.int32)] * E,
        interpret=interpret)
    return [o.reshape(-1)[:Q] for o in outs]


def lookup_pallas(queries, root, mat, vec, keys, *, n_leaves: int,
                  root_kind: str = "linear", leaf_kind: str = "linear",
                  iters: int | None = None, tile: int | None = None,
                  interpret: bool = True):
    """Positions (left boundary, window-clamped) of ``queries`` in ``keys``.

    queries: (Q,); root: pack_root block; mat/vec: pack_leaves tables;
    keys: (S,) sorted. ``iters`` is the static window search depth
    (see search_iters); ``tile`` the key-tile size (multiple of 128).
    """
    S = keys.shape[0]
    q = queries.astype(jnp.float32)
    lo, hi = _route_window(
        root, mat.reshape(-1), vec.reshape(-1), q, n_keys=S,
        n_leaves=n_leaves, lp=mat.shape[1], route_n=S, root_kind=root_kind,
        leaf_kind=leaf_kind)
    (out,) = _window_search((q,), (lo,), (hi,), keys, sides=(False,),
                           tile=tile, iters=iters, interpret=interpret)
    return out


# ---------------------------------------------------------------------------
# Two-tier (base + delta) dynamic lookup: the update subsystem's serving
# path.  The base tier takes the static lookup's four stages; the sorted
# delta tier (the device-resident insert buffer, sized by the Lemma 4.1
# rebuild policy) takes a full-depth probe.  The tombstone mask and two-tier
# rank arithmetic are O(Q) gathers in the jitted ops wrapper
# (``ops.dynamic_index_lookup``).
# ---------------------------------------------------------------------------
def _full_probe(dk, q, *, nd: int, d_iters: int, right: bool = False):
    """Full-depth branchless search of the delta tier (XLA, shared by the
    dynamic point and range lookups).  The tier is sorted ascending and
    +inf padded, so both boundaries of a finite query always land within
    the live prefix (``kv <= q`` is False at every +inf pad)."""
    l = jnp.zeros(q.shape, jnp.int32)
    h = jnp.full(q.shape, nd, jnp.int32)
    for _ in range(d_iters):      # unrolled: no loop carry to type under
        active = h - l > 0        # shard_map's varying-axes check
        mid = (l + h) // 2
        kv = jnp.take(dk, jnp.clip(mid, 0, nd - 1))
        below = kv <= q if right else kv < q
        l, h = (jnp.where(active & below, mid + 1, l),
                jnp.where(active & ~below, mid, h))
    return l


def pad_delta(delta_keys, dtype=jnp.float32):
    """+inf-pad the delta tier to a 128-lane multiple (floor 128)."""
    nd = delta_keys.shape[0]
    ndp = max(-(-max(nd, 1) // 128) * 128, 128)
    return jnp.pad(delta_keys.astype(dtype), (0, ndp - nd),
                   constant_values=jnp.inf)


def dynamic_lookup_pallas(queries, root, mat, vec, keys, delta_keys, *,
                          n_leaves: int, route_n: int | None = None,
                          root_kind: str = "linear",
                          leaf_kind: str = "linear",
                          iters: int | None = None, tile: int | None = None,
                          interpret: bool = True):
    """(base_pos, delta_pos) of ``queries`` against the two tiers.

    base_pos is the window-clamped left boundary in ``keys`` (identical
    semantics to :func:`lookup_pallas`); delta_pos is the full-depth left
    boundary in the sorted, +inf-padded ``delta_keys``.  ``route_n`` is the
    frozen routing scale of the dynamic index (defaults to the current key
    count, i.e. static-index behaviour).
    """
    S = keys.shape[0]
    q = queries.astype(jnp.float32)
    lo, hi = _route_window(
        root, mat.reshape(-1), vec.reshape(-1), q, n_keys=S,
        n_leaves=n_leaves, lp=mat.shape[1],
        route_n=S if route_n is None else route_n, root_kind=root_kind,
        leaf_kind=leaf_kind)
    (out,) = _window_search((q,), (lo,), (hi,), keys, sides=(False,),
                           tile=tile, iters=iters, interpret=interpret)
    dk = pad_delta(delta_keys)
    nd = dk.shape[0]
    return out, _full_probe(dk, q, nd=nd, d_iters=full_iters(nd))


# ---------------------------------------------------------------------------
# Fused range lookup: both endpoints of [lo, hi] searched in ONE tile pass.
# The lo endpoint uses the point path's left-bound search; the hi endpoint
# runs the mirrored right-bound search (first position with key > q, i.e.
# the rightmost rank under duplicate keys — the min-merge composes
# identically: the first tile containing a key > q yields the winning
# candidate, tiles whose clipped window is entirely <= q converge to
# l == thi, and +inf capacity padding compares > q for every finite query).
# Each key tile is streamed through VMEM once and searched twice, so a range
# lookup costs one kernel invocation and the same HBM traffic as a single
# point lookup.  Both candidates are window-clamped and seam-verified by the
# ops wrapper (``ops.range_lookup``) exactly like the point path.
# ---------------------------------------------------------------------------
def dynamic_range_pallas(q_lo, q_hi, root, mat, vec, keys, delta_keys, *,
                         n_leaves: int, route_n: int | None = None,
                         root_kind: str = "linear",
                         leaf_kind: str = "linear",
                         iters: int | None = None, tile: int | None = None,
                         interpret: bool = True):
    """(base_lo, base_hi, delta_lo, delta_hi) of range endpoint pairs.

    base_lo/delta_lo are the left boundaries of ``q_lo`` (leftmost rank
    under duplicates — identical semantics to the point path); base_hi/
    delta_hi are the *right* boundaries of ``q_hi`` (first position whose
    key compares > q_hi, i.e. rightmost rank).  Both endpoints ride the
    same grid pass, so each key tile is fetched from HBM exactly once.
    """
    assert q_hi.shape[0] == q_lo.shape[0], "endpoint arrays must pair up"
    S = keys.shape[0]
    ql = q_lo.astype(jnp.float32)
    qh = q_hi.astype(jnp.float32)
    win = lambda q: _route_window(
        root, mat.reshape(-1), vec.reshape(-1), q, n_keys=S,
        n_leaves=n_leaves, lp=mat.shape[1],
        route_n=S if route_n is None else route_n, root_kind=root_kind,
        leaf_kind=leaf_kind)
    (llo, lhi), (rlo, rhi) = win(ql), win(qh)
    blo, bhi = _window_search((ql, qh), (llo, rlo), (lhi, rhi), keys,
                             sides=(False, True), tile=tile, iters=iters,
                             interpret=interpret)
    dk = pad_delta(delta_keys)
    nd = dk.shape[0]
    d_iters = full_iters(nd)
    return (blo, bhi, _full_probe(dk, ql, nd=nd, d_iters=d_iters),
            _full_probe(dk, qh, nd=nd, d_iters=d_iters, right=True))


# ---------------------------------------------------------------------------
# RMRT: fixed-depth masked descent over the flat node tables (see the module
# docstring for the pack_rmrt layout), then the same clamped tiled search
# as the static lookup.
# ---------------------------------------------------------------------------
def pack_rmrt(kind: str, params, is_leaf, child_base, y_start, y_end,
              err_lo, err_hi):
    """Lane-major RMRT node tables: (3H, Np) params + (8, Np) scalars.

    ``params`` are the stacked per-node models (LinearParams or MLPParams,
    leading dim = num_nodes); linear models ride in w1[:, 0] / b2 exactly
    like the RMI leaf tables.  Row layout documented in the module
    docstring.  ``child_base`` must stay f32-exact (node count << 2^24).
    """
    N = int(is_leaf.shape[0])
    if N >= 1 << 24:        # raise (not assert): must survive python -O
        raise ValueError(
            f"RMRT node count {N} exceeds f32 integer resolution (2^24): "
            "child_base pointers in the packed f32 tables would be rounded "
            "silently — raise leaf_cap or shard the tree")
    if kind == "linear":
        w1 = jnp.zeros((N, H), jnp.float32).at[:, 0].set(
            params.a.astype(jnp.float32))
        zeros = jnp.zeros((N, H), jnp.float32)
        b1, w2, b2 = zeros, zeros, params.b
    else:
        w1, b1, w2, b2 = params.w1, params.b1, params.w2, params.b2
    npad = -(-N // 128) * 128
    padT = lambda a: jnp.pad(a.astype(jnp.float32).T, ((0, 0), (0, npad - N)))
    mat = jnp.concatenate([padT(w1), padT(b1), padT(w2)], axis=0)
    vec = jnp.zeros((8, npad), jnp.float32)
    for r, a in ((0, b2), (1, err_lo), (2, err_hi), (3, y_start),
                 (4, y_end), (5, child_base), (6, is_leaf)):
        vec = vec.at[r, :N].set(a.astype(jnp.float32))
    return mat, vec


def _rmrt_route_window(mat, vec, q, *, n_keys: int, npad: int, fanout: int,
                       depth: int, kind: str):
    """Stages 1-3 of the RMRT lookup (XLA; the oracle in ``kernels.ref``
    reimplements it): depth-D masked descent over the flattened node
    tables, then the leaf's error-bound window clamped to ``n_keys``."""
    row = lambda flat, r, idx: jnp.take(flat, idx + r * npad)

    def predict(node):
        if kind == "linear":
            return row(mat, 0, node) * q + row(vec, 0, node)
        pred = row(vec, 0, node)
        for k in range(H):
            hk = jnp.maximum(q * row(mat, k, node) + row(mat, H + k, node),
                             0.0)
            pred = pred + hk * row(mat, 2 * H + k, node)
        return pred

    def body(_, node):
        pred = predict(node)
        ys = row(vec, 3, node)
        span = row(vec, 4, node) - ys
        child = jnp.clip(((pred - ys) * fanout / span).astype(jnp.int32),
                         0, fanout - 1)
        nxt = row(vec, 5, node).astype(jnp.int32) + child
        return jnp.where(row(vec, 6, node) > 0.5, node, nxt)

    node = jax.lax.fori_loop(0, depth, body,
                             jnp.zeros(q.shape, jnp.int32))
    pred = predict(node)
    lo = jnp.clip(jnp.floor(pred + row(vec, 1, node)), 0, n_keys - 1
                  ).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + row(vec, 2, node)) + 1.0, 1, n_keys
                  ).astype(jnp.int32)
    return lo, hi


def rmrt_lookup_pallas(queries, mat, vec, keys, *, fanout: int, depth: int,
                       kind: str = "linear", iters: int | None = None,
                       tile: int | None = None, interpret: bool = True):
    """Positions (left boundary, window-clamped) of ``queries`` in ``keys``
    under the RMRT: the depth-``depth`` descent over the packed node tables
    (``pack_rmrt``), then the error-window-clamped tiled search kernel.
    """
    q = queries.astype(jnp.float32)
    lo, hi = _rmrt_route_window(
        mat.reshape(-1), vec.reshape(-1), q, n_keys=keys.shape[0],
        npad=mat.shape[1], fanout=fanout, depth=depth, kind=kind)
    (out,) = _window_search((q,), (lo,), (hi,), keys, sides=(False,),
                           tile=tile, iters=iters, interpret=interpret)
    return out
