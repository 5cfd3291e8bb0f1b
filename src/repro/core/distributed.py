"""Distributed learned-index service: range-partitioned keys under
``shard_map``, replicated model pool, all_to_all query routing.

Scale design (DESIGN.md §4): a production index over O(10^11) keys does not
fit one host. Keys are range-partitioned across the ``data`` mesh axis (the
pool — 30 MB at eps=0.9 — is replicated). A query batch arrives sharded;
each shard routes its queries to the owning shard with a capacity-bucketed
``all_to_all``, the owner answers with its local RMI (the same jitted lookup
path as the single-host index), and results return via the inverse
``all_to_all``. All collectives are explicit, so the dry-run roofline for
the index service is auditable like the LM cells.

Partitioning invariants (shared by the static and dynamic index): shard
boundaries come from :func:`shard_bounds`, an equal-count split *snapped to
equal-key run starts*, so a run of duplicate keys is always owned by exactly
one shard.  ``splits[s]`` is the last key of shard s and every key of shard
s+1 is strictly greater, hence ``searchsorted(splits, q, side="left")``
routes every query/update for a key to the one shard that can own it and
the global leftmost live rank decomposes as (live keys in shards < dest) +
(local leftmost rank).  Shards may be *empty* (n < n_shards, or runs longer
than a balanced shard): they carry a trivial zero-model RMI over an all-+inf
key block, answer rank 0 / found False, and re-absorb load through
rebalancing.

Dynamic serving (``ShardedDynamicIndex``): each shard owns a full two-tier
``core.updates.DynamicRMI`` — base tier + sorted pow2-capacity delta tier
with tombstone bitmaps, per-leaf Lemma 4.1 budgets driving pool-reuse
rebuilds (``rmi.fit_leaves``).  ``insert_batch``/``delete_batch`` pre-bucket
keys by the split vector on the host and run one device merge per touched
shard; ``find`` dispatches the fused ``dynamic_lookup_pallas`` kernel — or
its jnp oracle — per shard under ``shard_map`` with the same
capacity-bucketed ``all_to_all`` exchange as the static path.  Per-shard
frozen routing scales ride the packed root blocks
(``lookup.pack_root(route_scale=...)``) so one statically-traced kernel
serves every shard.

Slice-cache invalidation contract (the maintenance cost model): the stacked
device state the ``shard_map`` dispatch consumes is assembled from
*per-shard slices* and maintained incrementally, so every mutation path
costs O(touched shards), never O(all shards):

  * Each shard stores its tiers at its **own** capacity class
    (``kernels.lookup.capacity_class`` — pow2, 128 floor); the assembled
    stack pads every slice to the *global* max class with +inf keys / zero
    tombstones / edge-extended prefix sums.
  * Each shard's own tiers live on its home device, the first device of
    its slot on the mesh axis (``_shard_device``); its mutations run
    there.  Stacked tier arrays are assembled per device from the rows
    that device holds, so no device stages the whole stack.
  * A mutation (routed merge, delete, rebuild, migration) marks only the
    touched shards dirty; the next ``find`` rewrites exactly those rows of
    the stacked arrays (the dirty devices' buffers; the root and leaf
    models take one batched row-scatter) and leaves the rest untouched.
    Packed kernel tables ride the same rows: per-shard
    ``mat``/``vec`` come from the shard's cached ``RMIIndex.packed_tables``
    and the root block from ``DynamicRMI.packed_root`` (cacheable forever —
    roots and routing scales are frozen at shard build).
  * Re-padding the whole stack happens **only** when the global capacity
    class actually changes — i.e. a shard's tier outgrows (or a rebuilt
    shard retires) the current global max.  A hot shard doubling *below*
    the global max stays a row-local event.
  * Shard-level scalars (live offsets, rebalance counters) live in a
    device-resident ``(n_shards, 4)`` counter table updated with O(touched)
    row scatters; the rebalance trigger is one jitted reduction over it
    returning two scalars, so trigger cost no longer scales with the host
    counter scan at O(1k) shards.

Skew handling: when the device trigger fires (delta ratio, dead ratio, or
raw live-count skew), whole boundary runs migrate to an adjacent shard and
the split between them moves — monotone and duplicate-run-safe because cuts
snap to run boundaries.  Migration is *incremental*: the donor sheds its
boundary region in place (``DynamicRMI.shed_suffix``/``shed_prefix`` — a
truncation or an exact uniform intercept shift; no refit), and the migrated
run rides the **delta tier** of the receiver via the ordinary routed merge,
at worst triggering localized Lemma 4.1 leaf rebuilds.  Only when the run
overflows the receiver's aggregate Lemma 4.1 insertion headroom
(``bounds.insertion_headroom`` — the regime where most leaves would churn
anyway) does the receiver fall back to one full rebuild; delta-hot shards
with balanced live counts flush their delta in place
(``DynamicRMI.flush_delta``) instead of rebuilding from scratch.

This module is exercised two ways:
  * functionally on small meshes in tests (shard_map over 1-8 CPU devices),
  * structurally in the multi-pod dry-run (lower/compile on 256 devices) via
    ``repro.launch.dryrun --arch index_service``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import drift as drift_mod
from . import rmi as rmi_mod
from .paths import resolve_path

Array = jax.Array


@dataclass
class ShardedIndex:
    """Per-shard RMI leaves + replicated routing table."""
    mesh: Mesh
    axis: str
    splits: Array            # (n_shards - 1,) range-partition boundaries
    # Stacked per-shard RMI components (leading dim = shard), each shard's
    # arrays padded to the max shard size.
    keys: Array              # (n_shards, cap)
    valid: Array             # (n_shards,) number of real keys per shard
    root: rmi_mod.models.LinearParams
    leaves: rmi_mod.models.LinearParams
    err_lo: Array
    err_hi: Array
    n_leaves: int
    search_iters: int | None = None   # error-window depth across all shards
    # Stacked packed kernel tables (lookup.pack_root / pack_leaves per
    # shard) so the per-shard answer can dispatch through the fused Pallas
    # kernel under shard_map.  Packed lazily on the first kernel-path
    # make_lookup_fn — jnp-path consumers (CPU meshes, the 256-device
    # dry-run) never pay for them.
    kroot: Array = None      # (n_shards, ROOT_ROWS, 128)
    kmat: Array = None       # (n_shards, 3H, Lp)
    kvec: Array = None       # (n_shards, 8, Lp)
    _f32_exact: bool | None = None

    @property
    def n_shards(self) -> int:
        return int(self.keys.shape[0])

    @property
    def f32_exact(self) -> bool:
        """Every shard's keys round-trip through f32 (kernel-path
        precondition; the +inf shard padding round-trips trivially).
        Lazily computed — one reduction over the stacked shards."""
        if self._f32_exact is None:
            k32 = self.keys.astype(jnp.float32).astype(jnp.float64)
            self._f32_exact = bool(jnp.all(k32 == self.keys))
        return self._f32_exact

    def packed_tables(self) -> tuple:
        """(kroot, kmat, kvec) stacked per-shard kernel tables, packed on
        first use and cached on the dataclass."""
        if self.kroot is None:
            from ..kernels import lookup as _lk
            kr, km, kv = [], [], []
            for s in range(self.n_shards):
                root_s = jax.tree.map(lambda a, s=s: a[s], self.root)
                leaves_s = jax.tree.map(lambda a, s=s: a[s], self.leaves)
                kr.append(_lk.pack_root("linear", root_s))
                w1, b1, w2, b2 = rmi_mod._leaf_table_arrays(
                    "linear", leaves_s, self.n_leaves)
                m, v = _lk.pack_leaves(w1, b1, w2, b2, self.err_lo[s],
                                       self.err_hi[s])
                km.append(m)
                kv.append(v)
            self.kroot = jnp.stack(kr)
            self.kmat = jnp.stack(km)
            self.kvec = jnp.stack(kv)
        return self.kroot, self.kmat, self.kvec


def shard_bounds(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Equal-count partition positions over sorted ``keys``, snapped to
    equal-key run *starts* so no duplicate run straddles a shard seam.

    Returns (n_shards + 1,) non-decreasing positions b with b[0] = 0 and
    b[-1] = n; shard s owns keys[b[s]:b[s+1]].  b[s] == b[s+1] marks an
    empty shard (n < n_shards, or a run longer than a balanced shard
    swallowing a boundary).  The snap guarantees the routing invariant the
    global-rank arithmetic rests on: every key of shard s+1 is strictly
    greater than the last key of shard s."""
    n = int(keys.shape[0])
    cap = -(-n // n_shards) if n else 0
    b = np.minimum(np.arange(n_shards + 1, dtype=np.int64) * max(cap, 1), n)
    for s in range(1, n_shards):
        p = int(b[s])
        if 0 < p < n and keys[p - 1] == keys[p]:
            b[s] = np.searchsorted(keys, keys[p], side="left")
    return np.maximum.accumulate(b)


def _splits_from_bounds(keys: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """(n_shards - 1,) split values: splits[s] = last key of shard s.
    Shards that are empty *with no key to their left* (an all-empty prefix)
    get -inf so any finite query routes past them; empty shards later in
    the order repeat the previous split (monotone either way)."""
    return np.asarray([keys[bounds[s + 1] - 1] if bounds[s + 1] > 0
                       else -np.inf for s in range(bounds.shape[0] - 2)],
                      np.float64)


def build_sharded(keys: Array, mesh: Mesh, axis: str = "data",
                  n_leaves: int = 1024, pool=None) -> ShardedIndex:
    """Equal-count range partition snapped to duplicate-run boundaries; one
    RMI per shard (empty shards get the trivial zero-model build)."""
    n_shards = mesh.shape[axis]
    keys = jnp.asarray(keys, jnp.float64)
    n = keys.shape[0]
    if n == 0:
        raise ValueError("build_sharded needs at least one key")
    kn = np.asarray(keys)
    bounds = shard_bounds(kn, n_shards)
    cap = max(int(np.diff(bounds).max()), 1)
    splits = jnp.asarray(_splits_from_bounds(kn, bounds))
    shards, valid = [], []
    roots, leaves, elos, ehis = [], [], [], []
    for s in range(n_shards):
        part = keys[int(bounds[s]):int(bounds[s + 1])]
        v = part.shape[0]
        idx = rmi_mod.build_rmi(part, n_leaves=n_leaves, kind="linear",
                                pool=pool)
        part = jnp.pad(part, (0, cap - v), constant_values=jnp.inf)
        shards.append(part)
        valid.append(v)
        roots.append(idx.root)
        leaves.append(idx.leaves)
        elos.append(idx.err_lo)
        ehis.append(idx.err_hi)
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
    from ..kernels.lookup import search_iters
    err_lo_all, err_hi_all = jnp.stack(elos), jnp.stack(ehis)
    return ShardedIndex(
        mesh=mesh, axis=axis, splits=splits,
        keys=jnp.stack(shards), valid=jnp.asarray(valid),
        root=stack(roots), leaves=stack(leaves),
        err_lo=err_lo_all, err_hi=err_hi_all, n_leaves=n_leaves,
        search_iters=search_iters(err_lo_all, err_hi_all, cap))


def make_lookup_fn(index: ShardedIndex, *,
                   capacity_factor: float | None = None,
                   path: str = "auto",
                   use_kernel: bool | None = None,
                   interpret: bool | None = None):
    """Returns a jitted distributed lookup: (q_local sharded on axis) ->
    global ranks, same sharding.

    ``capacity_factor``: per-destination slot budget as a multiple of the
    *balanced* load B/n_shards. None = worst-case B slots per destination
    (paper-faithful, never drops; all_to_all payload ~ n_shards x B).
    A factor like 2.0 shrinks the exchange by n_shards/2 at the cost of
    dropping queries beyond the budget (returned rank -1, retried by the
    caller) — EXPERIMENTS.md §Perf index-service iteration.

    ``path`` routes the per-shard answer through the fused Pallas kernel
    (``kernels.ops.index_lookup``: routing + clamped tiled
    search + sparse seam verification) or the clamped jnp path — the
    shared :func:`core.paths.resolve_path` contract (``"auto"`` = kernel
    on TPU backends when every shard's keys are f32-exact).
    ``use_kernel=`` is the deprecated boolean shim.  ``interpret``
    forwards to the kernel (None = auto: interpreter off-TPU)."""
    mesh, axis = index.mesh, index.axis
    n_shards = index.n_shards
    n_leaves = index.n_leaves
    cap = index.keys.shape[1]

    iters = index.search_iters      # static across shards; closure-captured

    use_kernel = resolve_path(path, f32_exact=lambda: index.f32_exact,
                              use_kernel=use_kernel,
                              what="sharded key space")

    if use_kernel:
        from ..kernels import ops as kernel_ops

        def local_lookup(tables, keys, q):
            kroot, kmat, kvec = tables
            return kernel_ops.index_lookup(
                q, kroot, kmat, kvec, keys, n_leaves=n_leaves,
                root_kind="linear", leaf_kind="linear", iters=iters,
                interpret=interpret)

        tables = index.packed_tables()
    else:
        def local_lookup(tables, keys, q):
            root, leaves, elo, ehi = tables
            b = rmi_mod.root_buckets("linear", root, q, n_leaves, cap)
            p = jax.tree.map(lambda a: a[b], leaves)
            pred = rmi_mod.models.linear_predict(p, q)
            lo = jnp.clip(jnp.floor(pred + elo[b]), 0,
                          cap - 1).astype(jnp.int32)
            hi = jnp.clip(jnp.ceil(pred + ehi[b]) + 1, 1,
                          cap).astype(jnp.int32)
            return rmi_mod.verified_search(keys, q, lo, hi, iters=iters)

        tables = (index.root, index.leaves, index.err_lo, index.err_hi)

    def shard_fn(splits, keys, valid, tables, q_local):
        """Runs per shard. q_local: (B_local,). All index args are the
        *local* shard's slice (shard_map strips the leading shard dim)."""
        B = q_local.shape[0]
        me = jax.lax.axis_index(axis)
        # capacity-bucketed routing: C slots per destination shard
        if capacity_factor is None:
            C = B          # worst case: all local queries target one shard
        else:
            C = max(int(B * capacity_factor / n_shards), 1)

        def answer(rq, live):
            # +inf exchange-padding slots are masked to a member query
            # first and answered `valid` (= rank past end) directly: on an
            # inf-padded (ragged) shard an inf query always fails the
            # left-boundary seam check, and a batch of them would blow the
            # sparse seam budget and demote every lookup to the dense
            # re-search fallback (both the kernel's _seam_fix and the jnp
            # path's verified_search).  An *empty* shard has no member key
            # (keys[0][0] is itself +inf) — mask to 0.0, which resolves to
            # position 0 against its all-+inf block.
            member = jnp.where(jnp.isfinite(keys[0][0]), keys[0][0], 0.0)
            ranks = local_lookup(jax.tree.map(lambda a: a[0], tables),
                                 keys[0], jnp.where(live, rq, member))
            ranks = jnp.where(live, ranks, valid[0])
            return (jnp.minimum(ranks, valid[0]) + me * cap)[:, None]

        # With a finite capacity_factor, queries beyond the budget keep
        # rank -1 (caller retries).
        (ranks,) = _routed_exchange(
            axis, n_shards, splits, q_local, C, answer,
            (-1 if capacity_factor is not None else 0,))
        return ranks

    fn = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh),
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=_checks_vma(use_kernel, interpret))

    @jax.jit
    def lookup(q_global: Array) -> Array:
        return fn(index.splits, index.keys, index.valid, tables, q_global)

    return lookup


def _auto_axes(mesh: Mesh) -> Mesh:
    """The mesh every ``shard_map`` program here runs on: ``mesh`` with all
    axes typed Auto.  ``jax.make_mesh`` types its axes Explicit, and an
    array sharded over an Explicit axis refuses the plain indexing that
    this module and its callers apply to results (``found[:Q]``)."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _shard_device(mesh: Mesh, axis: str, s: int):
    """Home device of shard ``s``'s own tiers: the first device of its
    slot along ``axis``."""
    ax = mesh.axis_names.index(axis)
    return np.moveaxis(mesh.devices, ax, 0)[s:s + 1].flat[0]


def _checks_vma(use_kernel: bool, interpret: bool | None) -> bool:
    """``check_vma`` for a ``shard_map`` program here: on, except around
    an *interpreted* Pallas kernel.  The Pallas interpreter evaluates the
    kernel body under the check, with invariant grid indices and literals
    next to varying blocks, which no kernel passes.  Compiled kernels carry
    their operands' varying axes (``kernels.call.pallas_call``)."""
    if not use_kernel:
        return True
    from ..kernels.ops import _default_interpret
    return not (_default_interpret() if interpret is None else interpret)


def _cumcount(ids: Array, n_bins: int) -> Array:
    """Occurrence rank of each element among equal ids (stable)."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    start = jnp.searchsorted(sorted_ids, jnp.arange(n_bins))
    ranks_sorted = jnp.arange(n, dtype=jnp.int32) - start[sorted_ids].astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(ranks_sorted)


def _routed_exchange(axis: str, n_shards: int, splits, q_local, C: int,
                     answer_fn, fills: tuple) -> list:
    """The capacity-bucketed query exchange shared by every shard_map body
    here (static lookup and dynamic find): route each local query to its
    owning shard (``searchsorted`` over the split vector, C slots per
    destination, +inf padding), ``all_to_all`` out, apply
    ``answer_fn(rq, live) -> (n_shards * C, P) int32 payload`` locally,
    and return the payload through the inverse exchange scattered back to
    each query's origin slot.

    Returns one (B,) int32 array per payload column; ``fills[k]`` is
    column k's value for unanswered slots (queries beyond a finite
    capacity budget, or exchange padding).
    """
    B = q_local.shape[0]
    dest = jnp.searchsorted(splits, q_local, side="left").astype(jnp.int32)
    slot = jnp.clip(_cumcount(dest, n_shards), 0, C - 1)
    send = jnp.full((n_shards, C), jnp.inf, q_local.dtype)
    send = send.at[dest, slot].set(q_local)
    opos = jnp.full((n_shards, C), -1, jnp.int32)
    opos = opos.at[dest, slot].set(jnp.arange(B, dtype=jnp.int32))
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
    rpos = jax.lax.all_to_all(opos, axis, 0, 0, tiled=False)
    rq = recv.reshape(-1)
    live = rq < jnp.inf                  # excludes +inf pads and NaN
    payload = answer_fn(rq, live)
    P = payload.shape[-1]
    back = jax.lax.all_to_all(payload.reshape(n_shards, C, P), axis, 0, 0,
                              tiled=False)
    bpos = jax.lax.all_to_all(rpos, axis, 0, 0, tiled=False)
    # scatter answers to their origin slots; padding (pos -1) is routed
    # out of range and dropped, leaving the fill value.
    tgt = jnp.where(bpos.reshape(-1) >= 0, bpos.reshape(-1), B)
    fv = back.reshape(-1, P)
    return [jnp.full((B,), fills[k], jnp.int32).at[tgt].set(fv[:, k],
                                                            mode="drop")
            for k in range(P)]


# ---------------------------------------------------------------------------
# Sharded dynamic index: per-shard two-tier DynamicRMI with routed updates,
# fused per-shard find under shard_map, and run-snapped split rebalancing.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, donate_argnums=(0,))
def _row_scatter_jit(dst: Array, idx: Array, rows: Array) -> Array:
    return dst.at[idx].set(rows)


def scatter_rows_donated(dst: Array, idx: Array, rows: Array) -> Array:
    """Batched row scatter ``dst[idx] = rows`` with the destination buffer
    *donated*: the restack slice cache's model rows and the serve
    front-end's tenant stack rewrite dirty rows truly in place instead of
    allocating a copy of the whole stacked array per mutation.  The caller
    must drop its handle to ``dst`` (it is invalidated by donation) and
    keep only the returned array.

    No-copy assertion: when XLA accepts the donation it consumes the input
    buffer and jax marks the handle deleted — ``dst.is_deleted()`` is the
    signal jax exposes for "the write aliased, no copy was scheduled"
    (refused donations leave the input alive and warn instead).  The CPU,
    GPU and TPU clients all honor input-output aliasing for this
    same-shape scatter, so a live ``dst`` after the call is a real
    regression, not backend noise.
    """
    out = _row_scatter_jit(dst, idx, rows)
    if not dst.is_deleted():
        raise AssertionError(
            "row-scatter restack was not donated: XLA refused the "
            "input-output alias and scheduled a copy")
    return out


@jax.jit
def _offs_jit(counts: Array) -> Array:
    """Per-shard global live-rank offsets from the device counter table:
    offs[s] = live keys in shards < s (one cumsum — no host scan)."""
    live = counts[:, 0] - counts[:, 1] + counts[:, 2]
    live = live.astype(jnp.int32)
    return jnp.cumsum(live, dtype=jnp.int32) - live


@jax.jit
def _rebalance_trigger_jit(counts: Array, muted: Array, ratio, skew):
    """The rebalance trigger as one device reduction over the counter
    table (columns: base_n, base_dead, delta_live, delta_dead): returns
    (hot shard id or -1, skewed?, delta-hot?, dead-hot?) — the only values
    the host-side policy needs, so the per-batch trigger cost is four
    synced scalars instead of an O(n_shards) host counter scan."""
    livei = counts[:, 0] - counts[:, 1] + counts[:, 2]
    live = livei.astype(jnp.float64)
    dlive = counts[:, 2].astype(jnp.float64)
    deadf = (counts[:, 1] + counts[:, 3]).astype(jnp.float64)
    stored = (counts[:, 0] + counts[:, 2] + counts[:, 3]).astype(jnp.float64)
    delta_hot = dlive / jnp.maximum(live, 1.0) > ratio
    dead_hot = deadf / jnp.maximum(stored, 1.0) > ratio
    tier = delta_hot | dead_hot
    mean = jnp.maximum(jnp.sum(live) / live.shape[0], 1.0)
    skewed = (live > skew * mean) & (livei != muted)
    trig = tier | skewed
    hot = jnp.argmax(jnp.where(trig, live, -1.0)).astype(jnp.int32)
    any_ = jnp.any(trig)
    return (jnp.where(any_, hot, -1), skewed[hot] & any_,
            delta_hot[hot] & any_, dead_hot[hot] & any_)


@dataclass
class ShardedDynamicIndex:
    """Range-partitioned two-tier dynamic index (module docstring: layout,
    slice-cache invalidation contract, and rebalance policy).  Mutations
    are host-driven per shard (each shard is a ``core.updates.DynamicRMI``
    with its own delta tier, tombstones, and Lemma 4.1 rebuild policy);
    serving assembles the per-shard slices into stacked device arrays —
    maintained incrementally, O(touched shards) per mutation — and answers
    a query batch in one ``shard_map`` dispatch.  Queries must be finite
    (the exchange uses +inf as its padding sentinel, like
    ``make_lookup_fn``)."""
    mesh: Mesh
    axis: str
    splits: np.ndarray                  # (n_shards - 1,) host split values
    shards: list                        # per-shard core.updates.DynamicRMI
    eps: float
    n_leaves: int
    pool: object = None
    # Rebalance policy: a shard whose delta tier holds more than
    # ``rebalance_ratio`` of its live keys (insert-hot), whose dead fraction
    # crosses the same ratio (delete-hot), or whose live count exceeds
    # ``rebalance_skew`` x the mean, sheds/absorbs whole boundary runs
    # to/from an adjacent shard and the split between them moves.  None
    # disables rebalancing.
    rebalance_ratio: float | None = 0.5
    rebalance_skew: float = 2.0
    # Migration fallback rule: a migrated run rides the receiver's delta
    # tier while its size stays within this multiple of the receiver's
    # aggregate Lemma 4.1 insertion headroom (``bounds.insertion_headroom``).
    # Per-leaf budgets are rebuild *triggers*, not soundness limits — an
    # over-budget boundary leaf rebuilds locally during the routed merge —
    # so a small multiple keeps the refit work localized; a run several
    # times the headroom would refit most leaves anyway (or lands on a
    # trivial empty receiver, headroom 0), and falls back to one full
    # receiver rebuild.
    migrate_headroom_factor: float = 4.0
    rebalances: int = 0
    # Maintenance-cost accounting (the O(touched) contract, assertable):
    migrations_incremental: int = 0     # delta-riding migrations
    migrations_full: int = 0            # receiver headroom-overflow rebuilds
    restack_full: int = 0               # cold stack assemblies (capacity
                                        # class changes / first use)
    restack_rows: int = 0               # dirty slice rows rewritten in place
    capacity_shrinks: int = 0           # shards whose tiers stepped a
                                        # capacity class back down
    # Shards replaced by trivial empty shards during a damaged restore
    # (persist.restore_sharded on_corrupt="quarantine"): queries routed to
    # their ranges answer found=False until the operator re-feeds them.
    quarantined: list = field(default_factory=list)
    build_kwargs: dict = field(default_factory=dict)
    _stack: dict | None = None          # assembled stacked device state
    _dirty: set = field(default_factory=set)    # shard ids needing re-slice
    _counts: Array = None               # (n_shards, 4) i64 device counters:
                                        # base_n, base_dead, delta_live,
                                        # delta_dead
    # Skew triggers that migration cannot resolve (one duplicate run bigger
    # than the skew threshold: cuts snap to run boundaries, so there is
    # nothing to move) are muted at the failing live count — re-armed as
    # soon as the shard's live count changes.  Tier-ratio triggers never
    # need this: their in-place flush/rebuild fallback always clears them.
    _muted: Array = None                # (n_shards,) i64 live count, -1 off
    # Per-shard drift monitor mirror: (n_shards, 2) device table of
    # [KS score, drifted latch] rows (``drift.state_row``), refreshed with
    # the same O(touched) row scatters as the counter table so the
    # maintenance trigger (``maybe_swap``) costs one sync, never a host
    # scan over shard DriftStates.  All-zero when drift monitoring is off.
    _drift: Array = None                # (n_shards, 2) f64 [score, drifted]
    swaps_committed: int = 0            # pool hot-swaps across all shards
    # Host mirrors of per-shard shape/depth metadata, updated O(touched):
    # capacity classes decide when the global pad width must change, the
    # depth vector feeds the static search depth of the find trace.
    _bcaps: np.ndarray = None
    _dcaps: np.ndarray = None
    _iters_vec: np.ndarray = None

    @classmethod
    def build(cls, keys, mesh: Mesh, axis: str = "data",
              n_leaves: int = 256, pool=None, eps: float = 0.9,
              rebalance_ratio: float | None = 0.5,
              rebalance_skew: float = 2.0, **rmi_kwargs):
        """Partition sorted ``keys`` with :func:`shard_bounds` (run-snapped,
        empty shards allowed) and build one ``DynamicRMI`` per shard."""
        from .updates import DynamicRMI
        rmi_kwargs.setdefault("kind", "linear")
        if rmi_kwargs.get("root_kind", "linear") != "linear":
            raise ValueError(
                "ShardedDynamicIndex requires a monotone (linear) root: "
                "split routing and run snapping assume key order")
        kn = np.asarray(keys, np.float64)
        n_shards = mesh.shape[axis]
        bounds = shard_bounds(kn, n_shards)
        shards = []
        for s in range(n_shards):
            with jax.default_device(_shard_device(mesh, axis, s)):
                shards.append(DynamicRMI.build(
                    kn[bounds[s]:bounds[s + 1]], pool=pool, eps=eps,
                    n_leaves=n_leaves, **rmi_kwargs))
        idx = cls(mesh=mesh, axis=axis,
                  splits=_splits_from_bounds(kn, bounds), shards=shards,
                  eps=eps, n_leaves=n_leaves, pool=pool,
                  rebalance_ratio=rebalance_ratio,
                  rebalance_skew=rebalance_skew, build_kwargs=rmi_kwargs)
        idx._init_maintenance()
        return idx

    def _init_maintenance(self) -> None:
        """Seed the device counter table, the skew mutes, and the host
        capacity/depth mirrors — the only full-shard scan outside a cold
        restack; everything after build updates these O(touched)."""
        S = self.n_shards
        self._bcaps = np.asarray(
            [d.index.keys.shape[0] for d in self.shards], np.int64)
        self._dcaps = np.asarray(
            [d.delta_keys.shape[0] for d in self.shards], np.int64)
        self._iters_vec = np.asarray(
            [d.index.search_iters for d in self.shards], np.int64)
        self._counts = jnp.asarray(
            [[d.base_n, d.base_dead_count, d.delta_live, d.delta_dead_count]
             for d in self.shards], jnp.int64)
        self._muted = jnp.full((S,), -1, jnp.int64)
        self._drift = jnp.stack(
            [drift_mod.state_row(d.drift) for d in self.shards])

    def _touch(self, ids) -> None:
        """Mark shards mutated: refresh their counter rows (one batched
        device row-scatter), host capacity/depth mirrors, and the dirty set
        the next restack consumes.  O(touched shards)."""
        ids = sorted({int(s) for s in ids})
        if not ids:
            return
        for s in ids:
            d = self.shards[s]
            # Eager capacity step-down (hysteresis inside shrink_capacity):
            # no shrinkable state survives a mutation, so a cold restack is
            # always a pure re-assembly of the logical state — the warm/cold
            # bit-exactness contract the restack-cache tests pin.
            with self._on(s):
                shrank = d.shrink_capacity()
            if shrank:
                self.capacity_shrinks += 1
            self._bcaps[s] = d.index.keys.shape[0]
            self._dcaps[s] = d.delta_keys.shape[0]
            self._iters_vec[s] = d.index.search_iters
            self._dirty.add(s)
        vals = np.asarray(
            [[self.shards[s].base_n, self.shards[s].base_dead_count,
              self.shards[s].delta_live, self.shards[s].delta_dead_count]
             for s in ids], np.int64)
        self._counts = self._counts.at[jnp.asarray(ids)].set(
            jnp.asarray(vals))
        self._drift = self._drift.at[jnp.asarray(ids)].set(
            jnp.stack([drift_mod.state_row(self.shards[s].drift)
                       for s in ids]))

    # -- shape / bookkeeping ----------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def _on(self, s: int):
        """Context for work on shard ``s``: it runs, and the arrays it
        makes land, on the shard's home device (:func:`_shard_device`)."""
        return jax.default_device(_shard_device(self.mesh, self.axis, s))

    def shard_sharding(self, lead: int = 0) -> NamedSharding:
        """Placement of stacked per-shard state: the shard axis (after
        ``lead`` unsharded leading axes) split over the mesh, so each
        device holds only its own shards' slices."""
        return NamedSharding(_auto_axes(self.mesh),
                             P(*(None,) * lead, self.axis))

    @property
    def f32_exact(self) -> bool:
        """Every shard's tiers round-trip through f32 (kernel path
        precondition, same contract as ``DynamicRMI.find``)."""
        return all(d.f32_exact for d in self.shards)

    @property
    def total_live(self) -> int:
        return int(self.live_counts().sum())

    def live_counts(self) -> np.ndarray:
        return np.asarray([d.live_count for d in self.shards], np.int64)

    def live_keys(self) -> np.ndarray:
        """Sorted live keys across every shard (host; ``find``'s global
        rank indexes exactly this array)."""
        return np.concatenate([d.live_keys() for d in self.shards])

    # -- mutation ----------------------------------------------------------
    def _route(self, keys: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.splits, keys, side="left")

    def insert_batch(self, keys) -> None:
        """Host pre-bucket by the split vector, one device merge per touched
        shard (each shard's ``DynamicRMI.insert_batch`` runs its own Lemma
        4.1 budget accounting and pool-reuse rebuilds).  Only the touched
        shards' cached slices invalidate."""
        keys = np.asarray(keys, np.float64).ravel()
        if keys.size == 0:
            return
        dest = self._route(keys)
        touched = np.unique(dest)
        for s in touched:
            with self._on(s):
                self.shards[s].insert_batch(keys[dest == s])
        self._touch(touched)
        self._maybe_rebalance()

    def delete_batch(self, keys) -> None:
        """Routed tombstone deletes (per-shard semantics — duplicates within
        one batch collapse to a single removal, like ``DynamicRMI``)."""
        keys = np.asarray(keys, np.float64).ravel()
        if keys.size == 0:
            return
        dest = self._route(keys)
        touched = np.unique(dest)
        for s in touched:
            with self._on(s):
                self.shards[s].delete_batch(keys[dest == s])
        self._touch(touched)
        self._maybe_rebalance()

    # -- rebalance ---------------------------------------------------------
    def _maybe_rebalance(self) -> None:
        """Load skew resolves by migration (boundary runs move between
        neighbours); tier triggers resolve *in place* — a delta-hot shard
        flushes its tier into the base (localized refits), a dead-hot shard
        rebuilds to purge base tombstones.  Migration deliberately does not
        answer tier triggers any more: the incremental donor/receiver paths
        leave tombstones and delta entries where they are, so only the
        in-place resolutions actually clear those ratios."""
        if self.rebalance_ratio is None or self.n_shards == 1:
            return
        hot_d, skew_d, delta_d, dead_d = _rebalance_trigger_jit(
            self._counts, self._muted, jnp.float64(self.rebalance_ratio),
            jnp.float64(self.rebalance_skew))
        hot = int(hot_d)
        if hot < 0:
            return
        if bool(skew_d):
            nb = [s for s in (hot - 1, hot + 1) if 0 <= s < self.n_shards]
            lv = {s: self.shards[s].live_count for s in [*nb, hot]}
            if lv[hot] >= min(lv[s] for s in nb):
                src, dst = hot, min(nb, key=lambda s: lv[s])     # shed
            else:
                src, dst = max(nb, key=lambda s: lv[s]), hot     # absorb
            if self._migrate(src, dst):
                self.rebalances += 1
                self._muted = self._muted.at[
                    jnp.asarray([src, dst])].set(-1)
                self._touch([src, dst])
                return
            if not (bool(delta_d) or bool(dead_d)):
                # Unmovable skew (one giant duplicate run): mute this
                # trigger at the current live count so every later batch
                # doesn't pay a fruitless donor live_keys() sync.
                self._muted = self._muted.at[hot].set(lv[hot])
                return
        if bool(dead_d):
            # Base tombstones only purge through a rebuild — in place, so
            # the trigger doesn't re-fire fruitlessly every batch.
            self._rebuild_shard(hot, self.shards[hot].live_keys())
        else:
            # Delta-hot: flush the tier into the base, refitting only the
            # leaves that hold delta entries.
            with self._on(hot):
                self.shards[hot].flush_delta()
        self.rebalances += 1
        self._touch([hot])

    def _migrate(self, src: int, dst: int) -> bool:
        """Move ~half the live-count excess of ``src`` to adjacent ``dst``
        as whole boundary runs and update the split between them —
        *incrementally*: the donor sheds its boundary region in place
        (``shed_suffix``/``shed_prefix`` — truncation or exact uniform
        intercept shift, no refit) and the migrated run rides the
        receiver's delta tier through the ordinary routed merge, at worst
        triggering localized Lemma 4.1 leaf rebuilds.  Only when the run
        overflows the receiver's aggregate Lemma 4.1 insertion headroom
        (the regime where most of its leaves would churn anyway — e.g. a
        trivial empty receiver) does the receiver fall back to one full
        rebuild; the donor never does.  Cuts snap to run boundaries so the
        strict-inequality routing invariant survives duplicate-heavy data;
        a cut that would move everything (one giant run) is skipped."""
        a = self.shards[src].live_keys()
        recv = self.shards[dst]
        m = int(a.size - recv.live_count) // 2
        if m <= 0 or a.size < 2:
            return False
        if dst == src + 1:
            c = int(np.searchsorted(a, a[a.size - m], side="left"))
            if c <= 0:
                return False
            moved, split_key = a[c:], float(a[c - 1])
            with self._on(src):
                self.shards[src].shed_suffix(split_key)
            self.splits[src] = split_key
        else:
            c = int(np.searchsorted(a, a[m], side="left"))
            if c <= 0:
                return False
            moved, split_key = a[:c], float(a[c - 1])
            with self._on(src):
                self.shards[src].shed_prefix(split_key)
            self.splits[dst] = split_key
        if moved.size <= self.migrate_headroom_factor * \
                recv.insertion_headroom:
            with self._on(dst):
                recv.insert_batch(moved)    # rides the delta tier
            self.migrations_incremental += 1
        else:
            live = recv.live_keys()
            merged = np.concatenate(
                [moved, live] if dst == src + 1 else [live, moved])
            self._rebuild_shard(dst, merged)
            self.migrations_full += 1
        return True

    def _rebuild_shard(self, s: int, keys: np.ndarray) -> None:
        from .updates import DynamicRMI
        with self._on(s):
            self.shards[s] = DynamicRMI.build(
                np.asarray(keys), pool=self.pool, eps=self.eps,
                n_leaves=self.n_leaves, **self.build_kwargs)

    # -- drift maintenance -------------------------------------------------
    def drift_scores(self) -> np.ndarray:
        """(n_shards, 2) [KS score, drifted latch] snapshot of the device
        drift table (one sync; all-zero when monitoring is off)."""
        return np.asarray(self._drift)

    def maybe_swap(self) -> int:
        """Pool hot-swap pass over every drift-latched shard: read the
        device drift table once (the only sync), run each flagged shard's
        ``DynamicRMI.maybe_swap`` — Algorithm 1 pool selection over its
        over-budget leaves, committed per leaf only when the on-device
        Lemma 4.1 bound check holds — and push swapped shards through the
        dirty-row slice cache (``_touch``), so new leaf/bound rows rewrite
        in place on the next find instead of forcing a cold re-pad.
        Returns the number of leaves swapped across all shards."""
        if all(d.drift is None for d in self.shards):
            return 0
        latched = set(
            np.flatnonzero(self.drift_scores()[:, 1] > 0.0).tolist())
        total = 0
        for s, d in enumerate(self.shards):
            if d.drift is None:
                continue
            # Un-latched shards still take the maintenance pass: the
            # per-shard call is where deferred over-budget refits run
            # (swap-mode insert_batch keeps them off the insert path).
            if s not in latched and not (d.n_inserts > d.budget).any():
                continue
            rb0 = d.rebuilds
            with self._on(s):
                n = d.maybe_swap()
            if n or d.rebuilds != rb0:
                total += n
                self._touch([s])
        self.swaps_committed += total
        return total

    # -- serving: the per-shard slice cache --------------------------------
    # Invalidation contract (module docstring): mutations mark shards dirty
    # via _touch; _stacked rewrites exactly the dirty rows of the stacked
    # arrays, re-assembling from scratch only when the *global* capacity
    # class changed.
    @staticmethod
    def _pads(bcap: int, dcap: int):
        from ..kernels.lookup import pad_capacity as padk
        padz = lambda a, c: a if a.shape[0] == c else \
            jnp.pad(a, (0, c - a.shape[0]))
        padp = lambda a, c: a if a.shape[0] == c + 1 else \
            jnp.pad(a, (0, c + 1 - a.shape[0]), mode="edge")
        return padk, padz, padp

    def _slice_rows(self, s: int, bcap: int, dcap: int) -> dict:
        """One shard's slice set, padded (on its home device) to the global
        capacity classes — the unit of incremental restacking."""
        d = self.shards[s]
        padk, padz, padp = self._pads(bcap, dcap)
        with self._on(s):
            return dict(
                route_n=jnp.float64(d.route_n),
                base=padk(d.index.keys, bcap),
                bdead=padz(d.base_dead, bcap),
                bpsum=padp(d.base_psum, bcap),
                dk=padk(d.delta_keys, dcap),
                ddead=padz(d.delta_dead, dcap),
                dpsum=padp(d.delta_psum, dcap),
                err_lo=d.index.err_lo,
                err_hi=d.index.err_hi)

    _ROW_KEYS = ("route_n", "base", "bdead", "bpsum", "dk", "ddead",
                 "dpsum", "err_lo", "err_hi")

    def _stacked(self) -> dict:
        """Assemble (or incrementally refresh) the stacked device state the
        ``shard_map`` dispatch consumes.  Dirty rows rewrite in place; a
        cold full assembly happens only on first use or when the global
        capacity class changes (a shard's tier outgrew — or a rebuilt shard
        retired — the current global max).  The packed kernel tables are a
        lazy sub-entry riding the same rows, so jnp-path consumers never
        pay for them."""
        bcap = int(self._bcaps.max())  # tracelint: ok[hot-sync](np mirror)
        dcap = int(self._dcaps.max())  # tracelint: ok[hot-sync](np mirror)
        st = self._stack
        if st is None or st["bcap"] != bcap or st["dcap"] != dcap:
            return self._restack_full(bcap, dcap)
        if self._dirty:
            self._restack_rows(st, sorted(self._dirty), bcap, dcap)
        return st

    def _restack_full(self, bcap: int, dcap: int) -> dict:
        """Cold assembly over every shard (first use / capacity-class
        change).  Also the capacity-class catch-all: shards that arrived
        oversized without passing through ``_touch`` (a just-restored or
        just-resharded index) step down here before the pad widths are
        fixed; for a maintained index the sweep is a no-op (``_touch``
        shrinks eagerly)."""
        for s, d in enumerate(self.shards):
            with self._on(s):
                shrank = d.shrink_capacity()
            if shrank:
                self.capacity_shrinks += 1
                self._bcaps[s] = d.index.keys.shape[0]
                self._dcaps[s] = d.delta_keys.shape[0]
                self._iters_vec[s] = d.index.search_iters
        bcap = int(self._bcaps.max())  # tracelint: ok[hot-sync](np mirror)
        dcap = int(self._dcaps.max())  # tracelint: ok[hot-sync](np mirror)
        stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
        rows = [self._slice_rows(s, bcap, dcap)
                for s in range(self.n_shards)]
        self._stack = dict(
            bcap=bcap, dcap=dcap,
            splits=jnp.asarray(self.splits),
            offs=_offs_jit(self._counts),
            root=stack([d.index.root for d in self.shards]),
            leaves=stack([d.index.leaves for d in self.shards]),
            leaf_kind=self.shards[0].index.leaf_kind,
            iters=int(self._iters_vec.max()),   # tracelint: ok[hot-sync](np mirror)
            packed=None,
            **{k: self._assemble({s: r[k] for s, r in enumerate(rows)})
               for k in self._ROW_KEYS})
        self.restack_full += 1
        self._dirty.clear()
        return self._stack

    def _assemble(self, rows: dict, old: Array | None = None) -> Array:
        """Stack per-shard rows (``rows[s]``) along a leading shard axis
        straight onto the mesh (:meth:`shard_sharding`): each device gets
        only its own shard's row, so no device stages the whole stack.
        With ``old`` (the previous stack), devices whose shard has no new
        row keep their buffers of ``old``."""
        sh = self.shard_sharding()
        shape = old.shape if old is not None else \
            (self.n_shards,) + next(iter(rows.values())).shape
        keep = {} if old is None else \
            {b.device: b.data for b in old.addressable_shards}
        bufs = []
        for dev, index in sh.addressable_devices_indices_map(shape).items():
            s = index[0].start or 0     # one shard per device slot
            if s in rows:
                with jax.default_device(dev):
                    bufs.append(jnp.expand_dims(rows[s], 0))
            else:
                bufs.append(keep[dev])
        return jax.make_array_from_single_device_arrays(shape, sh, bufs)

    def _restack_rows(self, st: dict, ids: list, bcap: int,
                      dcap: int) -> None:
        """Rewrite the dirty shards' rows of the stacked arrays — new
        buffers on the dirty shards' devices, one batched row-scatter per
        model array — O(touched) slice work."""
        rows = [self._slice_rows(s, bcap, dcap) for s in ids]
        idx = jnp.asarray(ids)
        for k in self._ROW_KEYS:
            st[k] = self._assemble(
                {s: r[k] for s, r in zip(ids, rows, strict=True)}, st[k])
        scat = lambda t, *r: scatter_rows_donated(t, idx, jnp.stack(r))
        st["root"] = jax.tree.map(
            scat, st["root"], *[self.shards[s].index.root for s in ids])
        st["leaves"] = jax.tree.map(
            scat, st["leaves"], *[self.shards[s].index.leaves for s in ids])
        if st["packed"] is not None:
            packs = [self._shard_pack(s) for s in ids]
            st["packed"] = tuple(
                scatter_rows_donated(t, idx,
                                     jnp.stack([p[i] for p in packs]))
                for i, t in enumerate(st["packed"]))
        st["offs"] = _offs_jit(self._counts)
        st["splits"] = jnp.asarray(self.splits)
        st["iters"] = int(self._iters_vec.max())  # tracelint: ok[hot-sync](np mirror)
        self.restack_rows += len(ids)
        self._dirty.clear()

    def _shard_pack(self, s: int) -> tuple:
        """One shard's packed kernel tables: mat/vec from the shard's
        cached ``RMIIndex.packed_tables``, the root block from the
        shard-lifetime ``DynamicRMI.packed_root`` cache (its frozen routing
        scale folded in, so the kernel traces once with static
        ``route_n = n_leaves``)."""
        d = self.shards[s]
        with self._on(s):
            _, mat, vec = d.index.packed_tables()
            return d.packed_root(self.n_leaves), mat, vec

    def _packed_stack(self, st: dict) -> tuple:
        """Stacked per-shard kernel tables (lazy: first kernel-path find,
        then maintained row-wise by :meth:`_restack_rows`)."""
        if st["packed"] is None:
            packs = [self._shard_pack(s) for s in range(self.n_shards)]
            st["packed"] = tuple(jnp.stack([p[i] for p in packs])
                                 for i in range(3))
        return st["packed"]

    def find(self, queries, *, path: str = "auto",
             use_kernel: bool | None = None,
             interpret: bool | None = None) -> tuple[Array, Array]:
        """(found, global live rank) per query, one ``shard_map`` dispatch:
        queries route to their owning shard by the split vector (capacity-
        bucketed ``all_to_all``), the owner answers with its fused two-tier
        find — the ``dynamic_lookup_pallas`` kernel via ``ops.dynamic_find``
        or the jnp oracle — and the globalized answer returns through the
        inverse exchange.  Path-selection contract mirrors
        ``DynamicRMI.find`` (:func:`core.paths.resolve_path`)."""
        q = jnp.asarray(queries, jnp.float64)
        use_kernel = resolve_path(path, f32_exact=lambda: self.f32_exact,
                                  use_kernel=use_kernel,
                                  what="sharded key space")
        st = self._stacked()
        Q = q.shape[0]
        qp = -(-max(Q, 1) // self.n_shards) * self.n_shards
        if qp != Q:
            q = jnp.pad(q, (0, qp - Q))      # 0.0 pads; sliced off below
        fn = _sharded_dynamic_find_fn(
            self.mesh, self.axis, n_leaves=self.n_leaves,
            leaf_kind=st["leaf_kind"], iters=st["iters"],
            use_kernel=bool(use_kernel),
            interpret=interpret if interpret is None else bool(interpret))
        tables = self._packed_stack(st) if use_kernel else \
            (st["root"], st["leaves"], st["err_lo"], st["err_hi"])
        found, rank = fn(st["splits"], st["offs"], st["route_n"], st["base"],
                         st["bdead"], st["bpsum"], st["dk"], st["ddead"],
                         st["dpsum"], tables, q)
        return found[:Q], rank[:Q]

    def find_range(self, q_lo, q_hi, *, path: str = "auto",
                   use_kernel: bool | None = None,
                   interpret: bool | None = None) -> tuple[Array, Array]:
        """(rank_lo, rank_hi) global live ranks of the inclusive key ranges
        ``[q_lo[i], q_hi[i]]``, one ``shard_map`` dispatch: both endpoint
        arrays are concatenated and streamed through the same capacity-
        bucketed ``_routed_exchange`` as :meth:`find`, each endpoint's
        owning shard answers with BOTH its leftmost and rightmost local
        live rank (the fused range kernel or the jnp two-tier range tail),
        and the origin composes global ranks from the counter-table
        offsets — a range spanning shard seams needs no extra round trips
        because rank_lo rides the lo endpoint's shard and rank_hi the hi
        endpoint's.  A ``hi`` inside a duplicate run that *starts* a shard
        routes to that run's owning shard (runs never straddle seams —
        ``shard_bounds`` snaps to run starts), so its rightmost rank
        already counts every earlier shard through ``offs``.  rank_hi is
        clamped to rank_lo: degenerate ranges (lo > hi, tombstoned
        singletons, fully out-of-range) come back empty, never
        negative-width.  ``live_keys()[rank_lo:rank_hi]`` is the range's
        content.  Path-selection contract mirrors :meth:`find`."""
        ql = jnp.asarray(q_lo, jnp.float64)
        qh = jnp.asarray(q_hi, jnp.float64)
        if ql.shape != qh.shape:
            raise ValueError("find_range endpoint arrays must pair up")
        use_kernel = resolve_path(path, f32_exact=lambda: self.f32_exact,
                                  use_kernel=use_kernel,
                                  what="sharded key space")
        st = self._stacked()
        Q = ql.shape[0]
        qp = -(-max(Q, 1) // self.n_shards) * self.n_shards
        if qp != Q:
            ql = jnp.pad(ql, (0, qp - Q))    # 0.0 pads; sliced off below
            qh = jnp.pad(qh, (0, qp - Q))
        fn = _sharded_dynamic_range_fn(
            self.mesh, self.axis, n_leaves=self.n_leaves,
            leaf_kind=st["leaf_kind"], iters=st["iters"],
            use_kernel=bool(use_kernel),
            interpret=interpret if interpret is None else bool(interpret))
        tables = self._packed_stack(st) if use_kernel else \
            (st["root"], st["leaves"], st["err_lo"], st["err_hi"])
        rl, rr = fn(st["splits"], st["offs"], st["route_n"], st["base"],
                    st["bdead"], st["bpsum"], st["dk"], st["ddead"],
                    st["dpsum"], tables, jnp.concatenate([ql, qh]))
        rank_lo = rl[:qp][:Q]
        return rank_lo, jnp.maximum(rr[qp:][:Q], rank_lo)

    def gather_range(self, rank_lo, rank_hi) -> list[np.ndarray]:
        """Materialize :meth:`find_range` spans: per-range sorted live keys
        (host numpy — the global live array is assembled once and
        sliced)."""
        live = self.live_keys()
        lo = np.asarray(rank_lo).ravel()
        hi = np.asarray(rank_hi).ravel()
        return [live[int(a):int(b)] for a, b in zip(lo, hi, strict=True)]


def _local_find_fn(*, n_leaves: int, leaf_kind: str, iters: int,
                   use_kernel: bool, interpret: bool | None):
    """One shard's two-tier find ``(tables, route_n, base, bdead, bpsum,
    dk, ddead, dpsum, q) -> (found, rank)``, shared by the sharded and the
    tenant-stacked find programs: the ``dynamic_lookup_pallas`` kernel via
    ``ops.dynamic_find``, or the jnp two-tier find with the frozen routing
    scale as a *traced* per-shard scalar (the static-route_n ``_find_jit``
    cannot serve shards with different build sizes under one shard_map
    trace).  On the jnp path ``base`` is in either key form
    (``rmi.like_keys``); ``q`` is f64."""
    if use_kernel:
        from ..kernels import ops as kernel_ops

        def local_find(tables, route_n, base, bdead, bpsum, dk, ddead,
                       dpsum, q):
            kroot, kmat, kvec = tables
            return kernel_ops.dynamic_find(
                q, kroot, kmat, kvec, base, bdead, bpsum, dk, ddead, dpsum,
                n_leaves=n_leaves, route_n=n_leaves, root_kind="linear",
                leaf_kind=leaf_kind, iters=iters, interpret=interpret)
        return local_find

    from . import updates as updates_mod

    def local_find(tables, route_n, base, bdead, bpsum, dk, ddead, dpsum, q):
        lo, hi = _jnp_window(tables, route_n, base, q, n_leaves, leaf_kind)
        found, rank, _ = updates_mod.two_tier_answer(
            base, bpsum, dk, dpsum, q, lo, hi, iters)
        return found, rank
    return local_find


def _local_range_fn(*, n_leaves: int, leaf_kind: str, iters: int,
                    use_kernel: bool, interpret: bool | None):
    """Range sibling of :func:`_local_find_fn`: ``(rank_lo, rank_hi)`` of
    each endpoint answered as both a lo and a hi endpoint
    (``ops.range_lookup`` or the jnp two-tier range tail)."""
    if use_kernel:
        from ..kernels import ops as kernel_ops

        def local_range(tables, route_n, base, bdead, bpsum, dk, ddead,
                        dpsum, q):
            kroot, kmat, kvec = tables
            return kernel_ops.range_lookup(
                q, q, kroot, kmat, kvec, base, bdead, bpsum, dk, ddead,
                dpsum, n_leaves=n_leaves, route_n=n_leaves,
                root_kind="linear", leaf_kind=leaf_kind, iters=iters,
                interpret=interpret)
        return local_range

    from . import updates as updates_mod

    def local_range(tables, route_n, base, bdead, bpsum, dk, ddead, dpsum,
                    q):
        lo, hi = _jnp_window(tables, route_n, base, q, n_leaves, leaf_kind)
        return updates_mod.two_tier_range_answer(
            base, bpsum, dk, dpsum, q, q, lo, hi, iters)
    return local_range


def _jnp_window(tables, route_n, base, q, n_leaves: int, leaf_kind: str):
    """The jnp path's f64 routing and leaf window: the root's bucket under
    the traced routing scale, then the routed leaf's error-bound window
    (``updates.leaf_window``)."""
    from . import updates as updates_mod

    root, leaves, elo, ehi = tables
    b = jnp.clip((rmi_mod.models.linear_predict(root, q)
                  * n_leaves / route_n).astype(jnp.int32), 0, n_leaves - 1)
    return updates_mod.leaf_window(leaves, elo, ehi, b, q,
                                   rmi_mod.tier_size(base), leaf_kind)


def _pad_member(base) -> Array:
    """The f64 key an exchange pad is searched as: the shard's first base
    key, or 0.0 on an empty shard's all-+inf row, so pads never blow the
    sparse seam budget.  ``base`` is one shard's row in either key form."""
    k0 = rmi_mod.key_value(jax.tree.map(lambda a: a[0], base))
    return jnp.where(jnp.isfinite(k0), k0, 0.0)


@functools.lru_cache(maxsize=64)
def _sharded_dynamic_find_fn(mesh: Mesh, axis: str, *, n_leaves: int,
                             leaf_kind: str, iters: int, use_kernel: bool,
                             interpret: bool | None):
    """Jitted shard_map program for ``ShardedDynamicIndex.find``.  Cached on
    the static configuration so a mutate/find churn loop only re-traces when
    a capacity (array shape) actually crosses a power of two."""
    n_shards = mesh.shape[axis]
    local_find = _local_find_fn(n_leaves=n_leaves, leaf_kind=leaf_kind,
                                iters=iters, use_kernel=use_kernel,
                                interpret=interpret)

    def shard_fn(splits, offs, route_n, base, bdead, bpsum, dk, ddead,
                 dpsum, tables, q_local):
        def answer(rq, live):
            # Exchange pads take a member key (_pad_member); their answers
            # are forced dead here.
            qm = jnp.where(live, rq, _pad_member(base[0]))
            found, rank = local_find(jax.tree.map(lambda a: a[0], tables),
                                     route_n[0], base[0], bdead[0],
                                     bpsum[0], dk[0], ddead[0], dpsum[0],
                                     qm)
            rank = jnp.where(live, rank.astype(jnp.int32) + offs[0], 0)
            return jnp.stack([rank, (found & live).astype(jnp.int32)],
                             axis=-1)

        rank, found = _routed_exchange(axis, n_shards, splits, q_local,
                                       q_local.shape[0], answer, (0, 0))
        return found.astype(bool), rank

    fn = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh),
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=_checks_vma(use_kernel, interpret))
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _sharded_dynamic_range_fn(mesh: Mesh, axis: str, *, n_leaves: int,
                              leaf_kind: str, iters: int, use_kernel: bool,
                              interpret: bool | None):
    """Jitted shard_map program for ``ShardedDynamicIndex.find_range``.

    The local query row is the concatenation [lo endpoints | hi endpoints];
    every routed endpoint is answered with BOTH its leftmost and rightmost
    local live rank (payload columns), and the caller keeps the left
    column for lo slots and the right column for hi slots.  Answering both
    sides unconditionally keeps the exchange single-round and the kernel
    single-pass (``dynamic_range_pallas`` with q_lo == q_hi routes each
    endpoint once per key tile)."""
    n_shards = mesh.shape[axis]
    local_range = _local_range_fn(n_leaves=n_leaves, leaf_kind=leaf_kind,
                                  iters=iters, use_kernel=use_kernel,
                                  interpret=interpret)

    def shard_fn(splits, offs, route_n, base, bdead, bpsum, dk, ddead,
                 dpsum, tables, q_local):
        def answer(rq, live):
            # Same exchange-pad masking as the point path; pad answers are
            # zeroed here.
            qm = jnp.where(live, rq, _pad_member(base[0]))
            rlo, rhi = local_range(jax.tree.map(lambda a: a[0], tables),
                                   route_n[0], base[0], bdead[0], bpsum[0],
                                   dk[0], ddead[0], dpsum[0], qm)
            rlo = jnp.where(live, rlo.astype(jnp.int32) + offs[0], 0)
            rhi = jnp.where(live, rhi.astype(jnp.int32) + offs[0], 0)
            return jnp.stack([rlo, rhi], axis=-1)

        rlo, rhi = _routed_exchange(axis, n_shards, splits, q_local,
                                    q_local.shape[0], answer, (0, 0))
        return rlo, rhi

    fn = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh),
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=_checks_vma(use_kernel, interpret))
    return jax.jit(fn)


# Trace-time counters for the serving retrace guard: the shard_map bodies
# below bump their key when (re)traced, so tests can pin "zero hot-path
# retraces across varying live batch sizes after warmup" exactly the way
# tests/test_updates.py pins the no-host-loop contract.
TRACE_COUNTS = {"tenant_find": 0, "tenant_range": 0}


@functools.lru_cache(maxsize=32)
def _tenant_stacked_find_fn(mesh: Mesh, axis: str, *, n_tenants: int,
                            n_leaves: int, leaf_kind: str, iters: int,
                            use_kernel: bool, interpret: bool | None):
    """Jitted shard_map program answering N independent tenants in one
    stacked dispatch (``serve.frontend.TenantPack``).

    Every operand carries a leading tenant axis over the per-shard stacked
    state (``P(None, axis)`` — tenant-replicated, shard-partitioned), and
    the body answers each tenant's query row through the same
    capacity-bucketed exchange + fused two-tier find as
    ``_sharded_dynamic_find_fn``.  Tenants of different build sizes share
    the one trace because their size differences are *data*, not shape:

      * tiers pad to the cross-tenant max capacity classes (+inf keys /
        zero tombstones / edge-extended prefix sums — the same trick the
        per-shard stack plays),
      * leaf tables pad to the widest tenant's ``n_leaves`` with the last
        live leaf replicated (``lookup.pad_packed_leaves``), so a routing
        overshoot lands on the window the tenant's own clip would pick,
      * routing rescales ride per-tenant *data*: the traced ``route_n``
        scalar on the jnp path, the ``pack_root(route_scale=...)`` fold on
        the kernel path — traced once with static
        ``n_leaves = route_n = max_t L_t``.

    On the jnp path ``base`` may arrive as its ``(hi, lo)`` f32 words
    (``rmi.split_words``; the pytree structure selects the trace), so that
    a call on the TPU gathers its probes from the words instead of
    splitting the whole f64 tier first.

    Cached on the static configuration, so after the serve front-end's
    warmup the hot path never retraces: live batch sizes only vary the
    *contents* of the pow2-padded query rows.
    """
    n_shards = mesh.shape[axis]
    local_find = _local_find_fn(n_leaves=n_leaves, leaf_kind=leaf_kind,
                                iters=iters, use_kernel=use_kernel,
                                interpret=interpret)

    def shard_fn(splits, offs, route_n, base, bdead, bpsum, dk, ddead,
                 dpsum, tables, q):
        TRACE_COUNTS["tenant_find"] += 1
        founds, ranks = [], []
        for t in range(n_tenants):
            def answer(rq, live, t=t):
                bt = jax.tree.map(lambda a: a[t, 0], base)
                qm = jnp.where(live, rq, _pad_member(bt))
                found, rank = local_find(
                    jax.tree.map(lambda a: a[t][0], tables),
                    route_n[t, 0], bt, bdead[t, 0], bpsum[t, 0],
                    dk[t, 0], ddead[t, 0], dpsum[t, 0], qm)
                rank = jnp.where(live, rank.astype(jnp.int32) + offs[t, 0],
                                 0)
                return jnp.stack([rank, (found & live).astype(jnp.int32)],
                                 axis=-1)

            rank, found = _routed_exchange(axis, n_shards, splits[t], q[t],
                                           q[t].shape[0], answer, (0, 0))
            founds.append(found.astype(bool))
            ranks.append(rank)
        return jnp.stack(founds), jnp.stack(ranks)

    fn = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh),
        in_specs=(P(), P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis)),
        out_specs=(P(None, axis), P(None, axis)),
        check_vma=_checks_vma(use_kernel, interpret))
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _tenant_stacked_range_fn(mesh: Mesh, axis: str, *, n_tenants: int,
                             n_leaves: int, leaf_kind: str, iters: int,
                             use_kernel: bool, interpret: bool | None):
    """Range-query sibling of :func:`_tenant_stacked_find_fn` for the serve
    front-end's ``"range"`` request kind: each tenant's query row is the
    concatenation [lo endpoints | hi endpoints] (the
    :func:`_sharded_dynamic_range_fn` layout), answered per shard with
    both boundary ranks and returned as (rank_lo_row, rank_hi_row)
    matrices.  Same padding/rescale tricks, same zero-retrace contract
    (``TRACE_COUNTS["tenant_range"]``)."""
    n_shards = mesh.shape[axis]
    local_range = _local_range_fn(n_leaves=n_leaves, leaf_kind=leaf_kind,
                                  iters=iters, use_kernel=use_kernel,
                                  interpret=interpret)

    def shard_fn(splits, offs, route_n, base, bdead, bpsum, dk, ddead,
                 dpsum, tables, q):
        TRACE_COUNTS["tenant_range"] += 1
        rlos, rhis = [], []
        for t in range(n_tenants):
            def answer(rq, live, t=t):
                bt = jax.tree.map(lambda a: a[t, 0], base)
                qm = jnp.where(live, rq, _pad_member(bt))
                rlo, rhi = local_range(
                    jax.tree.map(lambda a: a[t][0], tables),
                    route_n[t, 0], bt, bdead[t, 0], bpsum[t, 0],
                    dk[t, 0], ddead[t, 0], dpsum[t, 0], qm)
                rlo = jnp.where(live, rlo.astype(jnp.int32) + offs[t, 0], 0)
                rhi = jnp.where(live, rhi.astype(jnp.int32) + offs[t, 0], 0)
                return jnp.stack([rlo, rhi], axis=-1)

            rlo, rhi = _routed_exchange(axis, n_shards, splits[t], q[t],
                                        q[t].shape[0], answer, (0, 0))
            rlos.append(rlo)
            rhis.append(rhi)
        return jnp.stack(rlos), jnp.stack(rhis)

    fn = jax.shard_map(
        shard_fn, mesh=_auto_axes(mesh),
        in_specs=(P(), P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis)),
        out_specs=(P(None, axis), P(None, axis)),
        check_vma=_checks_vma(use_kernel, interpret))
    return jax.jit(fn)
