"""Recursive Model Reuse Tree (RMRT, paper §3).

A node holding more than N keys trains a model that partitions its keys into
B children (agile model reuse applied whenever a model is needed); recursion
stops when a partition holds <= N keys, which is then indexed by a (reused or
fresh) leaf model. The tree is unbalanced by construction — dense regions get
more levels — which is the paper's answer to skew.

TPU adaptation: the tree is built *level-synchronously* — every node of a
level is processed by the same batched machinery as the RMI layer (segment
fits, batched histograms, one fused pool selection for all nodes), and the
tree is stored as flat arrays (child_base/is_leaf/bounds per node) because
TPUs do not chase pointers. Descent is a fixed-depth masked loop.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import models
from .adapt import DomainSpec, adapt_linear, adapt_mlp
from .bounds import reuse_err_bounds
from .paths import resolve_path
from .reuse import ModelPool, select_from_pool_batch
from .rmi import (leaf_histograms, leaf_stats, segment_linear_fit,
                  segment_residual_bounds, verified_search,
                  _batched_leaf_mlp, _leaf_predict_all)

Array = jax.Array


@dataclass
class RMRTIndex:
    keys: Array              # (n,) sorted
    kind: str                # leaf/internal model kind: "linear" | "mlp"
    params: models.LinearParams | models.MLPParams   # stacked (num_nodes, ...)
    is_leaf: Array           # (num_nodes,) bool
    child_base: Array        # (num_nodes,) int32 — flat index of first child
    y_start: Array           # (num_nodes,) f64 — position range for bucketing
    y_end: Array             # (num_nodes,)
    err_lo: Array            # (num_nodes,) leaf bounds (0 for internal)
    err_hi: Array
    node_sim: Array          # (num_nodes,) build-time similarity (Lemma 4.1)
    reused_mask: Array       # (num_nodes,) bool
    fanout: int
    leaf_cap: int
    depth: int
    _iters: int | None = None        # cached error-window search depth
    _packed: tuple | None = None     # (mat, vec) kernel node tables
    _f32_exact: bool | None = None   # keys round-trip through f32

    @property
    def n(self) -> int:
        return int(self.keys.shape[0])

    @property
    def search_iters(self) -> int:
        """Static search depth bounded by the widest live leaf window (§4)."""
        if self._iters is None:
            from ..kernels.lookup import search_iters
            self._iters = search_iters(self.err_lo, self.err_hi, self.n)
        return self._iters

    @property
    def num_nodes(self) -> int:
        return int(self.is_leaf.shape[0])

    @property
    def reuse_fraction(self) -> float:
        return float(jnp.mean(self.reused_mask.astype(jnp.float64)))

    @property
    def f32_exact(self) -> bool:
        """True when every key round-trips through f32 — the precondition
        for the Pallas kernel path (same guard as RMIIndex.f32_exact)."""
        if self._f32_exact is None:
            k32 = self.keys.astype(jnp.float32).astype(jnp.float64)
            self._f32_exact = bool(jnp.all(k32 == self.keys))
        return self._f32_exact

    def packed_tables(self) -> tuple:
        """(mat, vec) VMEM-layout node tables for the fused RMRT kernel."""
        if self._packed is None:
            from ..kernels import lookup as _lk
            self._packed = _lk.pack_rmrt(
                self.kind, self.params, self.is_leaf, self.child_base,
                self.y_start, self.y_end, self.err_lo, self.err_hi)
        return self._packed


def _fit_level(keys, slots, n_slots, kind, pool, train_steps, seed,
               paper_bounds):
    """Fit (reuse-or-train) one model per occupied slot; returns params,
    measured/theorem bounds, sim, reused mask — all (n_slots,) stacked."""
    count, kmin, kmax, pmin, pmax = leaf_stats(keys, slots, n_slots)
    found = jnp.zeros((n_slots,), bool)
    if pool is not None:
        if pool.sel_a is None:
            pool._refresh_tables()
        hists = leaf_histograms(keys, slots, n_slots, pool.m, kmin, kmax)
        sel = select_from_pool_batch(pool.sel_a, pool.sel_ps, hists,
                                     jnp.float32(pool.eps))
        found = sel.found & (count > 1)
        src = jax.tree.map(lambda a: a[sel.index], pool.domains)
        tgt = DomainSpec(x_start=kmin,
                         x_end=jnp.where(kmax > kmin, kmax, kmin + 1.0),
                         y_start=pmin, y_end=jnp.maximum(pmax, pmin + 1.0))
        pp = jax.tree.map(lambda a: a[sel.index], pool.params)
        adapt = adapt_linear if pool.kind == "linear" else adapt_mlp
        adapted = jax.vmap(adapt)(pp, src, tgt)
        s_dy = (tgt.y_end - tgt.y_start) / (src.y_end - src.y_start)
        thm_lo, thm_hi = reuse_err_bounds(pool.err_lo[sel.index],
                                          pool.err_hi[sel.index],
                                          sel.dist, count, s_dy)

    if kind == "linear":
        fresh = segment_linear_fit(keys, slots, n_slots)
    else:
        fresh = _batched_leaf_mlp(keys, slots, n_slots, count, kmin, kmax,
                                  pmin, train_steps, seed,
                                  skip_mask=found if pool is not None else None)

    if pool is not None and pool.kind == kind:
        merge = lambda a, f: jnp.where(
            jnp.expand_dims(found, tuple(range(1, a.ndim))), a, f)
        params = jax.tree.map(merge, adapted, fresh)
    else:
        params = fresh
        found = jnp.zeros((n_slots,), bool)

    pred = _leaf_predict_all(kind, params, keys, slots)
    lo, hi = segment_residual_bounds(pred, slots, n_slots)
    if pool is not None and paper_bounds:
        lo = jnp.where(found, thm_lo, lo)
        hi = jnp.where(found, thm_hi, hi)
    # Empty slots are reachable by out-of-distribution queries: give them a
    # sound full-array window (plain binary search fallback).
    n = keys.shape[0]
    lo = jnp.where(count > 0, lo, -float(n))
    hi = jnp.where(count > 0, hi, float(n))
    sim = jnp.where(found, 1.0 - sel.dist, 1.0) if pool is not None \
        else jnp.ones((n_slots,), jnp.float64)
    return params, lo, hi, sim, found, count, pmin, pmax


def build_rmrt(
    keys: Array,
    leaf_cap: int = 4096,            # paper's N (1e6 at 200M-key scale)
    fanout: int = 64,                # paper's B
    kind: str = "linear",
    pool: Optional[ModelPool] = None,
    paper_bounds: bool = False,
    train_steps: int = 200,
    max_depth: int = 12,
    seed: int = 0,
) -> RMRTIndex:
    keys = jnp.asarray(keys, jnp.float64)
    n = keys.shape[0]

    # Flat node storage, appended level by level. Keys that already settled
    # into a finished leaf are "parked" in a dummy tail slot at deeper levels
    # (fitted results for the dummy are trimmed before appending).
    all_params, all_leaf, all_cbase = [], [], []
    all_ylo, all_yhi, all_elo, all_ehi, all_sim, all_reused = [], [], [], [], [], []

    slots = jnp.zeros((n,), jnp.int32)        # key -> node slot in this level
    n_slots, has_dummy = 1, False
    level_base = 0                            # flat index of level's first node
    depth = 0

    for level in range(max_depth):
        depth = level + 1
        params, lo, hi, sim, found, count, pmin, pmax = _fit_level(
            keys, slots, n_slots, kind, pool, train_steps, seed + level,
            paper_bounds)
        real = n_slots - (1 if has_dummy else 0)
        count_np = np.asarray(count)[:real]
        leaf_mask = (count_np <= leaf_cap) | (level == max_depth - 1)
        internal = np.where(~leaf_mask)[0]

        # child_base: the next level is laid out as fanout-sized groups in
        # the order of `internal`.
        next_base = level_base + real
        cbase = np.full((real,), -1, np.int64)
        cbase[internal] = next_base + np.arange(internal.size) * fanout

        trim = lambda a, real=real: a[:real]
        all_params.append(jax.tree.map(trim, params))
        all_leaf.append(jnp.asarray(leaf_mask))
        all_cbase.append(jnp.asarray(cbase, jnp.int32))
        all_ylo.append(trim(pmin))
        all_yhi.append(trim(jnp.maximum(pmax, pmin) + 1.0))
        all_elo.append(jnp.where(jnp.asarray(leaf_mask), trim(lo), 0.0))
        all_ehi.append(jnp.where(jnp.asarray(leaf_mask), trim(hi), 0.0))
        all_sim.append(trim(sim))
        all_reused.append(trim(found))

        if internal.size == 0:
            break

        # Route keys of internal nodes to their child slot; park the rest.
        pred = _leaf_predict_all(kind, params, keys, slots)
        span = (jnp.maximum(pmax, pmin) + 1.0 - pmin)[slots]
        child = jnp.clip(((pred - pmin[slots]) * fanout / span).astype(jnp.int32),
                         0, fanout - 1)
        slot_remap = np.full((n_slots,), -1, np.int64)  # dummy stays -1
        slot_remap[internal] = np.arange(internal.size)
        new_slots = jnp.asarray(slot_remap, jnp.int32)[slots] * fanout + child
        # Pad the internal count to a power of two: stabilizes traced shapes
        # across levels/builds (jit-cache friendly). Padding slots are empty
        # and become sound empty leaves (full-window fallback).
        pad = 1 << max(int(internal.size) - 1, 0).bit_length()
        n_next = pad * fanout
        slots = jnp.where(new_slots >= 0, new_slots, n_next)
        n_slots, has_dummy = n_next + 1, True
        level_base = next_base

    cat = jnp.concatenate
    params = jax.tree.map(lambda *ps: cat(ps), *all_params)
    return RMRTIndex(
        keys=keys, kind=kind, params=params,
        is_leaf=cat(all_leaf), child_base=cat(all_cbase),
        y_start=cat(all_ylo), y_end=cat(all_yhi),
        err_lo=cat(all_elo), err_hi=cat(all_ehi),
        node_sim=cat(all_sim), reused_mask=cat(all_reused),
        fanout=fanout, leaf_cap=leaf_cap, depth=depth)


# ---------------------------------------------------------------------------
# Lookup.
# ---------------------------------------------------------------------------
def lookup(index: RMRTIndex, queries: Array, *, path: str = "auto",
           use_kernel: bool | None = None,
           clamp_iters: bool = True) -> Array:
    """Serving lookup.  ``path="kernel"`` is the fused Pallas kernel —
    descent, then the clamped search kernel; the masked-descent jnp path
    below is the CPU fast path, the kernel's f64 reference, and the f64
    fallback.  Same path-selection semantics as ``rmi.lookup``
    (``core.paths.resolve_path``; ``use_kernel`` is the deprecated shim)."""
    if resolve_path(path, f32_exact=lambda: index.f32_exact,
                    use_kernel=use_kernel):
        from ..kernels import ops as kernel_ops
        from ..kernels.lookup import full_iters
        iters = index.search_iters if clamp_iters else full_iters(index.n)
        mat, vec = index.packed_tables()
        return kernel_ops.rmrt_lookup(
            jnp.asarray(queries, jnp.float64), mat, vec, index.keys,
            fanout=index.fanout, depth=index.depth, kind=index.kind,
            iters=iters)
    return _rmrt_lookup(index.kind, index.params, index.is_leaf,
                        index.child_base, index.y_start, index.y_end,
                        index.err_lo, index.err_hi, index.keys,
                        jnp.asarray(queries, jnp.float64), index.fanout,
                        index.depth,
                        index.search_iters if clamp_iters else None)


def _predict_one(kind, params, node, q):
    p = jax.tree.map(lambda a: a[node], params)
    if kind == "linear":
        return models.linear_predict(p, q)
    h = jax.nn.relu(q[..., None] * p.w1 + p.b1)
    return jnp.sum(h * p.w2, -1) + p.b2


@functools.partial(jax.jit,
                   static_argnames=("kind", "fanout", "depth", "iters"))
def _rmrt_lookup(kind, params, is_leaf, child_base, y_start, y_end,
                 err_lo, err_hi, keys, queries, fanout: int, depth: int,
                 iters: int | None = None):
    """Masked fixed-depth descent (vectorized over queries), then the same
    bounded branchless binary search as RMI."""
    n = keys.shape[0]
    node = jnp.zeros(queries.shape, jnp.int32)

    def body(_, node):
        pred = _predict_one(kind, params, node, queries)
        span = y_end[node] - y_start[node]
        child = jnp.clip(((pred - y_start[node]) * fanout / span)
                         .astype(jnp.int32), 0, fanout - 1)
        nxt = child_base[node] + child
        return jnp.where(is_leaf[node], node, nxt)

    node = jax.lax.fori_loop(0, depth, body, node)
    pred = _predict_one(kind, params, node, queries)
    lo = jnp.clip(jnp.floor(pred + err_lo[node]), 0, n - 1).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pred + err_hi[node]) + 1, 1, n).astype(jnp.int32)
    return verified_search(keys, queries, lo, hi, iters=iters)
