"""The work a window search needs, counted from the index's own leaf bounds
and not from how a program streams its keys.

A live query endpoint needs its query (one key), its answer (4 bytes, an
int32 rank) and the keys of its error window: the window of
the leaf its shard's root routes it to, ``ceil(err_hi) - floor(err_lo) + 3``
positions wide (the paper's search window; the same widths the index derives
its search depth from), and at most the shard's key count.  A key is as wide
as the served path searches it: 8 bytes on the f64 jnp path, 4 on the f32
kernel path.  Padded lanes, tiles streamed and rows fetched are not counted,
so the count is the same whatever implements the search.  The search is a bisection over the window,
so its operations, log2(width) compares, are negligible next to its bytes:
the roofline time is bytes over the peak HBM bandwidth.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

ANSWER_BYTES = 4


def window_widths(err_lo: np.ndarray, err_hi: np.ndarray) -> np.ndarray:
    return np.ceil(np.asarray(err_hi, np.float64)) - \
        np.floor(np.asarray(err_lo, np.float64)) + 3.0


@dataclass
class ShardLeaves:
    """One shard's routing and leaf windows: linear root ``pos = a*q + b``
    scaled by ``n_leaves / route_n``; ``widths`` per leaf; ``n_keys``."""
    a: float
    b: float
    route_n: float
    widths: np.ndarray
    n_keys: int

    def endpoint_widths(self, q: np.ndarray) -> np.ndarray:
        L = self.widths.size
        leaf = np.clip(((self.a * q + self.b) * (L / self.route_n)
                        ).astype(np.int64), 0, L - 1)
        return np.minimum(self.widths[leaf], self.n_keys)


def needed_bytes(q: np.ndarray, splits: np.ndarray, shards: list, *,
                 key_bytes: int) -> float:
    """Bytes the live endpoints ``q`` need, with keys ``key_bytes`` wide:
    routed to a shard by ``splits`` (a key equal to a split goes left),
    then to a leaf by the shard's root."""
    q = np.asarray(q, np.float64).ravel()
    dest = np.searchsorted(splits, q, side="left")
    total = 0.0
    for s, sh in enumerate(shards):
        qs = q[dest == s]
        if qs.size:
            total += qs.size * (key_bytes + ANSWER_BYTES) + \
                key_bytes * float(sh.endpoint_widths(qs).sum())
    return total


def shard_leaves(backend) -> tuple:
    """``(splits, [ShardLeaves])`` read once from a sharded index backend
    (``repro.core.distributed.ShardedDynamicIndex``) with linear roots."""
    out = []
    for d in backend.shards:
        idx = d.index
        out.append(ShardLeaves(
            a=float(np.asarray(idx.root.a)), b=float(np.asarray(idx.root.b)),
            route_n=float(d.route_n),
            widths=window_widths(np.asarray(idx.err_lo),
                                 np.asarray(idx.err_hi)),
            n_keys=int(d.base_n)))
    return np.asarray(backend.splits, np.float64), out
