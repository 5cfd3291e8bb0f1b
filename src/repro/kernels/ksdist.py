"""Pallas TPU kernel: batched Algorithm-2 histogram distance (the agile-
reuse selection core).

Computes the (L, P) distance matrix between L target histograms (RMI leaves
/ RMRT level nodes) and a pool of P pre-trained synthetic histograms:

    d[l, p] = max( max_m (A_S[p,m] - P_T[l,m]),
                   max_m (A_T[l,m] - P_S[p,m]) )

where A = H + P(prefix) tables are precomputed per side (core.reuse.
pool_prefix_tables). TPU adaptation of the paper's sequential priority-queue
scan: one grid cell processes a (TL, TP) tile of the matrix with both
operand tiles resident in VMEM; the m-dim broadcast stays on-chip
(TL*TP*m f32 = 2 MiB at 64x128x64; TP is one lane tile, the
narrowest output block Mosaic accepts), so HBM traffic is O(L*m + P*m + L*P)
instead of the O(L*P*m) a naive XLA broadcast materializes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .call import pallas_call

TL, TP = 64, 128


def _ksdist_kernel(tgt_a_ref, tgt_pt_ref, pool_a_ref, pool_ps_ref, out_ref):
    ta = tgt_a_ref[...]       # (TL, m)  = H_T + P_T
    tp = tgt_pt_ref[...]      # (TL, m)  = P_T
    pa = pool_a_ref[...]      # (TP, m)  = H_S + P_S
    pp = pool_ps_ref[...]     # (TP, m)  = P_S
    up = jnp.max(pa[None, :, :] - tp[:, None, :], axis=2)     # (TL, TP)
    dn = jnp.max(ta[:, None, :] - pp[None, :, :], axis=2)     # (TL, TP)
    out_ref[...] = jnp.maximum(up, dn)


def ksdist_pallas(tgt_hists: jax.Array, pool_a: jax.Array, pool_ps: jax.Array,
                  *, interpret: bool = True) -> jax.Array:
    """(L, P) Algorithm-2 distances. All inputs f32; m is padded to a lane
    multiple inside (padding bins carry zero mass so prefix tables are flat
    there and do not perturb the max)."""
    L, m = tgt_hists.shape
    P = pool_a.shape[0]
    m_pad = -(-m // 128) * 128
    L_pad = -(-L // TL) * TL
    P_pad = -(-P // TP) * TP

    ht = tgt_hists.astype(jnp.float32)
    pt = jnp.concatenate(
        [jnp.zeros((L, 1), jnp.float32), jnp.cumsum(ht, 1)[:, :-1]], 1)
    ta = ht + pt

    def pad2(a, rows, col_fill, row_fill):
        a = jnp.pad(a, ((0, 0), (0, m_pad - a.shape[1])),
                    constant_values=col_fill)
        return jnp.pad(a, ((0, rows - a.shape[0]), (0, 0)),
                       constant_values=row_fill)

    # Column padding must be neutral under the max: A-side columns get -10
    # (never the max), prefix-side columns +10 (subtracted, never the max).
    # Pool *row* padding gets A_S = +2 so padded pool entries report
    # distance > 1 and are never eligible; target padding rows are sliced
    # off afterwards.
    ta_p = pad2(ta, L_pad, -10.0, 0.0)
    tp_p = pad2(pt, L_pad, +10.0, 0.0)
    pa_p = pad2(pool_a.astype(jnp.float32), P_pad, -10.0, +2.0)
    pp_p = pad2(pool_ps.astype(jnp.float32), P_pad, +10.0, 0.0)

    out = pallas_call(
        _ksdist_kernel, ta_p, tp_p, pa_p, pp_p,
        grid=(L_pad // TL, P_pad // TP),
        in_specs=[
            pl.BlockSpec((TL, m_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((TL, m_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((TP, m_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((TP, m_pad), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((TL, TP), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((L_pad, P_pad), jnp.float32),
        interpret=interpret)
    return out[:L, :P]
