"""Pallas TPU kernel: batched per-segment linear least squares (RMI/RMRT
leaf fitting).

Accumulates, for every leaf bucket b, the moment sums
    S[b] = [count, Sum x, Sum y, Sum xy, Sum x^2]
as a (8, B) accumulator (stat rows padded 5->8 for sublane alignment) via an
MXU matmul per tile:  feats(8, T) @ onehot(T, TB)  ->  (8, TB).

The closed-form solve (a = (n Sxy - Sx Sy) / (n Sxx - Sx^2), b = ...) is a
tiny elementwise epilogue done by the ops wrapper. Keys are pre-centered /
scaled per segment *range block* by the wrapper to keep f32 moments stable
(raw SOSD keys are u64-scale; x'^2 sums overflow f32 otherwise).

Grid: (bucket_tiles, key_tiles) with key tiles innermost so each (8, TB)
output block accumulates over its full key stream before moving on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .call import pallas_call

TILE = 1024     # keys per grid step
TB = 512        # buckets per grid step


def _linfit_kernel(x_ref, y_ref, b_ref, out_ref, *, n_valid: int):
    jb, step = pl.program_id(0), pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].reshape(TILE)
    y = y_ref[...].reshape(TILE)
    b = b_ref[...].reshape(TILE)
    gidx = step * TILE + jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)[:, 0]
    valid = (gidx < n_valid).astype(jnp.float32)

    local = b - jb * TB                                     # bucket in tile?
    onehot = (local[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (TILE, TB), 1))
    onehot = onehot.astype(jnp.float32) * valid[:, None]    # (TILE, TB)

    feats = jnp.stack([jnp.ones_like(x), x, y, x * y, x * x,
                       jnp.zeros_like(x), jnp.zeros_like(x),
                       jnp.zeros_like(x)])                  # (8, TILE)
    out_ref[...] += jnp.dot(feats, onehot,                  # (8, TB) on MXU
                            preferred_element_type=jnp.float32)


def linfit_sums_pallas(x: jax.Array, y: jax.Array, buckets: jax.Array,
                       n_buckets: int, *, interpret: bool = True) -> jax.Array:
    """Per-bucket moment sums (n_buckets, 5) float32.

    x, y: (N,) f32 (pre-scaled); buckets: (N,) int32.
    """
    n = x.shape[0]
    n_pad = -(-n // TILE) * TILE
    b_pad = -(-n_buckets // TB) * TB
    xp = jnp.pad(x.astype(jnp.float32), (0, n_pad - n)).reshape(-1, 8, TILE // 8)
    yp = jnp.pad(y.astype(jnp.float32), (0, n_pad - n)).reshape(-1, 8, TILE // 8)
    bp = jnp.pad(buckets.astype(jnp.int32), (0, n_pad - n),
                 constant_values=-1).reshape(-1, 8, TILE // 8)

    def kern(x_ref, y_ref, b_ref, out_ref):
        _linfit_kernel(x_ref, y_ref, b_ref, out_ref, n_valid=n)

    out = pallas_call(
        kern, xp, yp, bp,
        grid=(b_pad // TB, n_pad // TILE),
        in_specs=[
            pl.BlockSpec((1, 8, TILE // 8), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, 8, TILE // 8), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, 8, TILE // 8), lambda j, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((8, TB), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((8, b_pad), jnp.float32),
        interpret=interpret)
    return out[:5, :n_buckets].T                            # (n_buckets, 5)
