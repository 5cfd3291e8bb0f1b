"""Pallas TPU kernel: streaming relative-frequency histogram.

Used on the ingest/update path where keys arrive unsorted (the sorted path
uses the O(m log n) searchsorted trick in core.cdf). TPU adaptation: binning
is a one-hot compare + a (1, T) x (T, m) matmul so the accumulation runs on
the MXU; the m-bin accumulator lives in VMEM across grid steps (same output
block for every step, initialized at step 0).

Tiling: keys are streamed HBM->VMEM in (8, 128) f32 tiles; the histogram is
one (1, m_pad) f32 block (m_pad = m rounded up to a lane multiple of 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .call import pallas_call

TILE_R, TILE_C = 8, 128
TILE = TILE_R * TILE_C


def _hist_kernel(prm_ref, keys_ref, out_ref, *, m: int, n_valid: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo, inv_span = prm_ref[0, 0], prm_ref[0, 1]
    k = keys_ref[...].reshape(TILE)                       # (TILE,) f32
    gidx = step * TILE + jax.lax.broadcasted_iota(jnp.int32, (TILE, 1), 0)[:, 0]
    valid = gidx < n_valid
    x = (k - lo) * inv_span
    # right-closed bins: bin = ceil(x*m) - 1, clipped
    b = jnp.clip(jnp.ceil(x * m).astype(jnp.int32) - 1, 0, m - 1)
    m_pad = out_ref.shape[1]
    onehot = (b[:, None] == jax.lax.broadcasted_iota(jnp.int32, (TILE, m_pad), 1))
    onehot = jnp.where(valid[:, None], onehot.astype(jnp.float32), 0.0)
    ones = jnp.ones((1, TILE), jnp.float32)
    out_ref[...] += jnp.dot(ones, onehot,                  # (1, m_pad) on MXU
                            preferred_element_type=jnp.float32)


def hist_pallas(keys: jax.Array, m: int, lo, hi, *,
                interpret: bool = True) -> jax.Array:
    """Relative-frequency m-bin histogram of ``keys`` (any 1-D float array).

    Returns float32 (m,) frequencies summing to 1.
    """
    n = keys.shape[0]
    n_pad = -(-n // TILE) * TILE
    m_pad = -(-m // 128) * 128
    kp = jnp.pad(keys.astype(jnp.float32), (0, n_pad - n))
    kp = kp.reshape(n_pad // TILE, TILE_R, TILE_C)
    lo32 = jnp.asarray(lo, jnp.float32)
    span = jnp.maximum(jnp.asarray(hi, jnp.float32) - lo32, 1e-30)
    prm = jnp.zeros((1, 128), jnp.float32).at[0, 0].set(lo32) \
        .at[0, 1].set(1.0 / span)

    def kern(prm_ref, keys_ref, out_ref):
        _hist_kernel(prm_ref, keys_ref, out_ref, m=m, n_valid=n)

    counts = pallas_call(
        kern, prm, kp,
        grid=(n_pad // TILE,),
        in_specs=[pl.BlockSpec((1, 128), lambda i: (0, 0)),
                  pl.BlockSpec((1, TILE_R, TILE_C), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, m_pad), jnp.float32),
        interpret=interpret)
    return counts[0, :m] / jnp.float32(n)
