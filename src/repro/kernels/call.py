"""The one way into ``pl.pallas_call`` for every kernel of this package.

The package runs with x64 on (``repro/__init__``), but Mosaic lowers only
32-bit scalars: under x64 every Python literal in a kernel body or an index
map traces as a 64-bit value, and the TPU compile fails (an ``i64`` grid
index, or a recursion in the element-type conversion rule).  Every kernel
here takes 32-bit operands, so :func:`pallas_call` traces the kernel under
``jax.enable_x64(False)``; the jit around it keeps x64.

It also types the outputs for ``jax.shard_map(..., check_vma=True)``: each
output varies over the mesh axes its operands vary over.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *args, out_shape, **kw):
    """``pl.pallas_call(kernel, out_shape=..., **kw)(*args)``, traced with
    32-bit defaults and with ``out_shape`` (one ``ShapeDtypeStruct`` or a
    list of them) given the operands' varying mesh axes."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    typed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
    if isinstance(out_shape, (list, tuple)):
        out_shape = [typed(s) for s in out_shape]
    else:
        out_shape = typed(out_shape)
    with jax.enable_x64(False):
        return pl.pallas_call(kernel, out_shape=out_shape, **kw)(*args)
