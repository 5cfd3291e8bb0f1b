"""Sharding contract for the manual-SPMD model code.

Mesh axes (DESIGN.md §4):
  pod    — pure data parallel across pods (gradient psum)
  data   — batch shard + FSDP/ZeRO-3 parameter shard
  model  — tensor parallel (heads / d_ff / vocab / experts)

Model code always runs under shard_map with all three axes bound; a
single-device smoke test uses a (1,1,1) mesh so the same collectives become
no-ops. Conventions:
  * every weight leaf carries FSDP on the axis named by its spec; the
    gather helper materializes the full weight just-in-time (backward
    auto-transposes to psum_scatter => ZeRO gradient reduction for free)
  * activations are replicated across `model` between blocks; each block
    ends in exactly one psum over `model`
  * the batch dim is sharded over ("pod", "data")
"""
from __future__ import annotations

import jax

POD, FSDP, TP = "pod", "data", "model"


# Batch-carrying axes. The production single-pod mesh is (data, model) with
# no pod axis, so this is configured per step-factory (set_batch_axes runs
# again inside each step_fn, i.e. at trace time, making the psums correct
# for whichever mesh the enclosing shard_map binds).
_BATCH_AXES = ("pod", "data")


def set_batch_axes(axes) -> None:
    global _BATCH_AXES
    _BATCH_AXES = tuple(axes)


def batch_axes() -> tuple:
    return _BATCH_AXES


def batch_axes_for(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


_FSDP_GATHER_ON = True     # serve-replicated mode turns JIT gathers off
_PSUM_DTYPE = None         # hillclimb lever: bf16 block-output psums


def set_fsdp_gather(on: bool) -> None:
    """serve-replicated mode: weights arrive full per chip (no data-axis
    shard), so the JIT gather must become identity. Trace-time global,
    set inside each step_fn like set_batch_axes."""
    global _FSDP_GATHER_ON
    _FSDP_GATHER_ON = on


def set_psum_dtype(dtype) -> None:
    """Cast block outputs to `dtype` (e.g. bf16) before the TP psum —
    halves the dominant all-reduce payload (EXPERIMENTS.md §Perf)."""
    global _PSUM_DTYPE
    _PSUM_DTYPE = dtype


_MESH_AXES = ("pod", "data", "model")


def set_mesh_axes(axes) -> None:
    """Trace-time: the axis names bound by the enclosing shard_map (set by
    every step factory, like set_batch_axes)."""
    global _MESH_AXES
    _MESH_AXES = tuple(axes)


def pvary_all(x):
    """Mark a value as varying over every bound mesh axis it is not varying
    on yet — vma alignment for scan carries under check_vma=True
    (numerically a no-op)."""
    def one(v):
        vma = jax.typeof(v).vma
        missing = tuple(a for a in _MESH_AXES if a not in vma)
        return jax.lax.pcast(v, missing, to="varying") if missing else v
    return jax.tree.map(one, x)


def scan_aligned(body, init, xs, length=None):
    """lax.scan whose initial carry is pcast to the body's NATURAL output
    vma (found by abstract evaluation). Over-varying the carry (e.g. a
    blanket pvary over all axes) is numerically a no-op forward but poisons
    the backward: implicit invariant->varying promotions inside the body
    transpose to psums, silently scaling gradients by axis sizes
    (tests/test_multidevice.py::test_spmd_numeric_equivalence guards this).
    """
    x0 = None if xs is None else jax.tree.map(lambda a: a[0], xs)

    def align(c, av):
        want = getattr(av, "vma", None) or frozenset()
        have = jax.typeof(c).vma or frozenset()
        missing = tuple(a for a in want if a not in have)
        return jax.lax.pcast(c, missing, to="varying") if missing else c

    for _ in range(2):  # vma grows monotonically; 2 rounds reach fixpoint
        out_sh = jax.eval_shape(lambda c, x: body(c, x)[0], init, x0)
        init = jax.tree.map(align, init, out_sh)
    return jax.lax.scan(body, init, xs, length=length)


def psum_forced(x, axes):
    """psum over `axes`, first marking x varying on any of them it is typed
    invariant on. For genuinely-replicated values this MULTIPLIES by the
    axis size — callers use it only where the value is either truly varying
    or the axis is degenerate (size 1 / weighted out, e.g. grad-norm
    accounting with repl_w)."""
    def one(v):
        missing = tuple(a for a in axes if a not in jax.typeof(v).vma)
        v = jax.lax.pcast(v, missing, to="varying") if missing else v
        return jax.lax.psum(v, axes)
    return jax.tree.map(one, x)


def unvary(x, keep=()):
    """Re-mark a value as replicated over every axis it is typed varying on
    (except `keep`). Implemented as pmax — the numeric identity for values
    that are already replicated — so shard_map out_specs like P() type-check
    under check_vma=True."""
    def one(v):
        axes = tuple(a for a in jax.typeof(v).vma if a not in keep)
        return jax.lax.pmax(v, axes) if axes else v
    return jax.tree.map(one, x)


def fsdp_gather(w: jax.Array, axis: int = 0) -> jax.Array:
    """Materialize the FSDP-sharded dim of a weight (ZeRO-3 just-in-time
    gather). Transpose under grad = psum_scatter over `data`."""
    if not _FSDP_GATHER_ON:
        return w
    return jax.lax.all_gather(w, FSDP, axis=axis, tiled=True)


def tp_psum(x: jax.Array) -> jax.Array:
    if _PSUM_DTYPE is not None:
        return jax.lax.psum(x.astype(_PSUM_DTYPE), TP)
    return jax.lax.psum(x, TP)


def tp_index() -> jax.Array:
    return jax.lax.axis_index(TP)


def axis_size(name: str) -> int:
    return jax.lax.axis_size(name)


def dp_psum(x: jax.Array) -> jax.Array:
    """Reduction over every batch-carrying axis (loss/metric aggregation)."""
    return jax.lax.psum(x, batch_axes())


def pod_psum(x: jax.Array) -> jax.Array:
    return jax.lax.psum(x, POD)
