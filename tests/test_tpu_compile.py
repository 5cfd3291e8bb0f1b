"""Every Pallas kernel, the served dispatch and the update programs
compile for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
lower each ``pallas_call`` at the smoke run's widths (S = 2^27 keys, Q =
1024 and 4096 queries) for one chip of a described ``v5e:2x2`` topology,
under the process's real x64 setting, and check that the kernel is in the
compiled program (``tpu_custom_call``) — what interpret mode cannot show:
Mosaic refuses gathers across vregs, 64-bit scalars and unaligned blocks.
The insert, delete and leaf-rebuild programs compile at the same widths:
XLA:TPU must not meet an f64 prefix scan there (leaf refits run on the host
CPU backend, ``core.updates._host_device``).  Nothing runs, so results and
times are not checked here.

The topology is described in a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.  Without
the TPU library (``libtpu``) the tests skip; any other failure to describe
the topology fails them.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import repro  # noqa: F401  (x64 on)
from repro.core import rmi
from repro.core import updates as upd
from repro.kernels import hist, ksdist, linfit, lookup

S = 1 << 27          # the one-chip smoke's base-tier capacity class
L = 1 << 16          # its leaves per shard
ND = 1 << 10         # a delta tier after a few insert batches
B = 4096             # an insert or delete batch


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None \
            and not os.environ.get("TPU_LIBRARY_PATH"):
        pytest.skip("the TPU library (libtpu) is not installed")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            from jax.experimental import topologies
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        finally:
            jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_LOOKUPS = {
    "lookup": lambda q, r, m, v, k, d: lookup.lookup_pallas(
        q, r, m, v, k, n_leaves=L, iters=14, interpret=False),
    "dynamic_lookup": lambda q, r, m, v, k, d: lookup.dynamic_lookup_pallas(
        q, r, m, v, k, d, n_leaves=L, route_n=S, iters=14, interpret=False),
    "dynamic_range": lambda q, r, m, v, k, d: lookup.dynamic_range_pallas(
        q, q, r, m, v, k, d, n_leaves=L, route_n=S, iters=14,
        interpret=False),
    "rmrt_lookup": lambda q, r, m, v, k, d: lookup.rmrt_lookup_pallas(
        q, m, v, k, fanout=64, depth=3, iters=14, interpret=False),
}


@pytest.mark.parametrize("Q", [1024, 4096])
@pytest.mark.parametrize("name", sorted(_LOOKUPS))
def test_lookup_kernel_compiles(one_chip, name, Q):
    sds = lambda shape, dtype: _sds(one_chip, shape, dtype)
    text = _compiled_text(
        _LOOKUPS[name], sds((Q,), jnp.float64), sds((8, 128), jnp.float32),
        sds((3 * lookup.H, L), jnp.float32), sds((8, L), jnp.float32),
        sds((S,), jnp.float64), sds((ND,), jnp.float64))
    assert "tpu_custom_call" in text


_SMALL = {
    "hist": (lambda k: hist.hist_pallas(k, 64, 0.0, 1.0, interpret=False),
             [((S,), jnp.float64)]),
    "ksdist": (lambda a, b, c: ksdist.ksdist_pallas(a, b, c,
                                                    interpret=False),
               [((L, 64), jnp.float32), ((300, 64), jnp.float32),
                ((300, 64), jnp.float32)]),
    "linfit": (lambda x, y, b: linfit.linfit_sums_pallas(x, y, b, L,
                                                         interpret=False),
               [((1 << 20,), jnp.float32), ((1 << 20,), jnp.float32),
                ((1 << 20,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(_SMALL))
def test_build_kernel_compiles(one_chip, name):
    fn, shapes = _SMALL[name]
    text = _compiled_text(fn, *[_sds(one_chip, s, d) for s, d in shapes])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["find", "range"])
def test_sharded_dispatch_compiles(topo, kind):
    """The front-end's stacked one-tenant dispatch on a one-chip mesh, as
    the smoke run serves it: shard_map with its varying-axes check on,
    routed exchange, compiled kernel, f64 rank algebra."""
    from repro.core import distributed as dist

    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    make = dist._tenant_stacked_range_fn if kind == "range" \
        else dist._tenant_stacked_find_fn
    fn = make(mesh, "data", n_tenants=1, n_leaves=L, leaf_kind="linear",
              iters=14, use_kernel=True, interpret=False)
    sh = dist.NamedSharding(mesh, dist.P())
    sds = lambda shape, dtype: _sds(sh, (1, 1) + shape, dtype)
    tables = (sds((8, 128), jnp.float32),
              sds((3 * lookup.H, L), jnp.float32),
              sds((8, L), jnp.float32))
    q = _sds(sh, (1, 512 * (2 if kind == "range" else 1)), jnp.float64)
    args = (_sds(sh, (1, 0), jnp.float64), sds((), jnp.int32),
            sds((), jnp.float64), sds((S,), jnp.float64),
            sds((S,), jnp.bool_), sds((S + 1,), jnp.int32),
            sds((ND,), jnp.float64), sds((ND,), jnp.bool_),
            sds((ND + 1,), jnp.int32), tables, q)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _update_programs(leaves, root):
    """(program, argument shapes) of every device program that
    ``insert_batch``, ``delete_batch`` and a leaf rebuild
    (``_rebuild_leaves``, which ``flush_delta`` runs) dispatch, at the
    smoke's widths."""
    f64, i32, b1 = jnp.float64, jnp.int32, jnp.bool_
    T = jax.ShapeDtypeStruct
    vec, mask = T((L,), f64), T((L,), b1)
    return {
        "route": (jax.jit(lambda r, k: rmi.root_buckets("linear", r, k, L, S)),
                  [root, T((B,), f64)]),
        "fill_delta": (functools.partial(upd._fill_delta_jit, cap_out=2 * B),
                       [T((B,), f64), T((B,), i32)]),
        "merge_delta_clean": (
            functools.partial(upd._merge_delta_clean_jit, cap_out=ND),
            [T((ND,), f64), T((ND,), i32), T((B // 8,), f64),
             T((B // 8,), i32)]),
        "merge_delta": (
            functools.partial(upd._merge_delta_jit, cap_out=ND),
            [T((ND,), f64), T((ND,), i32), T((ND,), b1), T((B // 8,), f64),
             T((B // 8,), i32)]),
        "batch_counts": (functools.partial(upd._batch_counts_sorted,
                                           n_leaves=L), [T((B,), i32)]),
        "delete": (upd._delete_jit, [T((S,), f64), T((S,), b1),
                                     T((ND,), f64), T((ND,), b1),
                                     T((B,), f64)]),
        "psum": (upd._psum, [T((S,), b1)]),
        "moved_counts": (upd._moved_counts_sorted, [T((ND,), i32), mask]),
        "gather_moved": (upd._gather_moved, [T((ND,), f64), T((ND,), i32),
                                             T((ND,), b1), mask]),
        "merge_base": (functools.partial(upd._merge_base_jit, cap_out=S,
                                         has_dead=True),
                       [T((S,), f64), T((S,), b1), T((ND,), f64)]),
        "compose_rebuild": (
            functools.partial(upd._compose_rebuild_jit, leaf_kind="linear"),
            [leaves, vec, vec, mask, vec, leaves, vec, vec, mask, vec, vec,
             mask, vec, T((), f64), T((), f64)]),
    }


_UPDATE_NAMES = ["route", "fill_delta", "merge_delta_clean", "merge_delta",
                 "batch_counts", "delete", "psum", "moved_counts",
                 "gather_moved", "merge_base", "compose_rebuild"]


@pytest.fixture(scope="module")
def linear_models():
    """Shapes of the leaf and root models of a linear-root, linear-leaf
    index with L leaves."""
    keys = np.sort(np.random.default_rng(0).random(4 * L))
    idx = upd.DynamicRMI.build(keys, n_leaves=L, kind="linear").index
    return jax.eval_shape(lambda: (idx.leaves, idx.root))


@pytest.mark.parametrize("name", _UPDATE_NAMES)
def test_update_program_compiles(one_chip, linear_models, name):
    fn, shapes = _update_programs(*linear_models)[name]
    args = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), shapes)
    jax.jit(fn).lower(*args).compile()
