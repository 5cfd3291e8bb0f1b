"""HloCost accountant: exactness on controlled programs (the reason this
exists: XLA cost_analysis counts while bodies once)."""
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.launch.hlo_cost import HloCost


def test_scan_trip_counts():
    def body(c, _):
        return c @ c, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    got = HloCost(c.as_text()).summary()["flops"]
    want = 8 * 2 * 256 ** 3
    assert abs(got - want) / want < 0.01, (got, want)
    # and confirm XLA's own number misses the trip count
    xla = c.cost_analysis()
    assert xla["flops"] < want / 4


def test_nested_scan():
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=3)
        return y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(x).compile()
    got = HloCost(c.as_text()).summary()["flops"]
    want = 15 * 2 * 128 ** 3
    assert abs(got - want) / want < 0.02, (got, want)


def test_dot_flops_plain():
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((64, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 32), jnp.float32)
    c = jax.jit(f).lower(a, b).compile()
    got = HloCost(c.as_text()).summary()["flops"]
    want = 2 * 64 * 512 * 32
    assert abs(got - want) / want < 0.01, (got, want)


def test_collectives_counted_with_trips():
    devs = jax.device_count()
    if devs < 2:
        import pytest
        pytest.skip("needs >1 device")
    mesh = jax.make_mesh((devs,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P

    def step(x, _):
        return jax.lax.psum(x, "d"), None

    def f(x):
        y, _ = jax.lax.scan(step, x, None, length=4)
        return y

    sm = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=True)
    x = jax.ShapeDtypeStruct((1024,), jnp.float32)
    c = jax.jit(sm).lower(x).compile()
    s = HloCost(c.as_text()).summary()
    n = devs
    want = 4 * 2 * 1024 * 4 * (n - 1) / n      # 4 trips, ring all-reduce
    got = s["collective_bytes"]
    assert abs(got - want) / want < 0.05, (got, want)
