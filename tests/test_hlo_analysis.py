"""Validate the loop-aware HLO analyzer against controlled programs."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _xla_cost(compiled):
    return compiled.cost_analysis()


def test_plain_matmul_flops_match_xla():
    x = jnp.zeros((128, 256), jnp.float32)
    w = jnp.zeros((256, 512), jnp.float32)
    c = _compile(lambda a, b: a @ b, x, w)
    ours = analyze_hlo(c.as_text())
    want = 2 * 128 * 256 * 512
    assert abs(ours["flops"] - want) / want < 0.05
    xla = _xla_cost(c)["flops"]
    assert abs(ours["flops"] - xla) / xla < 0.05


def test_scan_multiplies_by_trip_count():
    """THE bug this module exists to fix: XLA counts while bodies once."""
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jnp.zeros((128, 128), jnp.float32)
    c = _compile(f, x)
    ours = analyze_hlo(c.as_text())
    one = 2 * 128 ** 3
    assert abs(ours["flops"] - 10 * one) / (10 * one) < 0.05
    xla = _xla_cost(c)["flops"]
    assert xla < 2 * one            # XLA counted the body once
    assert ours["flops"] > 8 * xla  # we restored the factor


def test_nested_scans():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            y, _ = jax.lax.scan(inner, c, None, length=4)
            return y + 1.0, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jnp.zeros((64, 64), jnp.float32)
    c = _compile(f, x)
    ours = analyze_hlo(c.as_text())
    want = 3 * 4 * 2 * 64 ** 3
    assert abs(ours["flops"] - want) / want < 0.10


def test_dot_with_batch_dims():
    x = jnp.zeros((8, 64, 32), jnp.float32)
    w = jnp.zeros((8, 32, 16), jnp.float32)
    c = _compile(lambda a, b: jnp.einsum("bij,bjk->bik", a, b), x, w)
    ours = analyze_hlo(c.as_text())
    want = 2 * 8 * 64 * 32 * 16
    assert abs(ours["flops"] - want) / want < 0.05


def test_collectives_counted_with_trip_scaling():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >=2 devices (run under forced host devices)")
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((len(devs),), ("model",))
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, "model")))
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P()))

    def f(a, b):
        def body(c, _):
            h = c @ b                                   # sharded out
            h = jax.lax.with_sharding_constraint(
                h, NamedSharding(mesh, P()))            # all-gather
            return h, None
        y, _ = jax.lax.scan(body, a, None, length=6)
        return y

    c = jax.jit(f).lower(x, w).compile()
    ours = analyze_hlo(c.as_text())
    # 6 iterations x all-gather of a [32,256] f32 activation.
    assert ours["collective_link_total"] > 0
    n = len(devs)
    per_ag = 32 * 256 * 4 * (n - 1) / n
    total = ours["collective_link_total"]
    assert total >= 5 * per_ag * 0.5   # trip scaling happened


def test_memory_bytes_reasonable():
    x = jnp.zeros((1024, 1024), jnp.float32)
    c = _compile(lambda a: jnp.tanh(a) + 1.0, x)
    ours = analyze_hlo(c.as_text())
    want = 2 * 1024 * 1024 * 4          # read + write
    assert 0.5 * want <= ours["bytes_accessed"] <= 4 * want
