"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benchmarks see the real single device.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto (sharding propagated by
    the compiler, as the plan's shard_map claims expect)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """The grading mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests use small host-device meshes, e.g. (2,4))."""
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over however many (possibly forced) host devices exist."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, "
                         f"have {n}")
    return _make_mesh((data, model), ("data", "model"))
