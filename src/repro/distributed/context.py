"""Active-mesh context + the ``shard_map`` the fused wrappers use.

The mesh-aware StreamPlan (core/stream_plan.py) decides *which* mesh axes
each fused kernel's block grid shards over; the fused wrappers in
``models/layers.py`` need the actual ``Mesh`` object at trace time to
build the ``shard_map``.  Threading a mesh argument through every model
entry point would churn the whole call graph, so the mesh rides in a
context variable instead: the serving engine and the jitted step builders
enter ``use_mesh(mesh)`` around plan resolution and dispatch tracing, and
``current_mesh()`` is what the wrappers (and ``resolve_plan``) read.

This module deliberately imports nothing from ``repro`` so it can be
imported lazily from ``models/layers.py`` and ``core/stream_plan.py``
without creating an import cycle through ``distributed/__init__``.
"""

from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar
from typing import Iterator, Optional

from jax import shard_map as _shard_map
from jax.sharding import Mesh

_ACTIVE_MESH: ContextVar[Optional[Mesh]] = ContextVar(
    "repro_active_mesh", default=None)


def current_mesh() -> Optional[Mesh]:
    """The mesh the enclosing ``use_mesh`` installed, or None (1-device)."""
    return _ACTIVE_MESH.get()


@contextmanager
def use_mesh(mesh: Optional[Mesh]) -> Iterator[Optional[Mesh]]:
    """Install ``mesh`` as the active mesh for plan resolution and fused
    dispatch within the dynamic extent (None is a no-op single-device
    context, so callers need not branch)."""
    token = _ACTIVE_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE_MESH.reset(token)


def shard_map(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off: the
    Pallas kernels inside carry no replication annotations."""
    return _shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
