"""Shared Pallas kernel utilities.

All kernels in this package target TPU (pl.pallas_call + BlockSpec VMEM
tiling, MXU-aligned block shapes) and are *validated* on CPU with
``interpret=True`` — the kernel body executes in Python against the
``ref.py`` oracles.  ``on_tpu()`` picks the execution mode.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

MXU = 128          # systolic array edge: align matmul dims to multiples
LANE = 128         # vreg lanes (last dim)
SUBLANE = 8        # vreg sublanes (2nd-to-last dim, f32)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Interpret mode everywhere except a real TPU."""
    return not on_tpu()


def kernel_name(name: str) -> dict:
    """``pl.pallas_call`` keywords naming a kernel: the custom call's name
    and its ``kernel_metadata`` (``{"kernel": name}``), which the compiled
    program carries (``frontend_attributes={kernel_metadata=...}``)."""
    return {"name": name, "metadata": {"kernel": name}}


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pick_block(extent: int, target: int, align: int = MXU) -> int:
    """Largest aligned block <= target that divides extent; falls back to the
    largest divisor <= target when alignment is impossible (small test
    shapes), mirroring the tiling-space policy in core/tiling.py."""
    cap = min(extent, target)
    best = None
    for b in range(cap, 0, -1):
        if extent % b:
            continue
        if b % align == 0:
            return b
        if best is None:
            best = b
    return best or extent


def vmem_bytes(*shapes_dtypes: Tuple[Tuple[int, ...], jnp.dtype]) -> int:
    total = 0
    for shape, dtype in shapes_dtypes:
        total += math.prod(shape) * jnp.dtype(dtype).itemsize
    return total
