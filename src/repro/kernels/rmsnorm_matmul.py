"""Fused RMSNorm -> matmul kernel (norm streamed into the projection).

The normalized activation never round-trips HBM: per token tile the kernel
computes the row rsqrt statistics in VMEM and immediately feeds the
normalized tile into the MXU against a [D, bn] weight tile.  Grid
(t_blocks, n_blocks); the full D row is kept resident (D <= ~8k fits VMEM
comfortably at bt=256: 256*8192*2B = 4 MiB).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import interpret_default, kernel_name, pick_block

# Autotune candidate lattice (tuning/autotune.py): block-target grids
# the measured-latency tuner scores for this kernel family.  Points
# the kernel lint rejects (lane floor, VMEM budget) are pruned before
# anything is compiled or timed.
TUNE_SPACE = {"block_t": (128, 256, 512), "block_n": (128, 256, 512)}


def _kernel(x_ref, scale_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    normed = normed * (1.0 + scale_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.dot(normed.astype(x_ref.dtype), w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _kernel_w8(x_ref, scale_ref, w_ref, ws_ref, o_ref, *, eps: float):
    """Weight-only int8 body (DESIGN.md §14): ``w`` holds int8 codes with
    per-output-channel f32 scales.  The dot runs codes-against-f32 and the
    column scale is applied POST-dot — mathematically identical to
    dequantizing the tile first (``x @ (codes * s) == (x @ codes) * s``
    column by column), but streaming 1 byte/weight from HBM."""
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(var + eps)
    normed = normed * (1.0 + scale_ref[...].astype(jnp.float32))
    acc = jnp.dot(normed, w_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = (acc * ws_ref[...]).astype(o_ref.dtype)


def rmsnorm_matmul(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                   eps: float = 1e-6, block_t: int = 256,
                   block_n: int = 512,
                   w_scale: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """x: [T, D]; scale: [D]; w: [D, N] -> rms_norm(x) @ w  [T, N].

    ``w_scale`` [N]: weight-only int8 — ``w`` is int8 codes, dequantized
    against the per-output-channel scales inside the kernel.  The scales
    ride as a ``[1, N]`` row in ``(1, bn)`` blocks, the lane-aligned 2-D
    form Mosaic accepts for a partial block.
    """
    t, d = x.shape
    d2, n = w.shape
    assert d == d2 and scale.shape == (d,)
    bt = pick_block(t, block_t)
    bn = pick_block(n, block_n)
    grid = (t // bt, n // bn)
    interpret = interpret_default() if interpret is None else interpret
    in_specs = [
        pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        pl.BlockSpec((d,), lambda i, j: (0,)),
        pl.BlockSpec((d, bn), lambda i, j: (0, j)),
    ]
    operands = [x, scale, w]
    kernel = _kernel
    if w_scale is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        operands.append(w_scale.astype(jnp.float32).reshape(1, n))
        kernel = _kernel_w8
    return pl.pallas_call(
        functools.partial(kernel, eps=eps),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), x.dtype),
        interpret=interpret,
        **kernel_name("rmsnorm_matmul"),
    )(*operands)
