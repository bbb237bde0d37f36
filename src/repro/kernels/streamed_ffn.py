"""Stream-fused GLU FFN — the canonical StreamTensor kernel fusion.

Computes ``down( act(x @ Wg) * (x @ Wu) )`` with the [T, d_ff] intermediate
living ONLY in VMEM: grid (t_blocks, f_blocks) where the f dimension is the
sequential inner loop.  Per (t, f) step the kernel produces one intermediate
tile, immediately consumes it against the matching Wd tile, and accumulates
the [bt, d_model] output in a VMEM scratch — producer (gate/up matmuls) and
consumer (down matmul) are *stream-fused* exactly as the paper fuses Kernel0
into Kernel1 through an on-chip buffer instead of external memory.

With ``norm_scale`` the pre-FFN RMSNorm is folded in as well (the StreamPlan
path when the fusion pass grouped ln2 with the projections): each x tile is
normalized in VMEM right before hitting the MXU, so the normalized
activation never round-trips HBM either.  The norm is recomputed per f-step
on the resident x tile — pure VPU work traded for an HBM stream, the same
trade ``rmsnorm_matmul`` makes.

The itensor view: the intermediate's type is
    itensor<bt x bf, [T/bt, F/bf] * [bt, bf], (d0,d1)->(d0,d1)>
for both producer and consumer — types match, so fusion needs no layout
converter and the FIFO collapses to a single VMEM tile (itensor folding,
paper §4.3.2).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name, pick_block

# Autotune candidate lattice (tuning/autotune.py) shared by
# streamed_ffn and streamed_mlp; lint-pruned before timing.
TUNE_SPACE = {"block_t": (128, 256, 512), "block_f": (128, 256, 512)}


def _act(kind: str, x):
    if kind == "silu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)


def _rms_tile(x, scale_ref, eps: float):
    """RMS-normalize one [bt, D] tile in VMEM (matches layers.rms_norm)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    y = y * (1.0 + scale_ref[...].astype(jnp.float32))
    return y.astype(x.dtype)


def _row(scale: jax.Array) -> jax.Array:
    """Per-channel scales [N] -> the f32 [1, N] row the w8 bodies read."""
    return scale.astype(jnp.float32).reshape(1, -1)


def _ffn_kernel(*refs, n_f: int, activation: str, norm_eps: Optional[float]):
    if norm_eps is not None:
        x_ref, scale_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref = refs
    else:
        x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if norm_eps is not None:
        x = _rms_tile(x, scale_ref, norm_eps)
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (_act(activation, gate) * up).astype(x.dtype)   # stays in VMEM
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _ffn_kernel_w8(*refs, n_f: int, activation: str,
                   norm_eps: Optional[float]):
    """Weight-only int8 body (DESIGN.md §14): wg/wu/wd are int8 codes with
    per-output-channel f32 scales.  Gate/up scales apply pre-activation
    (the nonlinearity needs real values); the down scale applies post-dot
    per accumulation step — both exact per-column dequantizations, with
    every weight streamed from HBM at 1 byte."""
    if norm_eps is not None:
        (x_ref, scale_ref, wg_ref, wgs_ref, wu_ref, wus_ref, wd_ref,
         wds_ref, o_ref, acc_ref) = refs
    else:
        (x_ref, wg_ref, wgs_ref, wu_ref, wus_ref, wd_ref, wds_ref,
         o_ref, acc_ref) = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if norm_eps is not None:
        x = _rms_tile(x, scale_ref, norm_eps)
    x32 = x.astype(jnp.float32)
    gate = jnp.dot(x32, wg_ref[...].astype(jnp.float32),
                   preferred_element_type=jnp.float32) * wgs_ref[...]
    up = jnp.dot(x32, wu_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32) * wus_ref[...]
    h = _act(activation, gate) * up                     # stays in VMEM
    acc_ref[...] += jnp.dot(h, wd_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32
                            ) * wds_ref[...]

    @pl.when(pl.program_id(1) == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_ffn(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
                 *, activation: str = "silu",
                 norm_scale: Optional[jax.Array] = None,
                 norm_eps: float = 1e-6,
                 block_t: int = 256, block_f: int = 512,
                 wg_scale: Optional[jax.Array] = None,
                 wu_scale: Optional[jax.Array] = None,
                 wd_scale: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """x: [T, D]; wg/wu: [D, F]; wd: [F, D] -> [T, D].

    ``norm_scale`` [D]: fold ``rms_norm(x, norm_scale)`` into the kernel.
    ``wg_scale``/``wu_scale`` [F] + ``wd_scale`` [D]: weight-only int8 —
    the weights are int8 codes dequantized in-kernel per output channel.
    Scales ride as ``[1, N]`` rows in ``(1, bf)`` / ``(1, D)`` blocks, the
    lane-aligned 2-D form Mosaic accepts for a partial block.
    """
    t, d = x.shape
    d2, f = wg.shape
    assert d == d2 and wu.shape == (d, f) and wd.shape == (f, d)
    bt = pick_block(t, block_t)
    bf = pick_block(f, block_f)
    grid = (t // bt, f // bf)
    interpret = interpret_default() if interpret is None else interpret
    w8 = wg_scale is not None

    in_specs = [pl.BlockSpec((bt, d), lambda i, j: (i, 0))]
    operands = [x]
    if norm_scale is not None:
        in_specs.append(pl.BlockSpec((d,), lambda i, j: (0,)))
        operands.append(norm_scale)
    if w8:
        in_specs += [
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ]
        operands += [wg, _row(wg_scale), wu, _row(wu_scale),
                     wd, _row(wd_scale)]
        kernel = _ffn_kernel_w8
    else:
        in_specs += [
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),
        ]
        operands += [wg, wu, wd]
        kernel = _ffn_kernel

    return pl.pallas_call(
        functools.partial(kernel, n_f=grid[1], activation=activation,
                          norm_eps=norm_eps if norm_scale is not None
                          else None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        **kernel_name("streamed_ffn"),
    )(*operands)


def _mlp_kernel(*refs, n_f: int, activation: str, norm_eps: Optional[float]):
    if norm_eps is not None:
        x_ref, scale_ref, wu_ref, wd_ref, o_ref, acc_ref = refs
    else:
        x_ref, wu_ref, wd_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if norm_eps is not None:
        x = _rms_tile(x, scale_ref, norm_eps)
    h = _act(activation,
             jnp.dot(x, wu_ref[...],
                     preferred_element_type=jnp.float32)).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mlp_kernel_w8(*refs, n_f: int, activation: str,
                   norm_eps: Optional[float]):
    """Weight-only int8 ungated body (see ``_ffn_kernel_w8``)."""
    if norm_eps is not None:
        (x_ref, scale_ref, wu_ref, wus_ref, wd_ref, wds_ref,
         o_ref, acc_ref) = refs
    else:
        x_ref, wu_ref, wus_ref, wd_ref, wds_ref, o_ref, acc_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if norm_eps is not None:
        x = _rms_tile(x, scale_ref, norm_eps)
    x32 = x.astype(jnp.float32)
    up = jnp.dot(x32, wu_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32) * wus_ref[...]
    h = _act(activation, up)
    acc_ref[...] += jnp.dot(h, wd_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32
                            ) * wds_ref[...]

    @pl.when(pl.program_id(1) == n_f - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_mlp(x: jax.Array, wu: jax.Array, wd: jax.Array, *,
                 activation: str = "gelu",
                 norm_scale: Optional[jax.Array] = None,
                 norm_eps: float = 1e-6,
                 block_t: int = 256, block_f: int = 512,
                 wu_scale: Optional[jax.Array] = None,
                 wd_scale: Optional[jax.Array] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Ungated variant (GPT-2 / HuBERT): down(act(x @ Wu)).

    ``wu_scale`` [F] + ``wd_scale`` [D]: weight-only int8 codes.
    """
    t, d = x.shape
    _, f = wu.shape
    bt = pick_block(t, block_t)
    bf = pick_block(f, block_f)
    grid = (t // bt, f // bf)
    interpret = interpret_default() if interpret is None else interpret
    w8 = wu_scale is not None

    in_specs = [pl.BlockSpec((bt, d), lambda i, j: (i, 0))]
    operands = [x]
    if norm_scale is not None:
        in_specs.append(pl.BlockSpec((d,), lambda i, j: (0,)))
        operands.append(norm_scale)
    if w8:
        in_specs += [
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((1, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d), lambda i, j: (0, 0)),
        ]
        operands += [wu, _row(wu_scale), wd, _row(wd_scale)]
        kernel = _mlp_kernel_w8
    else:
        in_specs += [
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, d), lambda i, j: (j, 0)),
        ]
        operands += [wu, wd]
        kernel = _mlp_kernel

    return pl.pallas_call(
        functools.partial(kernel, n_f=grid[1], activation=activation,
                          norm_eps=norm_eps if norm_scale is not None
                          else None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        **kernel_name("streamed_ffn"),
    )(*operands)
