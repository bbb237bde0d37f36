"""MoE expert-FFN Pallas kernel (dense-gather EP formulation).

Grid (t_blocks, experts); the expert dimension is the sequential inner loop
accumulating the gated expert outputs in VMEM.  Each step computes one
expert's GLU on the resident token tile and folds it in weighted by that
expert's gate column — the router->dispatch->expert->combine chain of the
dataflow graph collapsed into one streaming kernel (gates with zero weight
still compute: the dense-gather trade that makes experts shardable over the
model axis without all-to-alls; see DESIGN.md §6).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name, pick_block

# Autotune candidate lattice (tuning/autotune.py): the expert grid is
# fixed by the config, so only the token tile is searched.
TUNE_SPACE = {"block_t": (128, 256, 512)}


def _act(kind: str, x):
    if kind == "silu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)


def _moe_kernel(x_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                n_e: int, activation: str):
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    h = (_act(activation, gate) * up).astype(x.dtype)
    y = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
    g = g_ref[...][:, 0:1].astype(jnp.float32)       # [bt, 1] this expert
    acc_ref[...] += y * g

    @pl.when(ei == n_e - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_experts_pallas(x: jax.Array, gates: jax.Array, wg: jax.Array,
                       wu: jax.Array, wd: jax.Array, *,
                       activation: str = "silu", block_t: int = 256,
                       interpret: Optional[bool] = None) -> jax.Array:
    """x: [T, D]; gates: [T, E] (zero off the top-k); wg/wu: [E, D, F];
    wd: [E, F, D] -> [T, D]."""
    t, d = x.shape
    e, d2, f = wg.shape
    assert d == d2 and gates.shape == (t, e)
    bt = pick_block(t, block_t)
    grid = (t // bt, e)
    interpret = interpret_default() if interpret is None else interpret
    return pl.pallas_call(
        functools.partial(_moe_kernel, n_e=e, activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, j)),
            pl.BlockSpec((1, d, f), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, d, f), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, f, d), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        **kernel_name("moe_experts"),
    )(x, gates, wg, wu, wd)
