"""Tiled matmul Pallas kernel — the baseline dataflow 'Kernel' (paper Fig.1).

Grid (m_blocks, n_blocks, k_blocks); K is the innermost (sequential) grid dim
so the f32 VMEM accumulator persists across K steps — the itensor iteration
space [M/bm, N/bn, K/bk] with map (d0,d1,d2)->(d0,d1) on the output (K is a
reuse dim), exactly the Fig. 5(c) pattern.  Block shapes are MXU-aligned
(multiples of 128) for the production path; test shapes fall back to exact
divisors.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name, pick_block

# Autotune candidate lattice (tuning/autotune.py) in KernelChoice
# block names (block_t/block_n map onto this wrapper's block_m/
# block_n); lint-illegal points are pruned before timing.
TUNE_SPACE = {"block_t": (128, 256, 512), "block_n": (128, 256, 512)}


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_matmul(x: jax.Array, w: jax.Array, *,
                 block_m: int = 256, block_n: int = 256, block_k: int = 512,
                 out_dtype: Optional[jnp.dtype] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """x: [M, K] @ w: [K, N] -> [M, N] with VMEM-tiled accumulation."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    bm = pick_block(m, block_m)
    bn = pick_block(n, block_n)
    bk = pick_block(k, block_k)
    grid = (m // bm, n // bn, k // bk)
    interpret = interpret_default() if interpret is None else interpret

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **kernel_name("block_matmul"),
    )(x, w)
