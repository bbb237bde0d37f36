"""Streamed cross-entropy kernel — the loss as a dataflow consumer.

Grid (t_blocks, v_blocks): per step the kernel computes one [bt, bv] logits
tile (hidden @ head tile on the MXU), folds it into a running online
logsumexp, and extracts the gold logit where the label falls in this vocab
tile.  The [T, V] logits tensor never exists — in itensor terms the logits
stream has type itensor<bt x bv, [T/bt, V/bv]*[bt, bv], (d0,d1)->(d0,d1)>
and its only consumer (the reduction) is fused, so the stream collapses
in-VMEM (paper §4.3.2 itensor folding).

Emits (lse [T], gold [T]); loss = mean(lse - gold) over valid labels,
computed by the wrapper.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, kernel_name, pick_block

# Autotune candidate lattice (tuning/autotune.py): vocab tiles are
# the dominant stream (the [T, V] logits never materialize), token
# tiles bound the online-logsumexp state resident per step.
TUNE_SPACE = {"block_t": (128, 256), "block_v": (512, 1024, 2048)}

NEG_INF = -1e30


def _xent_kernel(h_ref, w_ref, y_ref, lse_ref, gold_ref, m_ref, s_ref,
                 g_ref, *, n_v: int, block_v: int, vocab_size: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        g_ref[...] = jnp.full_like(g_ref, NEG_INF)

    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)     # [bt, bv]
    v_start = vi * block_v
    v_pos = v_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = v_pos < vocab_size
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
    s_ref[...] = s_ref[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new

    # Gold logit: the label's column may fall inside this vocab tile.
    y = y_ref[...]                                            # [bt]
    hit = (v_pos == y[:, None])
    tile_gold = jnp.max(jnp.where(hit, logits, NEG_INF), axis=-1,
                        keepdims=True)
    g_ref[...] = jnp.maximum(g_ref[...], tile_gold)

    @pl.when(vi == n_v - 1)
    def _done():
        lse_ref[...] = (m_ref[...] + jnp.log(
            jnp.maximum(s_ref[...], 1e-30)))[:, 0]
        gold_ref[...] = g_ref[...][:, 0]


def streamed_xent_parts(hidden: jax.Array, head: jax.Array,
                        labels: jax.Array, *, vocab_size: int,
                        block_t: int = 256, block_v: int = 2048,
                        interpret: Optional[bool] = None,
                        ) -> Tuple[jax.Array, jax.Array]:
    """hidden: [T, D]; head: [D, Vp]; labels: [T] -> (lse [T], gold [T])."""
    t, d = hidden.shape
    _, vp = head.shape
    bt = pick_block(t, block_t)
    bv = pick_block(vp, block_v)
    grid = (t // bt, vp // bv)
    interpret = interpret_default() if interpret is None else interpret
    lse, gold = pl.pallas_call(
        functools.partial(_xent_kernel, n_v=grid[1], block_v=bv,
                          vocab_size=vocab_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bt,), lambda i, j: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((bt,), lambda i, j: (i,)),
            pl.BlockSpec((bt,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t,), jnp.float32),
            jax.ShapeDtypeStruct((t,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=interpret,
        **kernel_name("streamed_xent"),
    )(hidden, head, labels)
    return lse, gold


def streamed_xent_loss(hidden: jax.Array, head: jax.Array,
                       labels: jax.Array, *, vocab_size: int,
                       interpret: Optional[bool] = None, **kw) -> jax.Array:
    """Mean CE over labels >= 0 (ignore index < 0), flat token axis."""
    lse, gold = streamed_xent_parts(hidden, head, jnp.maximum(labels, 0),
                                    vocab_size=vocab_size,
                                    interpret=interpret, **kw)
    valid = labels >= 0
    nll = jnp.where(valid, lse - gold, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)
