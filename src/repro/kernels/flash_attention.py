"""Flash attention Pallas kernel — attention as a streaming dataflow.

Grid (batch*kv_heads*group, q_blocks, kv_blocks); the kv dimension is the
sequential inner loop carrying (m, l, acc) in VMEM scratch — the online
softmax IS the paper's streaming pattern: score tiles are produced, consumed,
and discarded without ever visiting HBM.  Causal masking skips fully-masked
kv blocks with ``pl.when`` (no MXU work issued).

Supports GQA (q heads grouped over kv heads), causal and sliding-window
masks.  Head dim padded to the 128-lane width by the wrapper in ops.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_default, pick_block

# Autotune candidate lattice (tuning/autotune.py): query/KV stream
# tile grid for the measured-latency tuner; lint-pruned pre-compile.
TUNE_SPACE = {"block_q": (128, 256, 512), "block_kv": (128, 256, 512)}

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, block_q: int, block_kv: int, scale: float,
                  causal: bool, window: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv
    # Block-level skip: a kv block strictly after every query position of
    # this q block contributes nothing under causal masking — no MXU work is
    # issued for it.  This is where flash attention earns its O(S*w) local
    # cost (window lower-bound masking is per-element below).
    run = (k_start <= q_start + block_q - 1) if causal else (ki >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bkv]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[0], 1e-30)
        o_ref[0] = (acc_ref[0] / l).astype(o_ref.dtype)


def _flash_kernel_offset(meta_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                         acc_ref, *, n_kv: int, block_q: int, block_kv: int,
                         scale: float, causal: bool, window: int):
    """Offset twin of ``_flash_kernel`` for chunked prefill: query
    positions are ``q_offset + i`` and the valid KV length is dynamic,
    both carried in the scalar-prefetch ``meta_ref = [q_offset, kv_len]``
    — one compiled program serves any chunk index over any cache fill.
    """
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = meta_ref[0]
    kv_len = meta_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + q_off          # absolute query positions
    k_start = ki * block_kv
    # Block-level skips mirror the static kernel, but against the DYNAMIC
    # offset/length: kv blocks past the valid cache fill, or strictly
    # after every (absolute) query position of this q block, issue no MXU
    # work.  With a sliding window, blocks wholly before the earliest
    # query's window are dead too.
    run = k_start < kv_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run, k_start + block_kv > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [bq, bkv]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[0], 1e-30)
        o_ref[0] = (acc_ref[0] / l).astype(o_ref.dtype)


def _flash_kernel_offset_q(meta_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                           o_ref, m_ref, l_ref, acc_ref, *, n_kv: int,
                           block_q: int, block_kv: int, scale: float,
                           causal: bool, window: int):
    """Quantized twin of ``_flash_kernel_offset`` (DESIGN.md §14): K/V
    blocks are int8/fp8 codes dequantized in-register against per-POSITION
    f32 scales (``[Hkv_, 1, Skv]`` operands blocked alongside K/V — each
    KV position inherits its page's per-(page, head) scale, expanded by
    the gather wrapper).  A position's scale is a column scale of the
    score tile and a row scale of V, so both apply as a lane-major
    ``[1, bkv]`` row — ``q @ (codes * s)^T == (q @ codes^T) * s`` and
    ``p @ (codes * s) == (p * s) @ codes`` — with no in-kernel transpose.
    Math stays f32; masking/skips are unchanged."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_off = meta_ref[0]
    kv_len = meta_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + q_off          # absolute query positions
    k_start = ki * block_kv
    run = k_start < kv_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window:
        run = jnp.logical_and(run, k_start + block_kv > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (
                ks_ref[0] * scale)                           # [bq, bkv]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p * vs_ref[0], v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[0], 1e-30)
        o_ref[0] = (acc_ref[0] / l).astype(o_ref.dtype)


def flash_attention_2d(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int = 0,
                       kv_len=None,
                       scale: Optional[float] = None,
                       kv_group: int = 1,
                       block_q: int = 512, block_kv: int = 512,
                       q_offset=None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Flattened-head core: q [Hq_, Sq, D], k/v [Hkv_, Skv, D] where
    ``Hq_ == Hkv_ * kv_group`` -> [Hq_, Sq, D].

    GQA without K/V materialization: the KV BlockSpec index map sends the
    ``kv_group`` query-head programs sharing a KV head to the SAME K/V
    blocks (itensor view: the head dim is a *reuse* dim of the K/V stream —
    Fig. 5(c) again).

    ``q_offset`` (None = 0, static) shifts query positions for chunked
    prefill: query i masks as absolute position ``q_offset + i`` against
    a KV extent that already holds earlier chunks.  When it is given (an
    int or a traced scalar), it and ``kv_len`` ride in as scalar-prefetch
    operands so ONE compiled program serves every chunk of every prompt;
    ``kv_len`` may then be dynamic too (the valid fill of the cache).

    Quantized K/V (offset path only): pass ``k_scale``/``v_scale``
    [Hkv_, Skv] f32 per-position scales — k/v are then int8/fp8 codes,
    dequantized block-by-block in-register.
    """
    h, sq, d = q.shape
    _, skv, _ = k.shape
    kv_len = kv_len if kv_len is not None else skv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = pick_block(sq, block_q)
    bkv = pick_block(skv, block_kv)
    grid = (h, sq // bq, skv // bkv)
    interpret = interpret_default() if interpret is None else interpret
    g = kv_group
    quant = k_scale is not None
    if quant and q_offset is None:
        raise NotImplementedError(
            "quantized flash attention only supports the offset "
            "(chunked-prefill) path")

    if q_offset is not None:
        meta = jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                          jnp.asarray(kv_len, jnp.int32).reshape(())])

        def kv_block(b, i, j, meta):
            # Bound KV traffic by the live prefix: a kv block wholly past
            # the dynamic kv_len (= meta[1]) contributes nothing (its
            # ``run`` predicate is false), so clamp its index to the LAST
            # LIVE block — the pipeline re-fetches an already-resident
            # block instead of DMA'ing dead pages, and ``pl.when``
            # discards the (never-issued) compute.  Chunked prefill reads
            # O(prefix) K/V per chunk instead of O(table extent).
            last_live = jnp.maximum(meta[1] - 1, 0) // bkv
            return (b // g, jnp.minimum(j, last_live), 0)

        def sc_block(b, i, j, meta):
            last_live = jnp.maximum(meta[1] - 1, 0) // bkv
            return (b // g, 0, jnp.minimum(j, last_live))

        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i, j, meta: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), kv_block),
            pl.BlockSpec((1, bkv, d), kv_block),
        ]
        operands = (q, k, v)
        if quant:
            # Scales as [Hkv_, 1, Skv]: a (1, bkv) block row is then
            # full-dim on the sublane axis and lane-aligned.
            in_specs += [pl.BlockSpec((1, 1, bkv), sc_block),
                         pl.BlockSpec((1, 1, bkv), sc_block)]
            operands += (k_scale.astype(jnp.float32)[:, None],
                         v_scale.astype(jnp.float32)[:, None])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # [q_offset, kv_len]
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda b, i, j, meta: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, bq, 1), jnp.float32),
                pltpu.VMEM((1, bq, 1), jnp.float32),
                pltpu.VMEM((1, bq, d), jnp.float32),
            ],
        )
        kernel = _flash_kernel_offset_q if quant else _flash_kernel_offset
        return pl.pallas_call(
            functools.partial(
                kernel, n_kv=grid[2], block_q=bq,
                block_kv=bkv, scale=scale, causal=causal, window=window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
            interpret=interpret,
        )(meta, *operands)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, n_kv=grid[2], block_q=bq, block_kv=bkv,
            scale=scale, causal=causal, window=window, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, bq, 1), jnp.float32),
            pltpu.VMEM((1, bq, 1), jnp.float32),
            pltpu.VMEM((1, bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
