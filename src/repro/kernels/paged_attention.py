"""Paged attention Pallas kernel — K/V pages streamed by indirection.

W-token attention over the paged KV cache (``serving/kv_cache.py``): each
slot's W query rows attend to that slot's pages through the page table.
Decode is the W = 1 case; speculative verify scores the pending token plus
W - 1 drafts in the same dispatch.  The kernel follows the paper's
streaming pattern: each K/V page is DMA'd into VMEM, its score tile is
produced, folded into the online-softmax running (m, l, acc), and
discarded — the per-slot score row never materializes in HBM.

Grid: ``(slots, kv_heads, n_pages)`` with the page dimension as the
sequential inner loop carrying the accumulators in VMEM scratch.  The page
table and per-slot offsets ride in as *scalar-prefetch* operands
(``PrefetchScalarGridSpec``) so the K/V BlockSpec index maps are
data-dependent: program (b, h, j) fetches page ``table[b, j]`` of kv head
h — the explicit data-movement-by-indirection that PowerFusion's IR spells
out and that a dense BlockSpec cannot express.  The pools are head-major
(``[P, Hkv, page_size, D]``), so one (page, head) block is a dense
``[page_size, D]`` tile whose last two dims satisfy the TPU's (8, 128)
block rule.  GQA falls out of the grid: the ``G = Hq // Hkv`` query heads
sharing a KV head (times the W window rows) live in one block, so each K/V
page is fetched once per kv head.

Pages wholly outside every row's extent are skipped with ``pl.when`` (no
MXU work, though the page DMA itself is still issued by the pipeline);
unallocated table entries point at the NULL page so the indirection is
always in bounds.  Per-row length (and optional sliding-window) masking is
applied per element inside the page.  Pages a row cannot see fold in as
exact no-ops (p == 0, corr == 1), so each verify row is bit-identical to
the single-token decode at its own length.  Interpret-mode fallback on
CPU, same as every kernel in this package.

Quantized pools (DESIGN.md §14): when ``k_scale``/``v_scale`` pools are
passed, the K/V pools hold int8 / fp8-e4m3 codes and the kernel
dequantizes each page IN-REGISTER inside the online-softmax loop —
``k = codes.astype(f32) * scale[page, head]``.  Each page's per-kv-head
f32 scale row is streamed into SMEM through the same ``tbl[b, j]``
indirection as the page itself, so the page stream's HBM traffic drops to
the code itemsize while the math stays f32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANE, interpret_default, round_up

# Autotune candidate lattice (tuning/autotune.py): KV page sizes the
# tuner scores for the paged decode stream.  A page is one block's
# second-to-last dim, so sizes stay multiples of the 8-row sublane tile;
# the tuned winner becomes the PagedKVCache page size AND the
# verify-window granule (both dispatches read the same pools).
TUNE_SPACE = {"page_size": (8, 16, 32, 64)}

NEG_INF = -1e30


def _paged_kernel(off_ref, tbl_ref, q_ref, k_ref, v_ref, *refs,
                  page_size: int, n_pages: int, scale: float, window: int,
                  win: int, g: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_off = off_ref[b]
    page_start = j * page_size
    # Page-level skip across the whole window: the deepest row (win-1)
    # attends through q_off + win, the shallowest (row 0) starts its
    # sliding window at q_off + 1 - window; pages outside that union are
    # dead for every row.
    run = page_start < q_off + win
    if window:
        run = jnp.logical_and(
            run, page_start + page_size > q_off + 1 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [win*G, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [ps, D]
        v = v_ref[0, 0].astype(jnp.float32)                # [ps, D]
        if quant:
            k = k * ks_ref[0, 0, h]
            v = v * vs_ref[0, 0, h]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [win*G, ps]
        rows = win * g
        # Row i of the q block is query head (i % g) of window slot
        # (i // g): its causal extent is q_off + (i // g) + 1.
        q_idx = jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // g
        kv_pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qlen = q_off + q_idx + 1
        mask = kv_pos < qlen
        if window:
            mask = jnp.logical_and(mask, kv_pos >= qlen - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           q_off: jax.Array, *, window: int = 0,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """W-token attention against paged K/V pools.

    q: [B, W, Hq, D] — the pending token plus W-1 draft candidates per
    slot; k_pool/v_pool: [P, Hkv, page_size, D] (head-major pages from
    ``serving/kv_cache.py``); page_table: [B, max_pages] int32 physical
    page ids (NULL page for unallocated entries); q_off: [B] absolute
    position of window row 0 (row i attends causally through
    ``q_off + i``, i.e. length ``q_off + i + 1``).  Returns [B, W, Hq, D].

    The window's rows are stacked into the query block kv-head-major, so
    K/V pages are still fetched once per kv head for the whole window.

    Quantized pools: pass ``k_scale``/``v_scale`` [P, Hkv] f32 (both or
    neither) — the pools are then int8/fp8 codes, dequantized in-register.
    """
    b, w, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    n_pages = page_table.shape[1]
    g = hq // hkv
    quant = k_scale is not None
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    interpret = interpret_default() if interpret is None else interpret
    dp = d if interpret else round_up(d, LANE)
    if dp != d:
        pad = ((0, 0),) * 3 + ((0, dp - d),)
        q = jnp.pad(q, pad)
        k_pool = jnp.pad(k_pool, pad)
        v_pool = jnp.pad(v_pool, pad)
    # [B, W, Hq, D] -> [B, Hkv, W*G, D]: kv-head-major with the window
    # rows interleaved (row = w_idx * G + g_idx), so program (b, h) holds
    # every (window slot, query head) pair sharing KV head h.
    qk = q.reshape(b, w, hkv, g, dp).transpose(0, 2, 1, 3, 4) \
          .reshape(b, hkv, w * g, dp)

    def qmap(bi, hi, ji, *scalars):
        return (bi, hi, 0, 0)

    def kvmap(bi, hi, ji, off, tbl, *scalars):
        return (tbl[bi, ji], hi, 0, 0)

    def scalemap(bi, hi, ji, off, tbl, *scalars):
        return (tbl[bi, ji], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, w * g, dp), qmap),
        pl.BlockSpec((1, 1, page_size, dp), kvmap),
        pl.BlockSpec((1, 1, page_size, dp), kvmap),
    ]
    operands = (qk, k_pool, v_pool)
    if quant:
        # One page's [1, Hkv] scale row per step, streamed into SMEM
        # through the same indirection as the page: the scale pools grow
        # with the KV pool and do not fit SMEM whole as scalar-prefetch
        # operands.  As [P, 1, Hkv] the row block is full-dim in its last
        # two dims.
        in_specs += [pl.BlockSpec((1, 1, hkv), scalemap,
                                  memory_space=pltpu.SMEM)] * 2
        operands += (k_scale.astype(jnp.float32)[:, None],
                     v_scale.astype(jnp.float32)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # q_off, page_table
        grid=(b, hkv, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, w * g, dp), qmap),
        scratch_shapes=[
            pltpu.VMEM((w * g, 1), jnp.float32),
            pltpu.VMEM((w * g, 1), jnp.float32),
            pltpu.VMEM((w * g, dp), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, n_pages=n_pages,
            scale=scale, window=window, win=w, g=g, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, w * g, dp), q.dtype),
        interpret=interpret,
    )(q_off.astype(jnp.int32), page_table.astype(jnp.int32), *operands)
    return out.reshape(b, hkv, w, g, dp).transpose(0, 2, 1, 3, 4) \
              .reshape(b, w, hq, dp)[..., :d]


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, window: int = 0,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One-token attention against paged K/V pools: the W = 1 window.

    q: [B, 1, Hq, D]; lengths: [B] valid entries per slot (including the
    token appended this step); the rest as ``paged_verify_attention``.
    A slot with length 0 (inactive) produces zeros — its output is
    discarded by the engine.
    """
    return paged_verify_attention(
        q, k_pool, v_pool, page_table, lengths.astype(jnp.int32) - 1,
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale,
        interpret=interpret)
