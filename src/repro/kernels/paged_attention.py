"""Paged attention Pallas kernel — each slot's live K/V pages streamed by
indirection.

W-token attention over the paged KV cache (``serving/kv_cache.py``): each
slot's W query rows attend to that slot's pages through the page table.
Decode is the W = 1 case; speculative verify scores the pending token plus
W - 1 drafts in the same dispatch.  The kernel follows the paper's
streaming pattern: K/V pages are DMA'd into VMEM, their score tile is
produced, folded into the online-softmax running (m, l, acc), and
discarded — the per-slot score row never materializes in HBM.

Grid: ``(slots,)``.  The page table and per-slot offsets ride in as
*scalar-prefetch* operands (``PrefetchScalarGridSpec``); the K/V pools
stay in HBM (``memory_space=pl.ANY``) and the kernel copies pages by hand
(``pltpu.make_async_copy``) through ``table[b, j]`` — the explicit
data-movement-by-indirection that PowerFusion's IR spells out and that a
dense BlockSpec cannot express.

What streams is each slot's live extent and nothing more.
``live_page_span`` gives a slot's first page (the sliding window's first,
when ``window`` is set) and its page count from ``q_off + W``; a
``fori_loop`` runs ``cdiv(count, pages_per_block)`` blocks, and within
the last block only live pages are copied, each under its own guard.  A
slot of length L streams ``ceil(L / page_size)`` pages; a slot of length
0 streams none and produces zeros.

The pools are page-major (``[P, Hkv, page_size, D]``), so one page of
every kv head is one contiguous ``[Hkv, page_size, D]`` DMA.  A block is
``pages_per_block`` such pages — one 128-lane score tile of tokens — and
folds into the running (m, l, acc) of every kv head at once: the query
block ``[Hkv, W*G, D]`` (the ``G = Hq // Hkv`` query heads sharing a kv
head, times the W window rows) is loaded once per slot.  Blocks are
double-buffered: while one computes, the next is in flight — the slot's
next block, or at its last block the next slot's first, so slots do not
serialise on DMA latency.  The pools are read as stored, never padded
per call: at head_dim 64 the chip keeps a page's rows in 128-lane tiles,
and the kernel copies those tiles and contracts their first 64 lanes.

Per-row length (and optional sliding-window) masking is applied per
element.  Positions a row cannot see fold in as exact no-ops (p == 0,
corr == 1), so each verify row is bit-identical to the single-token
decode at its own length.  Interpret-mode fallback on CPU, same as every
kernel in this package.

Quantized pools (DESIGN.md §14): when ``k_scale``/``v_scale`` pools are
passed, the K/V pools hold int8 / fp8-e4m3 codes and the kernel
dequantizes each page in VMEM inside the online-softmax loop —
``k = codes.astype(f32) * scale[page, head]``.  Each page's per-kv-head
f32 scale row is copied into SMEM through the same ``table[b, j]``
indirection as the page itself, so the page stream's HBM traffic drops to
the code itemsize while the math stays f32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANE, interpret_default, kernel_name, round_up

# Autotune candidate lattice (tuning/autotune.py): KV page sizes the
# tuner scores for the paged decode stream.  A page is one block's
# second-to-last dim, so sizes stay multiples of the 8-row sublane tile;
# the tuned winner becomes the PagedKVCache page size AND the
# verify-window granule (both dispatches read the same pools).
TUNE_SPACE = {"page_size": (8, 16, 32, 64)}

NEG_INF = -1e30


def pages_per_block(page_size: int, n_pages: int) -> int:
    """Pages one streamed block holds: enough to fill one 128-lane score
    tile with tokens, never more than a slot's table."""
    return max(1, min(n_pages, LANE // page_size))


def live_page_span(q_off, *, win: int, page_size: int, n_pages: int,
                   window: int = 0, xp=np):
    """(first page, page count) a slot streams: every page holding a
    position one of its W query rows attends to.  Row i of the window
    sits at ``q_off + i`` and sees positions ``< q_off + i + 1``, from
    ``q_off + i + 1 - window`` on when ``window`` is set; so the pages
    run from row 0's window start to the page holding ``q_off + W - 1``,
    clipped to the table.  ``q_off`` may be a scalar or an array (the
    host counts whole dispatches at once); ``xp`` is the array module,
    ``jax.numpy`` inside the kernel."""
    end = xp.minimum((q_off + win + page_size - 1) // page_size, n_pages)
    if not window:
        return 0, xp.maximum(end, 0)
    first = xp.maximum(q_off + 1 - window, 0) // page_size
    return first, xp.maximum(end - first, 0)


def _paged_kernel(off_ref, tbl_ref, q_ref, k_hbm, v_hbm, *refs,
                  page_size: int, n_pages: int, ppb: int, lanes: int,
                  scale_lanes: int, scale: float, window: int, win: int,
                  g: int, quant: bool):
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, kf_ref,
         vf_ref, sems, cur_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (o_ref, k_buf, v_buf, sems, cur_ref, m_ref, l_ref, acc_ref) = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    hkv, rows, d = q_ref.shape[1:]
    bt = ppb * page_size                       # tokens per block

    def span(s):
        return live_page_span(off_ref[s], win=win, page_size=page_size,
                              n_pages=n_pages, window=window, xp=jnp)

    def page_copies(s, blk, slot, which, j):
        """Page ``j`` of block ``blk`` of slot ``s`` into buffer ``slot``:
        its K (which=0) or V (1) page of every kv head, and with
        quantized pools its scale row."""
        pool, buf, sbuf_hbm, sbuf = ((k_hbm, k_buf, ks_hbm, ks_buf)
                                     if which == 0 else
                                     (v_hbm, v_buf, vs_hbm, vs_buf))
        page = tbl_ref[s, span(s)[0] + blk * ppb + j]
        cps = [pltpu.make_async_copy(
            pool.at[page, :, :, pl.ds(0, lanes)], buf.at[slot, :, j],
            sems.at[slot, which])]
        if quant:
            cps.append(pltpu.make_async_copy(
                sbuf_hbm.at[page, :, pl.ds(0, scale_lanes)],
                sbuf.at[slot, j], sems.at[slot, which]))
        return cps

    def over_live_pages(s, blk, fn):
        """``fn(j)`` for each page of block ``blk`` of slot ``s`` that
        holds live positions: the block's pages up to the slot's
        extent."""
        n = jnp.clip(span(s)[1] - blk * ppb, 0, ppb)

        def body(j, carry):
            fn(j)
            return carry
        jax.lax.fori_loop(0, n, body, 0)

    def start(s, blk, slot):
        def fn(j):
            for which in (0, 1):
                for c in page_copies(s, blk, slot, which, j):
                    c.start()
        over_live_pages(s, blk, fn)

    def wait(s, blk, slot, which):
        def fn(j):
            for c in page_copies(s, blk, slot, which, j):
                c.wait()
        over_live_pages(s, blk, fn)

    def load(buf, sbuf, f_ref, slot):
        """Block ``slot`` as f32 [Hkv, ppb * page_size, D], dequantized
        page by page (scale per page and kv head) for quantized pools."""
        if quant:
            def deq(h, carry):
                for j in range(ppb):
                    f_ref[h, j] = (buf[slot, h, j].astype(jnp.float32)
                                   * sbuf[slot, j, 0, h])
                return carry
            jax.lax.fori_loop(0, hkv, deq, 0)
            x = f_ref[...]
        else:
            x = buf[slot].astype(jnp.float32)
        return x.reshape(hkv, bt, lanes)[..., :d]

    @pl.when(b == 0)
    def _first_call():
        cur_ref[0] = 0

    first, count = span(b)
    n_blocks = pl.cdiv(count, ppb)
    q_off = off_ref[b]

    # A slot's first block was prefetched by the previous slot's last
    # block iff that slot streamed anything.
    prev_streamed = jnp.logical_and(b > 0, span(jnp.maximum(b - 1, 0))[1] > 0)

    @pl.when(jnp.logical_and(count > 0, jnp.logical_not(prev_streamed)))
    def _own_first_block():
        start(b, 0, cur_ref[0])

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32) * scale                # [Hkv, R, D]
    # Row i of the q block is query head (i % g) of window slot (i // g):
    # its causal extent is q_off + (i // g) + 1.
    qlen = q_off + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 0) // g + 1
    tok = jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
    tok_row = jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)

    def block(i, carry):
        slot = cur_ref[0]
        nxt = 1 - slot

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, i + 1, nxt)

        next_slot = jnp.minimum(b + 1, n_slots - 1)

        @pl.when(jnp.logical_and(
            i + 1 == n_blocks,
            jnp.logical_and(b + 1 < n_slots, span(next_slot)[1] > 0)))
        def _next_slot():
            start(next_slot, 0, nxt)

        base = (first + i * ppb) * page_size
        kv_pos = base + tok
        mask = kv_pos < qlen
        if window:
            mask = jnp.logical_and(mask, kv_pos >= qlen - window)

        wait(b, i, slot, 0)
        k = load(k_buf, ks_buf, kf_ref if quant else None, slot)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # [Hkv, R, bt]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

        wait(b, i, slot, 1)
        v = load(v_buf, vs_buf, vf_ref if quant else None, slot)
        # Pages past the slot's extent were not copied: whatever their
        # buffer rows hold must not reach p @ V (0 * NaN is NaN).
        v = jnp.where(base + tok_row < (first + count) * page_size, v, 0.0)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        cur_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           q_off: jax.Array, *, window: int = 0,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """W-token attention against paged K/V pools.

    q: [B, W, Hq, D] — the pending token plus W-1 draft candidates per
    slot; k_pool/v_pool: [P, Hkv, page_size, D] (page-major pages from
    ``serving/kv_cache.py``); page_table: [B, max_pages] int32 physical
    page ids (NULL page for unallocated entries); q_off: [B] absolute
    position of window row 0 (row i attends causally through
    ``q_off + i``, i.e. length ``q_off + i + 1``).  Returns [B, W, Hq, D].

    The window's rows are stacked into the query block kv-head-major, so
    each K/V page is copied once for the whole window and every head.

    Quantized pools: pass ``k_scale``/``v_scale`` [P, Hkv] f32 (both or
    neither) — the pools are then int8/fp8 codes, dequantized in VMEM.
    """
    b, w, hq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    n_pages = page_table.shape[1]
    g = hq // hkv
    quant = k_scale is not None
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    interpret = interpret_default() if interpret is None else interpret
    ppb = pages_per_block(page_size, n_pages)
    # The chip stores a page's rows in 128-lane tiles, so a head_dim 64
    # page lies in HBM as [Hkv, page_size, 128] with its upper lanes
    # unused: copy the tiles as stored and contract the first D lanes.
    # The same holds for a [1, Hkv] scale row.  The interpreter sees the
    # logical shapes.
    lanes = d if interpret else round_up(d, LANE)
    scale_lanes = hkv if interpret else round_up(hkv, LANE)
    # [B, W, Hq, D] -> [B, Hkv, W*G, D]: kv-head-major with the window
    # rows interleaved (row = w_idx * G + g_idx), so program b holds every
    # (kv head, window slot, query head) triple of slot b.
    qk = q.reshape(b, w, hkv, g, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, hkv, w * g, d)

    def qmap(bi, *scalars):
        return (bi, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, hkv, w * g, d), qmap), hbm, hbm]
    operands = (qk, k_pool, v_pool)
    buf = pltpu.VMEM((2, hkv, ppb, page_size, lanes), k_pool.dtype)
    scratch = [buf, buf]
    if quant:
        # A page's [1, Hkv] scale row is copied into SMEM beside the
        # page: the scale pools grow with the KV pool and do not fit SMEM
        # whole.  As [P, 1, Hkv] the row is full-dim in its last two dims.
        in_specs += [hbm, hbm]
        operands += (k_scale.astype(jnp.float32)[:, None],
                     v_scale.astype(jnp.float32)[:, None])
        scratch += [pltpu.SMEM((2, ppb, 1, scale_lanes), jnp.float32)] * 2
        scratch += [pltpu.VMEM((hkv, ppb, page_size, lanes),
                               jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),       # [buffer, K or V]
        pltpu.SMEM((1,), jnp.int32),           # buffer of the next block
        pltpu.VMEM((hkv, w * g, 1), jnp.float32),
        pltpu.VMEM((hkv, w * g, 1), jnp.float32),
        pltpu.VMEM((hkv, w * g, d), jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # q_off, page_table
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, w * g, d), qmap),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, n_pages=n_pages, ppb=ppb,
            lanes=lanes, scale_lanes=scale_lanes, scale=scale,
            window=window, win=w, g=g, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, w * g, d), q.dtype),
        # Slots run in order: a slot's last block prefetches the next
        # slot's first into the buffer the next grid step reads.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        **kernel_name("paged_attention"),
    )(q_off.astype(jnp.int32), page_table.astype(jnp.int32), *operands)
    return out.reshape(b, hkv, w, g, d).transpose(0, 2, 1, 3, 4) \
              .reshape(b, w, hq, d)


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
