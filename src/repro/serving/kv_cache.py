"""Paged KV cache: fixed-size pages + slot indirection for decode.

The contiguous slot cache (PR 1) reserves ``slots x max_len`` worst-case
K/V per layer group.  This module pages it, vLLM/TensorRT-LLM style:

  * K/V storage is a *pool* of fixed-size pages per layer group, stored
    page-major and head-major within a page: ``[G, P, Hkv, page_size,
    hd]`` regardless of the model's ``kv_cache_layout`` (append/gather
    adapt at the edges, so both "bshd" and "bhsd" configs run paged).
    One (page, kv head) is then a dense ``[page_size, hd]`` tile — the
    block the paged attention kernel streams.
  * A device-resident page table ``[slots, max_pages] int32`` maps each
    slot's logical page j to a physical page id.  Physical page 0 is the
    NULL page: unallocated table entries point at it, so inactive slots'
    decode writes land in a sacrificial page and data-dependent page
    lookups (the Pallas kernel's scalar-prefetch index map) never read out
    of bounds.
  * Pages are allocated from a host-side free list as a slot's sequence
    grows and returned when the request finishes — bytes-in-use is
    ``pages_in_use * page_bytes``, not ``slots * max_len`` worst case.
  * Allocation is REFCOUNTED (DESIGN.md §10): several slots may reference
    the same physical page (a shared prompt prefix), and the prefix cache
    (``serving/prefix_cache.py``) may hold a page *cached* after every
    referencing slot exits.  A page is therefore in exactly one of three
    states — free (on the free list), referenced (``refs > 0``), or
    cached (tree-owned, ``refs == 0``, reclaimed lazily through the
    ``evictor`` hook when the free list runs dry) — and
    ``assert_page_accounting`` checks that partition.  Shared pages are
    never written in place: the first divergent write goes through a
    copy-on-write page swap (``cow_page`` + the ``cow_src``/``cow_dst``
    operands of ``paged_append``/``place_chunk_pages``).
  * Non-sequence state leaves (SSM / conv / wkv / token-shift) carry no
    sequence axis; they stay slot-contiguous ``[G, slots, ...]`` and are
    whole-replaced per slot.  Leaf classification comes from the shared
    schema in ``models/params.py`` (``cache_leaf_kind``) — an unknown leaf
    raises instead of being silently mishandled.

The functional primitives (``paged_append`` / ``gather_pages`` /
``place_prefill``) are pure: they take and return arrays so the engine can
run them inside donated jits, and ``models/model.py`` calls
``paged_append`` from the decode step when a page table is passed.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..models.params import (CacheDef, cache_defs, cache_leaf_kind,
                             cache_leaf_name)
from ..obs import (NULL_RECORDER, PAGE_ALLOC, PAGE_COW, PAGE_EVICT,
                   PAGE_FREE, PAGE_ROLLBACK, TRACK_KV)

Tree = Any

NULL_PAGE = 0       # physical page reserved as the write sink for
#                     unallocated table entries / inactive slots

# Paged-memory invariants the static analyzer (analysis/effects.py)
# checks the pool schema and dispatch effect signatures against — the
# declarative twin of the runtime ``assert_page_accounting`` audit.
POOL_INVARIANTS = {
    # Every page-table-indexed scatter masks dead rows onto NULL_PAGE;
    # page 0 is sacrificial and never allocated to a slot.
    "null_page": NULL_PAGE,
    # Under a KV QuantMode every value pool leaf ``<name>`` carries a
    # sibling ``<name>_scale`` [G, num_pages, Hkv] f32 indexed by the
    # SAME physical page ids; appends/COW/chunk placement update both in
    # lockstep (scales grow monotonically so codes stay valid).
    "scale_suffix": "_scale",
    "scale_dtype": "float32",
    # ``cow_page`` allocates the private dst page fresh (refs == 1,
    # never the src unless both are NULL) before any divergent write.
    "cow_fresh_dst": True,
}


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


# --------------------------------------------------------------------- #
# Functional primitives (jit-safe, layout-adapting)
# --------------------------------------------------------------------- #

def to_seq_major(seq: jax.Array, layout: str) -> jax.Array:
    """K/V with a batch axis -> token-major [..., S, H, hd] order (the
    order per-token appends scatter in).

    seq: [B, S, H, hd] ("bshd") or [B, H, S, hd] ("bhsd").
    """
    if layout == "bhsd":
        return jnp.swapaxes(seq, -3, -2)
    return seq


def to_pages(seq: jax.Array, page_size: int) -> jax.Array:
    """Token-major [..., S, H, hd] (S a multiple of ``page_size``) ->
    pool pages [..., S // page_size, H, page_size, hd]."""
    *lead, s, h, hd = seq.shape
    pages = seq.reshape(*lead, s // page_size, page_size, h, hd)
    return jnp.swapaxes(pages, -3, -2)


def from_pages(pages: jax.Array, layout: str) -> jax.Array:
    """Gathered pool pages [B, n, H, page_size, hd] -> contiguous K/V in
    the model's layout: [B, n*page_size, H, hd] ("bshd") or [B, H,
    n*page_size, hd] ("bhsd")."""
    b, n, h, ps, hd = pages.shape
    if layout == "bhsd":
        return pages.transpose(0, 2, 1, 3, 4).reshape(b, h, n * ps, hd)
    return pages.transpose(0, 1, 3, 2, 4).reshape(b, n * ps, h, hd)


# --------------------------------------------------------------------- #
# KV quantization (DESIGN.md §14)
# --------------------------------------------------------------------- #
#
# Quantized pools store CODES: ``value ≈ code * scale`` with one f32 scale
# per (physical page, kv head) riding in a scale pool ``[G, num_pages,
# Hkv]`` next to each value pool.  Scales are per-page so the paged
# kernels can fetch them through the same page-table indirection as the
# pages themselves, and per-kv-head because head norms
# differ by orders of magnitude while positions within a page do not.

def kv_quant_dtype(kind: Optional[str]):
    """Pool storage dtype for a ``ModelConfig.kv_quant`` kind."""
    if kind is None:
        return None
    if kind == "int8":
        return jnp.int8
    if kind == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown kv quant kind {kind!r}")


def kv_quant_qmax(dtype) -> float:
    """Largest representable code magnitude (amax maps onto it)."""
    if jnp.dtype(dtype) == jnp.int8:
        return 127.0
    return 448.0          # float8_e4m3fn finite max


def quantize_kv(x: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Encode values as codes at ``scale`` (broadcastable): int8 rounds
    and saturates; fp8 stores ``value / scale`` directly (the e4m3 cast
    rounds).  A zero scale (all-zero page) encodes zeros."""
    qmax = kv_quant_qmax(dtype)
    v = jnp.where(scale > 0, x.astype(jnp.float32)
                  / jnp.maximum(scale, 1e-30), 0.0)
    v = jnp.clip(v, -qmax, qmax)
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.round(v).astype(jnp.int8)
    return v.astype(dtype)


def _requant_codes(codes: jax.Array, old_scale: jax.Array,
                   new_scale: jax.Array) -> jax.Array:
    """Re-encode existing page codes after their scale grew (monotone
    scale update): ``code * old / new``.  When the scale is unchanged the
    ratio is exactly 1.0 and the round-trip is the identity, so steady
    appends never drift a page's earlier rows."""
    ratio = jnp.where(new_scale > 0,
                      old_scale / jnp.maximum(new_scale, 1e-30), 0.0)
    v = codes.astype(jnp.float32) * ratio
    if jnp.dtype(codes.dtype) == jnp.int8:
        return jnp.round(v).astype(jnp.int8)
    return v.astype(codes.dtype)


def cow_copy_pool(pool: jax.Array, src: jax.Array,
                  dst: jax.Array) -> jax.Array:
    """Copy physical page(s) ``src`` onto ``dst`` inside a pool.

    pool: [P, H, page_size, hd]; src/dst: int32 scalars or [N] vectors of
    physical page ids.  The copy-on-write primitive: a shared page is
    duplicated into a freshly allocated one *before* the first divergent
    write, so the writer mutates its private copy and every other
    referent keeps reading the original.  Slots with nothing to copy pass
    ``src == dst == NULL_PAGE`` — the NULL page is copied onto itself, a
    no-op (duplicate NULL entries in a vectorized call all write the same
    content, so the scatter stays deterministic).
    """
    return pool.at[dst].set(pool[src])


def paged_append(pool: jax.Array, page_table: jax.Array, pos: jax.Array,
                 new: jax.Array, *, layout: str,
                 cow_src: Optional[jax.Array] = None,
                 cow_dst: Optional[jax.Array] = None) -> jax.Array:
    """Scatter one decode token per slot into its page.

    pool: [P, H, page_size, hd]; page_table: [B, max_pages] int32;
    pos: [B] absolute write positions; new: [B, 1, H, hd] ("bshd") or
    [B, H, 1, hd] ("bhsd").  Unallocated table entries resolve to the NULL
    page, and a position at/past the table's extent routes to the NULL
    page too — an over-run scan tick (or a slot deliberately parked past
    capacity while it is still prefilling) lands in the sacrificial page
    instead of silently rewriting the slot's last real KV row.  The
    scatter is therefore always in bounds and never corrupts live data.

    Copy-on-write path: when a slot's write position lands inside a page
    it does NOT own exclusively (a prefix-shared page — including the
    partial-last-page case where a prompt ends mid-page and decode
    appends into the shared tail page), pass per-slot ``cow_src`` /
    ``cow_dst`` [B] vectors: each slot's ``cow_src`` page is copied onto
    its ``cow_dst`` page *before* the scatter (``NULL_PAGE`` pairs no-op),
    and ``page_table`` must already point at ``cow_dst`` so the write —
    and every later read — resolves to the private copy.
    """
    page_size = pool.shape[2]
    b = page_table.shape[0]
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
    tok = to_seq_major(new, layout)[:, 0]                  # [B, H, hd]
    extent = page_table.shape[1] * page_size
    in_range = jnp.logical_and(pos >= 0, pos < extent)
    posc = jnp.clip(pos, 0, extent - 1)
    phys = jnp.where(in_range,
                     page_table[jnp.arange(b), posc // page_size],
                     NULL_PAGE)                            # [B]
    return pool.at[phys, :, posc % page_size].set(tok.astype(pool.dtype))


def _append_row_q(pool: jax.Array, scale: jax.Array,
                  page_table: jax.Array, pos: jax.Array,
                  tok: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Quantize-on-write core: one page-major token row per slot.

    pool: [P, H, page_size, hd] codes; scale: [P, H] f32; tok: [B, H, hd]
    full-precision.  Per-page scales are MONOTONE non-decreasing: the new
    scale is ``max(old, amax(tok)/qmax)``, and when it grows the page's
    existing rows are re-encoded at the new scale in the same scatter
    (error ~1 code LSB — bounded by the round-trip tests).  NULL routing
    matches ``paged_append``: out-of-range positions write the
    sacrificial page's codes and scale, which nothing dequantizes.
    """
    page_size = pool.shape[2]
    b = page_table.shape[0]
    extent = page_table.shape[1] * page_size
    in_range = jnp.logical_and(pos >= 0, pos < extent)
    posc = jnp.clip(pos, 0, extent - 1)
    phys = jnp.where(in_range,
                     page_table[jnp.arange(b), posc // page_size],
                     NULL_PAGE)                            # [B]
    qmax = kv_quant_qmax(pool.dtype)
    amax = jnp.max(jnp.abs(tok.astype(jnp.float32)), axis=-1)   # [B, H]
    old = scale[phys]                                           # [B, H]
    new = jnp.maximum(old, amax / qmax)
    page = _requant_codes(pool[phys], old[:, :, None, None],
                          new[:, :, None, None])     # [B, H, ps, hd]
    row = quantize_kv(tok, new[..., None], pool.dtype)
    pool = pool.at[phys].set(page)
    pool = pool.at[phys, :, posc % page_size].set(row)
    return pool, scale.at[phys].set(new)


def paged_append_q(pool: jax.Array, scale: jax.Array,
                   page_table: jax.Array, pos: jax.Array, new: jax.Array,
                   *, layout: str,
                   cow_src: Optional[jax.Array] = None,
                   cow_dst: Optional[jax.Array] = None,
                   ) -> Tuple[jax.Array, jax.Array]:
    """Quantized twin of ``paged_append``: scatter one decode token per
    slot as codes and fold its magnitude into the page's scale.  Returns
    ``(pool, scale)``.  The COW copy duplicates the scale row alongside
    the value page — the two pools move in lockstep by construction."""
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
        scale = cow_copy_pool(scale, cow_src, cow_dst)
    tok = to_seq_major(new, layout)[:, 0]                  # [B, H, hd]
    return _append_row_q(pool, scale, page_table, pos, tok)


def paged_append_window(pool: jax.Array, page_table: jax.Array,
                        pos: jax.Array, new: jax.Array, *, layout: str,
                        cow_src: Optional[jax.Array] = None,
                        cow_dst: Optional[jax.Array] = None) -> jax.Array:
    """Scatter a W-token verify window per slot into its pages.

    The speculative-decoding sibling of ``paged_append``: ``new`` carries
    ``W = k + 1`` rows per slot ([B, W, H, hd] "bshd" / [B, H, W, hd]
    "bhsd") written at absolute positions ``pos[b] .. pos[b] + W - 1``.
    The same NULL routing applies per row — any row at/past the table
    extent (or a negative position: an inactive slot parked at ``pos=-1``)
    lands in the sacrificial page — so a verify window that overruns a
    slot's capacity degrades into sink writes instead of corrupting live
    K/V.  A window may straddle a page boundary; each row resolves its own
    physical page, so no alignment between ``pos`` and the page grid is
    required.  COW pairs behave exactly as in ``paged_append`` (the
    engine's pre-scan already swapped the table entry to ``cow_dst``).

    The rows past the accepted prefix are STALE after acceptance: the
    engine rolls the slot's extent back (``rollback_extent``) and later
    writes overwrite them; reads in between are masked by ``lengths``.
    """
    page_size = pool.shape[2]
    b = page_table.shape[0]
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
    win = to_seq_major(new, layout)                        # [B, W, H, hd]
    w = win.shape[1]
    extent = page_table.shape[1] * page_size
    p = pos[:, None] + jnp.arange(w)[None, :]              # [B, W]
    in_range = jnp.logical_and(p >= 0, p < extent)
    pc = jnp.clip(p, 0, extent - 1)
    phys = jnp.where(
        in_range,
        page_table[jnp.arange(b)[:, None], pc // page_size],
        NULL_PAGE)                                         # [B, W]
    return pool.at[phys, :, pc % page_size].set(win.astype(pool.dtype))


def paged_append_window_q(pool: jax.Array, scale: jax.Array,
                          page_table: jax.Array, pos: jax.Array,
                          new: jax.Array, *, layout: str,
                          cow_src: Optional[jax.Array] = None,
                          cow_dst: Optional[jax.Array] = None,
                          ) -> Tuple[jax.Array, jax.Array]:
    """Quantized twin of ``paged_append_window``: the W verify rows are
    appended sequentially through the single-row quantize-on-write core
    (W is small and static), so a window that grows its page's scale
    re-encodes earlier rows exactly as single-token decode would."""
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
        scale = cow_copy_pool(scale, cow_src, cow_dst)
    win = to_seq_major(new, layout)                        # [B, W, H, hd]
    for i in range(win.shape[1]):
        pool, scale = _append_row_q(pool, scale, page_table, pos + i,
                                    win[:, i])
    return pool, scale


def place_chunk_pages_q(pool: jax.Array, scale: jax.Array, seq: jax.Array,
                        chunk_pages: jax.Array, *, layout: str,
                        cow_src: Optional[jax.Array] = None,
                        cow_dst: Optional[jax.Array] = None,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Quantized twin of ``place_chunk_pages``: whole pages are encoded at
    scales computed from their own content (``amax/qmax`` per page per kv
    head) — chunk placement always overwrites whole pages, so the scale
    is SET, not folded; later decode appends into a partial last page go
    through the monotone ``paged_append_q`` update."""
    page_size = pool.shape[2]
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
        scale = cow_copy_pool(scale, cow_src, cow_dst)
    chunks = to_pages(to_seq_major(seq, layout)[0],
                      page_size)                           # [n_cp, H, ps, hd]
    qmax = kv_quant_qmax(pool.dtype)
    amax = jnp.max(jnp.abs(chunks.astype(jnp.float32)),
                   axis=(2, 3))                            # [n_cp, H]
    new = amax / qmax
    codes = quantize_kv(chunks, new[:, :, None, None], pool.dtype)
    return (pool.at[chunk_pages].set(codes),
            scale.at[chunk_pages].set(new))


def gather_pages_dequant(pool: jax.Array, scale: jax.Array,
                         page_table: jax.Array, *,
                         layout: str) -> jax.Array:
    """Quantized twin of ``gather_pages``: materialize dense f32 K/V by
    dequantizing each gathered page with its per-(page, head) scale —
    the eager reference the quantized Pallas kernels must match."""
    pages = pool[page_table].astype(jnp.float32)  # [B, n, H, ps, hd]
    s = scale[page_table]                         # [B, n, H]
    return from_pages(pages * s[..., None, None], layout)


def live_page_table(page_table: jax.Array, lengths, page_size: int
                    ) -> jax.Array:
    """Re-route table entries wholly past the live prefix to the NULL page.

    page_table: [max_pages] (one slot) or [B, max_pages]; lengths: the
    matching scalar or [B] valid-token counts (may be traced).  Bounds KV
    traffic for the gather paths the same way the offset flash kernel's
    index-map clamp bounds its DMA: a gather through the clamped table
    touches O(live prefix) distinct pages — the dead tail all reads the
    one (cache-resident) NULL page — and correctness is unchanged because
    every consumer already masks scores at the valid length.
    """
    live = (jnp.asarray(lengths) + page_size - 1) // page_size
    idx = jnp.arange(page_table.shape[-1])
    if page_table.ndim == 2:
        mask = idx[None] < jnp.reshape(live, (-1, 1))
    else:
        mask = idx < live
    return jnp.where(mask, page_table, NULL_PAGE)


def gather_pages(pool: jax.Array, page_table: jax.Array, *,
                 layout: str) -> jax.Array:
    """Materialize per-slot contiguous K/V from the pool (reference path).

    pool: [P, H, page_size, hd] -> [B, max_pages*page_size, H, hd]
    ("bshd") or [B, H, S, hd] ("bhsd").  Entries past a slot's length read
    whatever its (or the NULL) pages hold; callers mask by length exactly
    as with the contiguous cache.
    """
    return from_pages(pool[page_table], layout)


def place_prefill(cache: Tree, fresh: Tree, slot: jax.Array,
                  pages: jax.Array, *, layout: str) -> Tree:
    """Write one request's prefill cache into the paged pools.

    ``fresh`` is a batch-1 prefill cache ([G, 1, ...] leaves).  K/V leaves
    are chunked into pages and scattered to the physical ``pages`` of this
    slot; state leaves replace the slot row.  Runs inside a donated jit —
    both scatters update in place.

    Quantized pools carry ``*_scale`` siblings the fresh (full-precision)
    prefill cache does not have, so the walk is over the parallel dict
    structures rather than a ``tree_map``: each K/V leaf's pages are
    encoded at their own per-(page, head) scales and the sibling scale
    pool rows are written in the same pass (freshly ``ensure``d pages —
    the scale is set, never folded).
    """
    page_size = None
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            page_size = leaf.shape[3]
            break

    def place_dict(cd: dict, fd: dict) -> dict:
        out = dict(cd)
        for name, small in fd.items():
            kind = cache_leaf_kind(name)
            pool = cd[name]
            if kind == "state":
                out[name] = pool.at[:, slot].set(
                    small[:, 0].astype(pool.dtype))
                continue
            seq = to_seq_major(small, layout)[:, 0]        # [G, S, H, hd]
            pad = pages.shape[0] * page_size - seq.shape[1]
            if pad:
                seq = jnp.pad(seq, ((0, 0), (0, pad), (0, 0), (0, 0)))
            chunks = to_pages(seq, page_size)              # [G, n, H, ps, hd]
            sname = name + "_scale"
            if sname in cd:
                qmax = kv_quant_qmax(pool.dtype)
                amax = jnp.max(jnp.abs(chunks.astype(jnp.float32)),
                               axis=(3, 4))                # [G, n, H]
                new = amax / qmax
                codes = quantize_kv(chunks, new[..., None, None],
                                    pool.dtype)
                out[name] = pool.at[:, pages].set(codes)
                out[sname] = cd[sname].at[:, pages].set(new)
            else:
                out[name] = pool.at[:, pages].set(chunks.astype(pool.dtype))
        return out

    return {
        "blocks": tuple(place_dict(c, f) for c, f
                        in zip(cache["blocks"], fresh["blocks"])),
        "rest": tuple(place_dict(c, f) for c, f
                      in zip(cache["rest"], fresh["rest"])),
    }


def place_chunk_pages(pool: jax.Array, seq: jax.Array,
                      chunk_pages: jax.Array, *, layout: str,
                      cow_src: Optional[jax.Array] = None,
                      cow_dst: Optional[jax.Array] = None) -> jax.Array:
    """Page-aligned incremental prefill placement: write ONE chunk's K/V
    into its physical pages.

    pool: [P, H, page_size, hd]; seq: a batch-1 chunk [1, C, H, hd]
    ("bshd") or [1, H, C, hd] ("bhsd"); chunk_pages: [C // page_size]
    int32 physical page ids for the chunk's logical pages.  The chunk size
    is a whole multiple of the page size by construction (the engine
    aligns the chunk grid to the page grid), so the write is a whole-page
    scatter — no read-modify-write of partially-filled pages.  Entries of
    ``chunk_pages`` past the slot's capacity carry the NULL page and land
    in the sacrificial page (pad tokens of the final chunk).  Runs inside
    a donated jit: the scatter updates the pool in place.

    Copy-on-write path: when the chunk's span includes a page the slot
    claimed from the prefix cache rather than allocating fresh (a prompt
    whose divergence point sits mid-page), pass scalar ``cow_src`` /
    ``cow_dst``: the shared page is copied onto the private ``cow_dst``
    page before the chunk scatter (``NULL_PAGE`` pair no-ops), keeping
    the state machine uniform — a shared page is never a scatter target;
    ``chunk_pages`` must already carry ``cow_dst``.
    """
    page_size = pool.shape[2]
    if cow_src is not None:
        pool = cow_copy_pool(pool, cow_src, cow_dst)
    chunks = to_pages(to_seq_major(seq, layout)[0], page_size)
    return pool.at[chunk_pages].set(chunks.astype(pool.dtype))


def stage_chunk(prompt: np.ndarray, off: int, chunk: int,
                row: np.ndarray, page_size: int):
    """Host-side staging of one prefill chunk for ``prefill_chunk``.

    prompt: [S] tokens; off: chunk start — any PAGE-aligned offset (the
    prefix cache resumes prefill at the first non-cached page, which
    need not sit on the chunk grid); row:
    the slot's page-table row (after ``ensure``); returns ``(tokens
    [chunk] zero-padded past the prompt, chunk_pages [chunk // page_size]
    physical ids with NULL past the table extent, last_idx)`` where
    ``last_idx`` is the within-chunk index of the prompt's final real
    token (clamped; only meaningful on the final chunk).  Shared by the
    engine and the tests so the staging contract lives in one place.
    """
    n_cp = chunk // page_size
    j0 = off // page_size
    cpages = np.full(n_cp, NULL_PAGE, np.int32)
    n = max(0, min(n_cp, int(row.shape[0]) - j0))
    cpages[:n] = row[j0:j0 + n]
    toks = np.zeros(chunk, np.int32)
    seg = prompt[off:off + chunk]
    toks[:len(seg)] = seg
    last = min(int(prompt.shape[0]) - 1 - off, chunk - 1)
    return toks, cpages, last


# --------------------------------------------------------------------- #
# Pool construction
# --------------------------------------------------------------------- #

def paged_cache_defs(cfg: ModelConfig, slots: int, max_len: int,
                     page_size: int) -> Tree:
    """Cache definition tree with K/V leaves replaced by page pools.

    Under a KV ``QuantMode`` each K/V pool stores int8 / fp8 codes and
    gains a sibling ``<name>_scale`` leaf ``[G, num_pages, Hkv]`` f32 —
    one scale per (physical page, kv head), indexed by the same page ids
    as the pool (DESIGN.md §14).
    """
    num_pages = 1 + slots * cdiv(max_len, page_size)       # +1: NULL page
    hkv, hd = cfg.num_kv_heads, cfg.head_dim_
    qdtype = kv_quant_dtype(cfg.kv_quant)

    def group_defs(defs: dict) -> dict:
        out = {}
        for name, cd in defs.items():
            if cache_leaf_kind(name) == "state":
                out[name] = cd
                continue
            groups = cd.shape[0]
            out[name] = CacheDef(
                (groups, num_pages, hkv, page_size, hd),
                ("layers", "kv_pages", "kv_heads", None, None),
                qdtype if qdtype is not None else cd.dtype)
            if qdtype is not None:
                out[name + "_scale"] = CacheDef(
                    (groups, num_pages, hkv),
                    ("layers", "kv_pages", "kv_heads"), jnp.float32)
        return out

    base = cache_defs(cfg, slots, max_len)
    return {"blocks": tuple(group_defs(d) for d in base["blocks"]),
            "rest": tuple(group_defs(d) for d in base["rest"])}


class PagedKVCache:
    """Device page pools + page table + host-side refcounted allocator.

    The device state (``cache`` pytree, ``page_table``) flows through the
    engine's donated dispatches; this object owns the *allocation* state:
    which physical pages belong to which slot, and which are free.  The
    page table itself is kept as host numpy (tiny) and re-uploaded per
    dispatch — allocation happens between dispatches, never inside jit.

    Ownership is refcounted so the prefix cache can point several slots
    at one physical page (DESIGN.md §10).  Page states:

      * **free** — on ``_free``, ``refs == 0``, not tree-owned.
      * **referenced** — ``refs`` = number of slots whose table rows
        carry the page.  ``ensure`` allocates exclusively (``refs = 1``);
        ``adopt_shared`` claims an existing page (``refs += 1``).
      * **cached** — ``refs == 0`` but owned by the prefix tree
        (``mark_tree``): the page keeps its K/V after every referencing
        slot exited, and is reclaimed through ``evict_page`` (driven by
        the ``evictor`` hook when the free list runs dry).

    ``release`` moves a slot's references down exactly once: a page drops
    to the free list only when its refcount hits zero AND the tree does
    not own it — a shared or cached page can therefore never be
    double-freed, and ``assert_page_accounting`` verifies the partition
    (every physical page in exactly one state, the free list duplicate-
    free, refcounts equal to actual table occupancy).

    Bytes accounting counts a shared page ONCE: ``pages_in_use`` is the
    number of *distinct* referenced pages, so the paged-memory metrics
    (and the per-shard split under a mesh) report physical truth.
    """

    def __init__(self, cfg: ModelConfig, *, slots: int, max_len: int,
                 page_size: int = 16, mesh=None, obs=NULL_RECORDER):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg = cfg
        # Telemetry recorder (obs/events.py): page lifecycle instants on
        # the "kv" track.  NULL_RECORDER no-ops; emission sites guard on
        # ``enabled`` so the disabled path never builds argument dicts.
        self.obs = obs
        self.slots = slots
        self.max_len = max_len
        self.page_size = min(page_size, max_len)
        self.pages_per_slot = cdiv(max_len, self.page_size)
        self.num_pages = 1 + slots * self.pages_per_slot
        self._defs = paged_cache_defs(cfg, slots, max_len, self.page_size)
        # Mesh-aware pool layout (DESIGN.md §9): K/V pools shard over the
        # model axis at their ``kv_heads`` dim — resolved through the same
        # logical-axis rules as the parameters, so a head count that does
        # not divide falls back to replication.  The page table (and the
        # slot-contiguous state leaves) stay replicated: every shard
        # resolves the same logical->physical page indirection and only
        # streams its own heads' slice of each page.
        self.mesh = mesh
        self.kv_shards = 1
        self._shardings: Optional[Tree] = None
        if mesh is not None:
            from ..distributed.sharding import spec_for

            def leaf_sharding(path, cd):
                # Scale pools shard alongside their value pools (both
                # carry a ``kv_heads`` logical axis); state stays
                # replicated.
                if cache_leaf_kind(cache_leaf_name(path)) \
                        not in ("kv", "scale"):
                    return NamedSharding(mesh, P())
                return NamedSharding(
                    mesh, spec_for(cfg, cd.axes, cd.shape, mesh))

            self._shardings = jax.tree_util.tree_map_with_path(
                leaf_sharding, self._defs,
                is_leaf=lambda x: isinstance(x, CacheDef))
            def claims_model(spec) -> bool:
                return any(e == "model"
                           or (isinstance(e, tuple) and "model" in e)
                           for e in spec)

            for s in jax.tree.leaves(
                    self._shardings,
                    is_leaf=lambda x: isinstance(x, NamedSharding)):
                if claims_model(s.spec):
                    self.kv_shards = int(mesh.shape["model"])
                    break
        # Bytes of ONE physical page summed over every page-indexed pool
        # leaf (all layer groups) — the unit of the bytes-in-use
        # accounting.  Computed from each pool's ACTUAL dtype, not an
        # assumed uniform compute dtype: quantized value pools count at
        # the int8/fp8 itemsize and the f32 scale pools count too, so
        # ``bytes_in_use``/``peak_bytes_per_shard`` report physical truth
        # across quant modes.  Every leaf with a ``kv_pages`` axis (dim 1)
        # contributes ``elems / num_pages * itemsize``.
        self.page_bytes = 0
        self._kv_elems_per_page = 0
        for path, cd in jax.tree_util.tree_flatten_with_path(
                self._defs, is_leaf=lambda x: isinstance(x, CacheDef))[0]:
            kind = cache_leaf_kind(cache_leaf_name(path))
            if kind not in ("kv", "scale"):
                continue
            per_page = int(np.prod(cd.shape)) // cd.shape[1]
            self.page_bytes += per_page * jnp.dtype(cd.dtype).itemsize
            if kind == "kv":
                self._kv_elems_per_page += per_page
        self._table = np.zeros((slots, self.pages_per_slot), np.int32)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._owned: List[List[int]] = [[] for _ in range(slots)]
        # Refcounts (slot references per physical page) + the set of
        # pages the prefix tree owns (kept out of the free list at ref 0).
        self._refs = np.zeros(self.num_pages, np.int64)
        self._in_use = 0            # distinct pages with refs > 0,
        #                             maintained on 0<->1 transitions
        self._tree: set = set()
        # Called when the free list runs dry: must reclaim >= 1 cached
        # page (via ``evict_page``) and return True, or return False.
        self.evictor: Optional[Callable[[], bool]] = None
        self.peak_pages = 0

    def init_cache(self) -> Tree:
        """Fresh device cache tree (paged pools + slot-contiguous state).
        The engine owns it from here: it is donated through every dispatch
        and this object only tracks which pages are whose.  With a mesh,
        every leaf is placed under its ``NamedSharding`` (K/V pools
        ``kv_heads``-sharded over 'model', the rest replicated)."""
        if self._shardings is None:
            return jax.tree.map(
                lambda cd: jnp.zeros(cd.shape, cd.dtype), self._defs,
                is_leaf=lambda x: isinstance(x, CacheDef))
        return jax.tree.map(
            lambda cd, ns: jax.device_put(jnp.zeros(cd.shape, cd.dtype), ns),
            self._defs, self._shardings,
            is_leaf=lambda x: isinstance(x, CacheDef))

    # ------------------------------------------------------------ state
    @property
    def page_table(self) -> jax.Array:
        t = jnp.asarray(self._table)
        if self.mesh is not None:
            t = jax.device_put(t, NamedSharding(self.mesh, P(None, None)))
        return t

    @property
    def pages_in_use(self) -> int:
        """Distinct physical pages referenced by slots — a page shared by
        k slots counts ONCE (it exists once in the pools).  Maintained
        incrementally on refcount 0<->1 transitions (the allocation hot
        path reads it per page via the peak update)."""
        return self._in_use

    def _ref(self, page: int) -> None:
        if self._refs[page] == 0:
            self._in_use += 1
        self._refs[page] += 1

    def _deref(self, page: int) -> None:
        self._refs[page] -= 1
        assert self._refs[page] >= 0, f"double release of page {page}"
        if self._refs[page] == 0:
            self._in_use -= 1
            if page not in self._tree:
                self.free_page(page)

    @property
    def pages_cached(self) -> int:
        """Tree-owned pages no slot references: K/V kept warm for future
        prefix hits, reclaimable by eviction."""
        return sum(1 for p in self._tree if self._refs[p] == 0)

    @property
    def bytes_in_use(self) -> int:
        return self.pages_in_use * self.page_bytes

    @property
    def bytes_cached(self) -> int:
        return self.pages_cached * self.page_bytes

    @property
    def peak_bytes_in_use(self) -> int:
        return self.peak_pages * self.page_bytes

    @property
    def peak_bytes_per_shard(self) -> int:
        """Per-device peak K/V bytes: the pools split over ``kv_shards``
        (the 'model' factor the kv_heads dim actually claimed)."""
        return self.peak_bytes_in_use // self.kv_shards

    @property
    def kv_itemsize_effective(self) -> float:
        """Stored bytes per K/V element, scale-pool overhead amortized in
        (e.g. bf16 -> 2.0; int8 with per-page-per-head f32 scales ->
        slightly above 1.0).  Self-describing unit for cross-quant-mode
        bytes comparisons in the metrics and benchmarks."""
        return self.page_bytes / self._kv_elems_per_page

    def slot_pages(self, slot: int) -> np.ndarray:
        return np.asarray(self._owned[slot], np.int32)

    def table_row(self, slot: int) -> np.ndarray:
        """One slot's logical->physical page map (unallocated: NULL)."""
        return self._table[slot].copy()

    @property
    def extent(self) -> int:
        """Positions addressable through the table (>= max_len; a write at
        or past this routes to the NULL page in ``paged_append``)."""
        return self.pages_per_slot * self.page_size

    def page_refs(self, page: int) -> int:
        return int(self._refs[page])

    # ------------------------------------------------------- allocation
    def alloc_page(self) -> int:
        """Pop one free page, evicting cached prefix pages through the
        ``evictor`` hook when the free list is dry.  Raises when every
        page is referenced — steady-state demand fits the pool (per-slot
        demand caps at ``pages_per_slot`` and sharing only lowers it),
        but a copy-on-write needs ONE transient extra page while both
        src and dst are live, so a fully-referenced pool can legally
        fail here; callers on the serving path catch and fail the one
        request instead of the stream."""
        while not self._free:
            if self.evictor is None or not self.evictor():
                raise RuntimeError(
                    f"KV page pool exhausted ({self.num_pages - 1} pages)")
        page = self._free.pop()
        if self.obs.enabled:
            self.obs.instant(PAGE_ALLOC, track=TRACK_KV, page=page,
                             free=len(self._free))
        return page

    def free_page(self, page: int) -> None:
        assert self._refs[page] == 0 and page != NULL_PAGE
        self._free.append(page)
        if self.obs.enabled:
            self.obs.instant(PAGE_FREE, track=TRACK_KV, page=page,
                             free=len(self._free))

    def ensure(self, slot: int, length: int) -> np.ndarray:
        """Allocate pages so ``slot`` can hold ``length`` tokens; returns
        the slot's physical pages.  ``length`` beyond ``max_len`` raises —
        the pool is sized for ``slots * max_len`` exactly.  Logical pages
        already populated (freshly allocated earlier, or prefix-shared
        via ``adopt_shared``) are kept; only the tail is allocated."""
        if length > self.max_len:
            raise ValueError(
                f"cannot ensure {length} tokens: slot capacity is "
                f"max_len={self.max_len}")
        need = cdiv(max(length, 1), self.page_size)
        owned = self._owned[slot]
        while len(owned) < need:
            page = self.alloc_page()
            self._ref(page)
            self._table[slot, len(owned)] = page
            owned.append(page)
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        return self.slot_pages(slot)

    def adopt_shared(self, slot: int, page: int) -> int:
        """Claim an existing (tree-cached or other-slot) page as this
        slot's next logical page: bump its refcount and write the shared
        physical id straight into the slot's table row.  Returns the
        logical index.  Prefix pages are adopted in walk order BEFORE any
        ``ensure`` so logical order matches token order."""
        owned = self._owned[slot]
        logical = len(owned)
        self._ref(page)
        self._table[slot, logical] = page
        owned.append(page)
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        return logical

    def cow_page(self, slot: int, logical: int) -> Tuple[int, int]:
        """Copy-on-write swap: replace the slot's shared logical page
        with a fresh exclusive one.  Returns ``(src, dst)`` physical ids
        for the in-jit page copy (``cow_src``/``cow_dst`` operands); the
        host table row already points at ``dst`` when this returns.  The
        slot's reference MOVES: ``src`` drops one ref (staying cached if
        the tree owns it), ``dst`` starts at one."""
        src = self._owned[slot][logical]
        dst = self.alloc_page()
        self._ref(dst)
        self._deref(src)               # stays cached when tree-owned
        self._owned[slot][logical] = dst
        self._table[slot, logical] = dst
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        if self.obs.enabled:
            self.obs.instant(PAGE_COW, track=TRACK_KV, slot=slot,
                             src=src, dst=dst, logical=logical)
        return src, dst

    def rollback_extent(self, slot: int, length: int) -> int:
        """Truncate a slot's extent to ``length`` tokens after a rejected
        speculative draft, releasing the freshly-appended tail pages
        exactly once.  Returns the number of pages released.

        Only pages WHOLLY past ``length`` are dropped; a partial last page
        is kept (its stale tail rows are masked by the slot's length and
        overwritten by later appends).  The engine never rolls back below
        the prompt — draft rows are appended strictly after the prefill
        extent — so every truncated page was allocated exclusively for
        draft K/V this pass.  That invariant is ASSERTED here rather than
        assumed: a truncated page must be exclusively owned (``refs == 1``)
        and not tree-owned, i.e. the prefix cache can never lose a shared
        or cached page to a rollback, and a rolled-back partial page can
        never have been adopted into the radix tree (``PrefixCache.insert``
        only indexes full prompt pages, which rollback never touches).
        """
        keep = cdiv(max(length, 1), self.page_size)
        owned = self._owned[slot]
        dropped = 0
        while len(owned) > keep:
            page = owned[-1]
            # Check BEFORE popping: a refused rollback must leave the
            # allocator untouched, not half-truncated.
            assert self._refs[page] == 1 and page not in self._tree, \
                (f"rollback of slot {slot} would release page {page} "
                 f"(refs={int(self._refs[page])}, "
                 f"tree={page in self._tree}) — draft pages must be "
                 f"exclusive and never tree-adopted")
            owned.pop()
            self._table[slot, len(owned)] = NULL_PAGE
            self._deref(page)
            dropped += 1
        if dropped and self.obs.enabled:
            self.obs.instant(PAGE_ROLLBACK, track=TRACK_KV, slot=slot,
                             pages=dropped, length=length)
        return dropped

    # ------------------------------------------------- tree page custody
    def mark_tree(self, page: int) -> None:
        """Hand custody of a page to the prefix tree: at refcount zero it
        stays CACHED (not freed) until ``evict_page`` reclaims it."""
        self._tree.add(page)

    def evict_page(self, page: int) -> None:
        """Tree eviction: reclaim a cached (ref-0, tree-owned) page."""
        assert page in self._tree and self._refs[page] == 0
        self._tree.discard(page)
        if self.obs.enabled:
            self.obs.instant(PAGE_EVICT, track=TRACK_KV, page=page)
        self.free_page(page)

    def disown(self, page: int) -> None:
        """Revoke tree custody WITHOUT freeing: a pruned subtree's
        still-referenced pages keep serving their slots and return to
        the free list normally when the last reference drops."""
        self._tree.discard(page)

    def release(self, slot: int) -> None:
        """Drop a finished slot's page references exactly once and point
        its table row back at the NULL page.  Exclusive pages return to
        the free list; shared pages just lose one reference; tree-owned
        pages stay cached at refcount zero (the prefix tree keeps their
        K/V warm until memory pressure evicts them).  Idempotent: a
        second release of the same slot is a no-op (``_owned`` already
        empty), so an engine error path can never double-free."""
        for page in reversed(self._owned[slot]):
            self._deref(page)
        self._owned[slot] = []
        self._table[slot, :] = NULL_PAGE

    # ------------------------------------------------------- invariants
    def assert_page_accounting(self, cache: Optional[Tree] = None) -> None:
        """Free-list / refcount / tree partition invariant (used by the
        churn tests and the engine's debug hooks).

        Every physical page (except NULL) is in exactly one state:
        free, referenced (refs > 0), or cached (tree-owned at refs 0);
        the free list holds no duplicates and nothing referenced or
        tree-owned; refcounts equal actual slot-table occupancy.

        Under a KV quant mode, additionally cross-checks that the value
        and scale pools stay in LOCKSTEP: every K/V leaf has a
        ``<name>_scale`` sibling indexed by the same physical page axis
        (``[G, num_pages, Hkv]`` f32) — and, when the live device
        ``cache`` tree is passed, that its leaves match the definitions.
        Since every page mutation (append, chunk placement, COW copy,
        prefill placement) goes through paired pool+scale primitives
        addressed by one shared page id, shape lockstep plus the single
        allocator are what make a value page and its scale row move
        together."""
        free = list(self._free)
        free_set = set(free)
        assert len(free) == len(free_set), "free list holds duplicates"
        assert NULL_PAGE not in free_set, "NULL page on the free list"
        counts = np.zeros(self.num_pages, np.int64)
        for owned in self._owned:
            for page in owned:
                counts[page] += 1
        assert np.array_equal(counts, self._refs), \
            "refcounts disagree with slot ownership"
        referenced = {p for p in range(1, self.num_pages)
                      if self._refs[p] > 0}
        assert self._in_use == len(referenced), \
            "incremental in-use counter drifted"
        cached = {p for p in self._tree if self._refs[p] == 0}
        assert not (free_set & referenced), "referenced page on free list"
        assert not (free_set & cached), "cached page on free list"
        assert free_set | referenced | cached \
            == set(range(1, self.num_pages)), "page leaked (no state)"
        # Table rows mirror ownership: owned prefix, NULL beyond.
        for slot, owned in enumerate(self._owned):
            assert list(self._table[slot, :len(owned)]) == owned
            assert np.all(self._table[slot, len(owned):] == NULL_PAGE)
        # Value/scale pool lockstep (DESIGN.md §14).
        quant = self.cfg.kv_quant is not None
        for group in self._defs["blocks"] + self._defs["rest"]:
            for name, cd in group.items():
                if cache_leaf_kind(name) != "kv":
                    continue
                sname = name + "_scale"
                if not quant:
                    assert sname not in group, \
                        f"unexpected scale pool {sname} without kv quant"
                    continue
                assert sname in group, f"missing scale pool {sname}"
                scd = group[sname]
                assert scd.shape == cd.shape[:3], \
                    (f"{sname} shape {scd.shape} out of lockstep with "
                     f"{name} {cd.shape}")
                assert jnp.dtype(scd.dtype) == jnp.float32
                assert jnp.dtype(cd.dtype) == jnp.dtype(
                    kv_quant_dtype(self.cfg.kv_quant))
        if cache is not None:
            flat_c = jax.tree_util.tree_flatten_with_path(cache)[0]
            flat_d = jax.tree_util.tree_flatten_with_path(
                self._defs, is_leaf=lambda x: isinstance(x, CacheDef))[0]
            assert len(flat_c) == len(flat_d), \
                "device cache structure out of lockstep with definitions"
            for (pc, leaf), (pd, cd) in zip(flat_c, flat_d):
                assert pc == pd and leaf.shape == cd.shape \
                    and jnp.dtype(leaf.dtype) == jnp.dtype(cd.dtype), \
                    (f"device leaf {pc} {leaf.shape}/{leaf.dtype} vs def "
                     f"{pd} {cd.shape}/{cd.dtype}")
