"""Model layers in pure JAX (functions over param pytrees).

Design notes (see DESIGN.md §7):
  * Attention is implemented in its *streaming* form — a ``lax.scan`` over KV
    chunks with a running (max, sum, acc) softmax — which is the TPU-native
    twin of the paper's stream-based dataflow: the score matrix is never
    materialized, intermediates stay in fast memory, and the same chunk loop
    is what the Pallas flash kernel implements at the BlockSpec level.
  * GQA is expressed by grouping query heads over KV heads (no KV repeat
    materialization).
  * Sliding-window layers use the two-chunk trick (chunk == window) so local
    attention is O(S * w).
  * Mamba2 uses the chunked SSD algorithm (parallel intra-chunk, scanned
    inter-chunk); RWKV6 uses a ``lax.scan`` linear recurrence with
    data-dependent diagonal decay.  Both have single-step decode forms.

All functions take/return plain jnp arrays; parameters are dicts produced by
``params.py``.  Compute dtype is the caller's; accumulation in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]

NEG_INF = -1e30

# Trace-time dispatch records (mesh-aware StreamPlan, DESIGN.md §9): each
# fused wrapper bumps "shard_map" when it dispatched its kernel under
# shard_map and "single" when it ran single-device — the probe the sharded
# serving tests use to assert the fused path really went multi-device
# (counts PROGRAMS TRACED, not calls, like the engine's trace probes).
DISPATCH_RECORDS: Dict[str, int] = {"shard_map": 0, "single": 0}


def reset_dispatch_records() -> None:
    DISPATCH_RECORDS["shard_map"] = 0
    DISPATCH_RECORDS["single"] = 0


# --------------------------------------------------------------------- #
# Dispatch effect signatures (static analysis, DESIGN.md §15)
# --------------------------------------------------------------------- #
# Declarative read/write effects of the serving engine's jitted
# dispatches over their DONATED buffers — the facts the alias & donation
# checker (analysis/effects.py) verifies without tracing anything.  One
# entry per compiled dispatch; ops appear in program order.  Op fields:
#
#   reads          — buffers read wherever they currently are (in-place
#                    scatter/gather semantics; safe after earlier writes).
#   reads_initial  — buffers whose PRE-DISPATCH state the op needs; a
#                    read-after-write on a donated buffer here is a bug.
#   writes         — buffers the op updates in place (donation makes
#                    these true aliases of the caller's arrays).
#   page_indexed   — the write scatters through the page table; such
#                    ops MUST set null_routed (masked writes land on the
#                    sacrificial NULL page, kv_cache.NULL_PAGE) and,
#                    under a KV QuantMode, updates_scales (the per-page
#                    scale twin updates in lockstep with the codes).
#   cow            — copy-on-write step: duplicates pool page ``src``
#                    onto ``dst`` before any scatter.  ``fresh_dst``
#                    declares the allocator invariant that dst is a
#                    freshly-allocated private page (never aliasing src
#                    unless both are NULL) — without it a shared page
#                    could be overwritten in place.
#
# The declarations mirror serving/engine.py (_prefill / _decode /
# _verify / _prefill_chunk) and models/model.py; keep them in sync when
# a dispatch gains an operand.
DISPATCH_EFFECTS: Dict[str, Dict[str, Any]] = {
    "prefill": {
        "donated": ("slot_cache",),
        "ops": (
            {"name": "model_prefill", "reads": ("params", "tokens"),
             "writes": ("fresh",)},
            {"name": "place_prefill", "reads": ("fresh", "pages"),
             "writes": ("slot_cache",), "page_indexed": True,
             "null_routed": True, "updates_scales": True},
        ),
    },
    "prefill_chunk": {
        "donated": ("slot_cache",),
        "ops": (
            {"name": "cow_copy",
             "reads_initial": ("slot_cache",), "writes": ("slot_cache",),
             "page_indexed": True, "null_routed": True,
             "updates_scales": True,
             "cow": {"src": "cow_src", "dst": "cow_dst",
                     "fresh_dst": True}},
            {"name": "chunk_scatter",
             "reads": ("params", "tokens", "table_row", "chunk_pages",
                       "slot_cache"),
             "writes": ("slot_cache",), "page_indexed": True,
             "null_routed": True, "updates_scales": True},
        ),
    },
    "decode": {
        "donated": ("cache",),
        "ops": (
            {"name": "cow_copy",
             "reads_initial": ("cache",), "writes": ("cache",),
             "page_indexed": True, "null_routed": True,
             "updates_scales": True,
             "cow": {"src": "cow_src", "dst": "cow_dst",
                     "fresh_dst": True}},
            {"name": "decode_scan",
             "reads": ("params", "tok", "cache", "table"),
             "writes": ("cache",), "page_indexed": True,
             "null_routed": True, "updates_scales": True},
        ),
    },
    "verify": {
        "donated": ("cache",),
        "ops": (
            {"name": "cow_copy",
             "reads_initial": ("cache",), "writes": ("cache",),
             "page_indexed": True, "null_routed": True,
             "updates_scales": True,
             "cow": {"src": "cow_src", "dst": "cow_dst",
                     "fresh_dst": True}},
            {"name": "verify_window",
             "reads": ("params", "toks", "cache", "table"),
             "writes": ("cache",), "page_indexed": True,
             "null_routed": True, "updates_scales": True},
        ),
    },
}


def _shard_mesh(shard):
    """The active mesh for a plan sharding claim (None = single-device).

    The claim comes from the StreamPlan (``KernelChoice.sharding``); the
    mesh comes from the ``distributed.context`` the engine / step builder
    installed around tracing.  Either absent -> plain dispatch.
    """
    if not shard:
        return None
    from ..distributed.context import current_mesh   # lazy: no core->dist cycle
    return current_mesh()


def _claim_axis(mesh, shard, dim: str, extent: int):
    """Mesh axis (or axis group, e.g. ('pod', 'data')) the plan claimed
    for ``dim``, if the RUNTIME extent divides.  Plan-time claims check
    config-derived extents; batch/token extents are only known here.  A
    grouped claim degrades like ``spec_for``'s candidate chain — drop
    leading axes (('pod','data') -> ('data',)) before giving up — and an
    extent that divides nothing falls back to replication for that dim,
    never to eager."""
    ax = dict(shard).get(dim)
    if mesh is None or ax is None:
        return None
    axes = ax if isinstance(ax, tuple) else (ax,)
    if any(a not in mesh.axis_names for a in axes):
        return None
    for start in range(len(axes)):
        cand = axes[start:]
        size = 1
        for a in cand:
            size *= int(mesh.shape[a])
        if size > 1 and extent % size == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def _smap(fn, mesh, in_specs, out_specs):
    """shard_map a kernel dispatch and record it."""
    from ..distributed.context import shard_map
    DISPATCH_RECORDS["shard_map"] += 1
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


# --------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------- #

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def apply_norm(kind: str, x: jax.Array, p: Params) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


# --------------------------------------------------------------------- #
# Weight-only int8 (DESIGN.md §14)
# --------------------------------------------------------------------- #

def quantize_channelwise(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8: w [D, N] -> (codes int8, scales
    [N] f32 with scale = amax|col| / 127).  An all-zero column encodes to
    zero codes with scale 0 (dequant stays exact)."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0)
    scales = amax / 127.0
    safe = jnp.where(scales > 0.0, scales, 1.0)
    codes = jnp.clip(jnp.round(w32 / safe), -127.0, 127.0).astype(jnp.int8)
    return codes, scales


def dequantize_channelwise(codes: jax.Array, scales: jax.Array,
                           dtype) -> jax.Array:
    return (codes.astype(jnp.float32) * scales[None, :]).astype(dtype)


def _w8_ste(w: jax.Array) -> jax.Array:
    """Quantize-dequantize with a straight-through gradient: the forward
    value carries the int8 rounding (matching the fused w8 kernels bit for
    bit in the eager reference), the backward passes cotangents through as
    if ``w`` were untouched."""
    codes, scales = quantize_channelwise(w)
    wq = dequantize_channelwise(codes, scales, w.dtype)
    return w + lax.stop_gradient(wq - w)


# --------------------------------------------------------------------- #
# Rotary embeddings (RoPE and M-RoPE)
# --------------------------------------------------------------------- #

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] (int)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# M-RoPE (Qwen2-VL): the rotary half-dim is split into (temporal, height,
# width) sections, each rotated by its own position stream.
MROPE_SECTIONS = (2, 1, 1)   # fractions of the half-dim: t=1/2, h=1/4, w=1/4


def apply_mrope(x: jax.Array, positions: jax.Array,
                theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [3, B, S] (temporal, height, width)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                      # [half]
    total = sum(MROPE_SECTIONS)
    sizes = [half * s // total for s in MROPE_SECTIONS]
    sizes[-1] = half - sum(sizes[:-1])
    angle_parts = []
    start = 0
    for sec, size in enumerate(sizes):
        f = freqs[start:start + size]
        pos = positions[sec].astype(jnp.float32)                # [B,S]
        angle_parts.append(pos[..., None] * f)
        start += size
    angles = jnp.concatenate(angle_parts, axis=-1)              # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def apply_positional(kind: str, x: jax.Array, positions: jax.Array,
                     theta: float) -> jax.Array:
    if kind == "rope":
        return apply_rope(x, positions, theta)
    if kind == "mrope":
        return apply_mrope(x, positions, theta)
    return x


# --------------------------------------------------------------------- #
# Streaming (chunked / flash-style) attention
# --------------------------------------------------------------------- #

def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: [B,Sq,Kh,G,D], k: [B,C,Kh,D] -> scores [B,Kh,G,Sq,C] (f32)."""
    return jnp.einsum("bqhgd,bchd->bhgqc", q, k,
                      preferred_element_type=jnp.float32)


def streaming_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    q_offset: int = 0,
    window: int = 0,
    chunk_size: int = 1024,
    kv_len=None,
    scale: Optional[float] = None,
    remat_chunk: bool = False,
) -> jax.Array:
    """Chunked online-softmax attention.

    Args:
        q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D] with Hq % Hkv == 0.
        causal: apply causal masking with query positions q_offset + i.
        q_offset: absolute position of q[0] relative to k[0] (prefill: 0 when
            Sq == Skv; decode-style calls use full-cache helpers instead).
            May be a traced scalar (chunked prefill against a cache).
        window: sliding window size (0 = unlimited); causal only.
        chunk_size: KV tile length (the stream token granularity).
        kv_len: valid KV entries (default Skv); may be a traced scalar when
            K/V come from a partially-filled cache extent.
    Returns: [B, Sq, Hq, D].
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    kv_len = skv if kv_len is None else kv_len
    g = hq // hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = (q * sc).reshape(b, sq, hkv, g, d)

    c = min(chunk_size, skv)
    if skv % c != 0:  # pad KV up to a chunk multiple; padding masked off
        pad = c - skv % c
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = k.shape[1] // c
    kc = k.reshape(b, nc, c, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, c, hkv, d).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        m, l, acc = carry
        ci, (kb, vb) = inputs
        kv_pos = ci * c + jnp.arange(c)
        s = _gqa_scores(qg, kb)                       # [B,Kh,G,Sq,C]
        mask = kv_pos[None, :] <= q_pos[:, None] if causal else \
            jnp.ones((sq, c), dtype=bool)
        mask = jnp.logical_and(mask, kv_pos[None, :] < kv_len)
        if window:
            mask = jnp.logical_and(
                mask, kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # Explicitly zero masked lanes: for a fully-masked chunk both s and
        # m_new sit at NEG_INF and exp(s - m_new) would be exp(0) = 1.
        p = jnp.where(mask[None, None, None], jnp.exp(s - m_new[..., None]),
                      0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), dtype=jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), dtype=jnp.float32)
    # remat_chunk: recompute score tiles in the backward pass instead of
    # stacking per-chunk residuals across the scan (flash-attention-style
    # O(1) residency; §Perf gemma3 hillclimb).
    body = jax.checkpoint(step) if remat_chunk else step
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0),
                              (jnp.arange(nc), (kc, vc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d).astype(q.dtype)


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int, q_offset: int = 0,
                    remat_chunk: bool = False) -> jax.Array:
    """Sliding-window attention via the streaming kernel with chunk=window
    (each query chunk touches at most 2 KV chunks worth of live scores)."""
    return streaming_attention(q, k, v, causal=True, q_offset=q_offset,
                               window=window,
                               chunk_size=max(128, min(window, k.shape[1])),
                               remat_chunk=remat_chunk)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *,
                     window: int = 0, layout: str = "bshd") -> jax.Array:
    """One-token attention against a (possibly sequence-sharded) KV cache.

    q: [B, 1, Hq, D]; caches: [B, S, Hkv, D] ("bshd") or [B, Hkv, S, D]
    ("bhsd" — attention-native, §Perf I5c); cache_len: [] or [B] valid
    entries.  The softmax reduction over S lowers to a sharded reduce when
    S is sharded over the model axis (context-parallel decode).
    """
    b, _, hq, d = q.shape
    if layout == "bhsd":
        hkv, s = k_cache.shape[1], k_cache.shape[2]
    else:
        s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = (q * (1.0 / math.sqrt(d))).reshape(b, 1, hkv, g, d)
    k_eq = "bhsd" if layout == "bhsd" else "bshd"
    if layout == "bhsd":
        # Attention-native layout: the einsum consumes the cache directly
        # (no transpose copy).  Emit in the cache dtype — the MXU still
        # accumulates f32 per tile; softmax runs in f32 below.
        scores = jnp.einsum(f"bqhgd,{k_eq}->bhgqs", qg,
                            k_cache).astype(jnp.float32)
    else:
        scores = jnp.einsum(f"bqhgd,{k_eq}->bhgqs", qg, k_cache,
                            preferred_element_type=jnp.float32)
    pos = jnp.arange(s)
    valid = pos[None] < jnp.reshape(cache_len, (-1, 1))          # [B,S]
    if window:
        valid = jnp.logical_and(
            valid, pos[None] >= jnp.reshape(cache_len, (-1, 1)) - window)
    scores = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # PV stays in f32 (p uncast; the cache promotes): the paged decode
    # kernel folds pages through the same f32 online softmax, and the
    # plan-selectable paged path is required to match this one to 1e-5 —
    # a bf16 downcast of p here would round at a different scale than the
    # kernel's running (m, l) and break that contract.
    out = jnp.einsum(f"bhgqs,{k_eq}->bqhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def verify_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     q_off: jax.Array, *, window: int = 0,
                     layout: str = "bshd") -> jax.Array:
    """W-token speculative-verify attention (eager reference path).

    q: [B, W, Hq, D] — the pending token plus W-1 draft candidates;
    caches: [B, S, Hkv, D] ("bshd") or [B, Hkv, S, D] ("bhsd"); q_off:
    [B] absolute position of window row 0, so row i's causal extent is
    ``q_off + i + 1``.  The W-row twin of ``decode_attention`` under the
    same numerics contract: scores in f32, f32 softmax, f32 PV — row i
    computes exactly what ``decode_attention`` would at length
    ``q_off + i + 1`` (extra cache rows score exact NEG_INF and drop out
    of the softmax as exact zeros), which is what lets the engine accept
    draft tokens without perturbing the greedy stream.
    """
    b, w, hq, d = q.shape
    if layout == "bhsd":
        hkv, s = k_cache.shape[1], k_cache.shape[2]
    else:
        s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = (q * (1.0 / math.sqrt(d))).reshape(b, w, hkv, g, d)
    k_eq = "bhsd" if layout == "bhsd" else "bshd"
    if layout == "bhsd":
        scores = jnp.einsum(f"bqhgd,{k_eq}->bhgqs", qg,
                            k_cache).astype(jnp.float32)
    else:
        scores = jnp.einsum(f"bqhgd,{k_eq}->bhgqs", qg, k_cache,
                            preferred_element_type=jnp.float32)
    pos = jnp.arange(s)
    qlen = jnp.reshape(q_off, (-1, 1)) + jnp.arange(w)[None] + 1  # [B,W]
    valid = pos[None, None] < qlen[..., None]                     # [B,W,S]
    if window:
        valid = jnp.logical_and(valid, pos[None, None]
                                >= qlen[..., None] - window)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(f"bhgqs,{k_eq}->bqhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, w, hq, d).astype(q.dtype)


# --------------------------------------------------------------------- #
# FFN / MoE
# --------------------------------------------------------------------- #

def _act(kind: str, x: jax.Array) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)


def ffn(x: jax.Array, p: Params, *, activation: str,
        gated: bool) -> jax.Array:
    if gated:
        gate = _act(activation, x @ p["wg"])
        up = x @ p["wu"]
        return (gate * up) @ p["wd"]
    h = _act(activation, x @ p["wu"])
    return h @ p["wd"]


def moe_gates(x: jax.Array, wr: jax.Array, top_k: int) -> jax.Array:
    """Router: renormalized top-k gate weights [..., E] (zero off-top-k)."""
    logits = x @ wr
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_vals, _ = lax.top_k(probs, top_k)
    thresh = top_vals[..., -1:]
    gates = jnp.where(probs >= thresh, probs, 0.0)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates.astype(x.dtype)


def moe_ffn(x: jax.Array, p: Params, *, activation: str, gated: bool,
            num_experts: int, top_k: int) -> jax.Array:
    """Dense-gather MoE: every expert computes on the full token set, gated
    by the (renormalized) top-k router weights.

    This is the einsum-friendly EP formulation: experts shard over the model
    axis and each device computes only its local experts — the token
    all-to-all of dispatch-based MoE is traded for FLOPs that XLA prunes on
    the expert axis when gates are sparse.  Exact (same math as dispatch).
    """
    gates = moe_gates(x, p["wr"], top_k)
    if gated:
        gate_h = _act(activation, jnp.einsum("...d,edf->...ef", x, p["wg"]))
        up_h = jnp.einsum("...d,edf->...ef", x, p["wu"])
        h = gate_h * up_h
    else:
        h = _act(activation, jnp.einsum("...d,edf->...ef", x, p["wu"]))
    y = jnp.einsum("...ef,efd->...ed", h, p["wd"])
    return jnp.einsum("...ed,...e->...d", y, gates)


# --------------------------------------------------------------------- #
# Mamba2 (chunked SSD)
# --------------------------------------------------------------------- #

def _segsum(x: jax.Array) -> jax.Array:
    """Lower-triangular segment sums: out[..., i, j] = sum_{j<k<=i} x[..., k]."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), dtype=bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def mamba2_ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
               c: jax.Array, d_skip: jax.Array, *, chunk: int = 128,
               init_state: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, jax.Array]:
    """Chunked state-space-dual scan (Mamba2).

    Args:
        x: [B, S, H, P] inner activations (heads x head_dim).
        dt: [B, S, H] softplus-ed step sizes.
        a_log: [H] log of -A (A = -exp(a_log)).
        b, c: [B, S, N] input/output projections (single group).
        d_skip: [H] skip connection.
        chunk: intra-chunk length Q.
        init_state: [B, H, P, N] carried SSM state.
    Returns: (y [B,S,H,P], final_state [B,H,P,N]).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    if s % q != 0:
        raise ValueError(f"seq {s} must divide by chunk {q}")
    nc = s // q
    a = -jnp.exp(a_log.astype(jnp.float32))                     # [H]
    da = dt.astype(jnp.float32) * a                             # [B,S,H]
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    # Reshape into chunks.
    dac = da.reshape(bsz, nc, q, h)
    xc = xdt.reshape(bsz, nc, q, h, p)
    bc = b.astype(jnp.float32).reshape(bsz, nc, q, n)
    cc = c.astype(jnp.float32).reshape(bsz, nc, q, n)

    # Intra-chunk (diagonal blocks): y_ij = C_i . B_j exp(segsum) x_j.
    ss = _segsum(dac.transpose(0, 1, 3, 2))                     # [B,nc,H,Q,Q]
    l_mat = jnp.exp(ss)
    cb = jnp.einsum("bcin,bcjn->bcij", cc, bc)                  # [B,nc,Q,Q]
    y_diag = jnp.einsum("bcij,bchij,bcjhp->bcihp",
                        cb, l_mat.transpose(0, 1, 2, 3, 4), xc,
                        preferred_element_type=jnp.float32)

    # Chunk-final states: S_c = sum_j exp(sum_{k>j} da) B_j x_j.
    da_cum = jnp.cumsum(dac, axis=2)                            # [B,nc,Q,H]
    da_tot = da_cum[:, :, -1:, :]                               # [B,nc,1,H]
    decay_to_end = jnp.exp(da_tot - da_cum)                     # [B,nc,Q,H]
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", bc, decay_to_end, xc,
                        preferred_element_type=jnp.float32)     # [B,nc,H,P,N]

    # Inter-chunk recurrence over c.
    chunk_decay = jnp.exp(da_tot[:, :, 0, :])                   # [B,nc,H]
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((bsz, h, p, n), jnp.float32))

    def scan_fn(carry, inp):
        dec, st = inp                                           # [B,H], [B,H,P,N]
        new = carry * dec[:, :, None, None] + st
        return new, carry                                       # emit state *before* chunk

    final, prev_states = lax.scan(
        scan_fn, s0,
        (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)          # [B,nc,H,P,N]

    # Inter-chunk contribution: y += C_i exp(cum da_i) S_{c-1}.
    state_decay = jnp.exp(da_cum)                               # [B,nc,Q,H]
    y_off = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", cc, state_decay,
                       prev_states, preferred_element_type=jnp.float32)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :,
                                                               None]
    return y.astype(x.dtype), final


def mamba2_decode_step(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                       b: jax.Array, c: jax.Array, d_skip: jax.Array,
                       state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSM update.  x: [B,H,P], dt: [B,H], b/c: [B,N],
    state: [B,H,P,N] -> (y [B,H,P], new_state)."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    da = jnp.exp(dt.astype(jnp.float32) * a)                    # [B,H]
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    upd = jnp.einsum("bhp,bn->bhpn", xdt, b.astype(jnp.float32))
    new_state = state * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, c.astype(jnp.float32))
    y = y + x.astype(jnp.float32) * d_skip[None, :, None]
    return y.astype(x.dtype), new_state


def causal_conv1d(x: jax.Array, w: jax.Array, bias: jax.Array,
                  init: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: [B,S,D], w: [K,D] -> (y, last K-1 inputs)."""
    k = w.shape[0]
    if init is None:
        init = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([init, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None, :] for i in range(k))
    tail = xp[:, xp.shape[1] - (k - 1):]
    return jax.nn.silu(y + bias[None, None, :]), tail


# --------------------------------------------------------------------- #
# RWKV6 (Finch) — data-dependent decay linear recurrence
# --------------------------------------------------------------------- #

def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, init_state: Optional[jax.Array] = None,
         ) -> Tuple[jax.Array, jax.Array]:
    """RWKV6 recurrence.

    r/k/v: [B, S, H, N]; w: [B, S, H, N] per-step decay in (0,1);
    u: [H, N] bonus.  State: [B, H, N, N] (keys x values).
        y_t = r_t . (S_{t-1} + u * k_t^T v_t)
        S_t = diag(w_t) S_{t-1} + k_t^T v_t
    Returns (y [B,S,H,N], final_state).
    """
    bsz, s, h, n = r.shape
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((bsz, h, n, n), jnp.float32))

    def step(state, inp):
        rt, kt, vt, wt = inp                                    # [B,H,N] each
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = jnp.einsum("bhk,bhkv->bhv", rt,
                       state + u[None, :, :, None] * kv)
        new = state * wt[..., None] + kv
        return new, y

    seq = (r.astype(jnp.float32).transpose(1, 0, 2, 3),
           k.astype(jnp.float32).transpose(1, 0, 2, 3),
           v.astype(jnp.float32).transpose(1, 0, 2, 3),
           w.astype(jnp.float32).transpose(1, 0, 2, 3))
    final, ys = lax.scan(step, s0, seq)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), final


def token_shift(x: jax.Array, prev: Optional[jax.Array] = None) -> jax.Array:
    """RWKV token shift: x[t-1] (zeros / carried token at t=0)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _pallas_fwd_eager_bwd(fused_fn, eager_fn):
    """Pallas forward, eager-recompute backward.

    ``pl.pallas_call`` has no autodiff rule, so every fused wrapper pairs
    the kernel with the jnp formulation it replaces: the primal runs the
    Pallas kernel; the cotangent recomputes through the eager path's VJP
    (flash-attention-style recompute — no kernel-side residuals).  Gradients
    are therefore *exactly* the eager path's gradients; only the forward
    value carries kernel-tiling numerics.
    """
    f = jax.custom_vjp(fused_fn)

    def fwd(*args):
        return fused_fn(*args), args

    def bwd(args, g):
        return jax.vjp(eager_fn, *args)[1](g)

    f.defvjp(fwd, bwd)
    return f


def _flat_tokens(x: jax.Array) -> Tuple[jax.Array, Tuple[int, int]]:
    """[B, S, D] -> ([B*S, D], (B, S)) for the token-major kernels."""
    b, s, d = x.shape
    return x.reshape(b * s, d), (b, s)


def fused_norm_matmul(x: jax.Array, scale: jax.Array, w: jax.Array, *,
                      eps: float = 1e-6, block_t: int = 256,
                      block_n: int = 512, w8: int = 0,
                      shard=()) -> jax.Array:
    """rms_norm(x) @ w via the ``rmsnorm_matmul`` Pallas kernel.

    x: [B, S, D]; w: [D, N] -> [B, S, N].  The normalized activation lives
    only in VMEM (norm stats recomputed per token tile).  Under an active
    mesh the plan's ``shard`` claim runs the kernel column-parallel: batch
    over 'data', output columns over 'model' (no collective — each shard
    normalizes the full D row and produces its own columns).

    ``w8`` (plan block flag, DESIGN.md §14): weight-only int8 — the weight
    is quantized per output channel in-trace and the kernel dequantizes
    post-dot against the column scales.  Under a column-parallel claim the
    quantization runs per shard on its own columns (scales are
    per-output-channel, so the split is exact).  The eager reference is the
    dequantized matmul with a straight-through backward.
    """
    from ..kernels import rmsnorm_matmul as _kernel

    def fused(x, scale, w):
        xf, (b, s) = _flat_tokens(x)
        if w8:
            codes, ws = quantize_channelwise(w)
            y = _kernel(xf, scale, codes, eps=eps, block_t=block_t,
                        block_n=block_n, w_scale=ws)
        else:
            y = _kernel(xf, scale, w, eps=eps, block_t=block_t,
                        block_n=block_n)
        return y.reshape(b, s, w.shape[-1])

    def eager(x, scale, w):
        return rms_norm(x, scale, eps) @ (_w8_ste(w) if w8 else w)

    mesh = _shard_mesh(shard)
    bax = _claim_axis(mesh, shard, "tokens", x.shape[0])
    nax = _claim_axis(mesh, shard, "out", w.shape[-1])
    if bax or nax:
        fused = _smap(fused, mesh,
                      (P(bax, None, None), P(None), P(None, nax)),
                      P(bax, None, nax))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(x, scale, w)


def fused_matmul(x: jax.Array, w: jax.Array, *, block_t: int = 256,
                 block_n: int = 256, block_k: int = 512,
                 shard=()) -> jax.Array:
    """x @ w via the tiled ``block_matmul`` Pallas kernel ([B,S,D] layout);
    same column-parallel sharding contract as ``fused_norm_matmul``."""
    from ..kernels import block_matmul as _kernel

    def fused(x, w):
        xf, (b, s) = _flat_tokens(x)
        y = _kernel(xf, w, block_m=block_t, block_n=block_n, block_k=block_k)
        return y.reshape(b, s, w.shape[-1])

    mesh = _shard_mesh(shard)
    bax = _claim_axis(mesh, shard, "tokens", x.shape[0])
    nax = _claim_axis(mesh, shard, "out", w.shape[-1])
    if bax or nax:
        fused = _smap(fused, mesh, (P(bax, None, None), P(None, nax)),
                      P(bax, None, nax))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, lambda x, w: x @ w)(x, w)


def fused_ffn(x: jax.Array, p: Params, *, activation: str, gated: bool,
              norm_scale: Optional[jax.Array] = None,
              block_t: int = 256, block_f: int = 512, w8: int = 0,
              shard=()) -> jax.Array:
    """Stream-fused (GLU) FFN; with ``norm_scale`` the pre-FFN RMSNorm is
    folded into the kernel so the normalized stream never leaves VMEM.

    Sharded dispatch is Megatron-style row-parallel on ``d_ff``: each
    shard streams its own F columns of wg/wu and F rows of wd, and the
    partial [B, S, D] outputs are psum'd over the model axis (the gate
    activation is elementwise in F, so the split is exact math).

    ``w8``: weight-only int8 on all three projections (per-output-channel
    scales quantized in-trace; under a d_ff claim each shard scales its
    own slice).  Eager reference dequantizes with straight-through grads.
    """
    from ..kernels import streamed_ffn, streamed_mlp

    mesh = _shard_mesh(shard)
    bax = _claim_axis(mesh, shard, "tokens", x.shape[0])
    fax = _claim_axis(mesh, shard, "d_ff",
                      p["wu"].shape[-1] if "wu" in p else 0)

    if gated:
        def fused(x, wg, wu, wd, *norm):
            xf, (b, s) = _flat_tokens(x)
            qkw = {}
            if w8:
                wg, qkw["wg_scale"] = quantize_channelwise(wg)
                wu, qkw["wu_scale"] = quantize_channelwise(wu)
                wd, qkw["wd_scale"] = quantize_channelwise(wd)
            y = streamed_ffn(xf, wg, wu, wd, activation=activation,
                             norm_scale=norm[0] if norm else None,
                             block_t=block_t, block_f=block_f, **qkw)
            y = y.reshape(b, s, -1)
            return lax.psum(y, fax) if fax else y

        def eager(x, wg, wu, wd, *norm):
            h = rms_norm(x, norm[0]) if norm else x
            if w8:
                wg, wu, wd = _w8_ste(wg), _w8_ste(wu), _w8_ste(wd)
            return (_act(activation, h @ wg) * (h @ wu)) @ wd

        args = (x, p["wg"], p["wu"], p["wd"])
        w_specs = (P(None, fax), P(None, fax), P(fax, None))
    else:
        def fused(x, wu, wd, *norm):
            xf, (b, s) = _flat_tokens(x)
            qkw = {}
            if w8:
                wu, qkw["wu_scale"] = quantize_channelwise(wu)
                wd, qkw["wd_scale"] = quantize_channelwise(wd)
            y = streamed_mlp(xf, wu, wd, activation=activation,
                             norm_scale=norm[0] if norm else None,
                             block_t=block_t, block_f=block_f, **qkw)
            y = y.reshape(b, s, -1)
            return lax.psum(y, fax) if fax else y

        def eager(x, wu, wd, *norm):
            h = rms_norm(x, norm[0]) if norm else x
            if w8:
                wu, wd = _w8_ste(wu), _w8_ste(wd)
            return _act(activation, h @ wu) @ wd

        args = (x, p["wu"], p["wd"])
        w_specs = (P(None, fax), P(fax, None))
    if norm_scale is not None:
        args = args + (norm_scale,)
        w_specs = w_specs + (P(None),)
    if bax or fax:
        fused = _smap(fused, mesh, (P(bax, None, None),) + w_specs,
                      P(bax, None, None))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(*args)


def fused_moe_ffn(x: jax.Array, p: Params, *, activation: str,
                  top_k: int, block_t: int = 256, shard=()) -> jax.Array:
    """Router eager (tiny), experts via the ``moe_experts`` Pallas kernel.

    Sharded dispatch is expert-parallel: the (globally renormalized)
    gates and the expert weight stacks split over the model axis, each
    shard computes its local experts' contributions, and the outputs are
    psum'd — same math as the dense-gather eager formulation.
    """
    from ..kernels import moe_experts_pallas

    gates = moe_gates(x, p["wr"], top_k)

    mesh = _shard_mesh(shard)
    bax = _claim_axis(mesh, shard, "tokens", x.shape[0])
    eax = _claim_axis(mesh, shard, "experts", p["wu"].shape[0])

    def fused(x, gates, wg, wu, wd):
        xf, (b, s) = _flat_tokens(x)
        gf = gates.reshape(b * s, -1)
        y = moe_experts_pallas(xf, gf, wg, wu, wd, activation=activation,
                               block_t=block_t)
        y = y.reshape(b, s, -1)
        return lax.psum(y, eax) if eax else y

    def eager(x, gates, wg, wu, wd):
        gate_h = _act(activation, jnp.einsum("...d,edf->...ef", x, wg))
        up_h = jnp.einsum("...d,edf->...ef", x, wu)
        y = jnp.einsum("...ef,efd->...ed", gate_h * up_h, wd)
        return jnp.einsum("...ed,...e->...d", y, gates)

    if bax or eax:
        fused = _smap(fused, mesh,
                      (P(bax, None, None), P(bax, None, eax),
                       P(eax, None, None), P(eax, None, None),
                       P(eax, None, None)),
                      P(bax, None, None))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(
        x, gates, p["wg"], p["wu"], p["wd"])


def fused_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 512, block_kv: int = 512,
                    shard=()) -> jax.Array:
    """Flash-attention Pallas kernel with GQA; eager backward recomputes
    through ``streaming_attention`` / ``local_attention``.

    Sharded dispatch splits the kernel grid's head dimension over the
    model axis at KV-head granularity (the G query heads sharing a KV
    head stay together, so GQA reuse survives the split) and batch over
    'data' — both embarrassingly parallel, no collectives.
    """
    from ..kernels import flash_attention

    def fused(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv)

    def eager(q, k, v):
        if window:
            return local_attention(q, k, v, window=window)
        return streaming_attention(q, k, v, causal=causal)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "kv_heads", k.shape[2])
    bax = _claim_axis(mesh, shard, "batch", q.shape[0])
    if hax or bax:
        sp = P(bax, None, hax, None)
        fused = _smap(fused, mesh, (sp, sp, sp), sp)
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(q, k, v)


def fused_attention_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                          q_offset, kv_len, *, causal: bool = True,
                          window: int = 0, block_q: int = 512,
                          block_kv: int = 512,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          shard=()) -> jax.Array:
    """Chunked-prefill twin of ``fused_attention``: the offset flash
    kernel with dynamic ``q_offset`` / ``kv_len`` scalar-prefetch
    operands, dispatched under the plan's sharding (KV heads over the
    model axis; the scalars replicate).  Serving-only — no VJP pairing
    (prefill is never differentiated).

    Quantized KV: ``k_scale``/``v_scale`` [B, Skv, Hkv] per-position f32
    scales (page-scale rows repeated over page positions) — k/v are then
    int8/fp8 codes and the kernel dequantizes in-register."""
    from ..kernels import flash_attention

    quant = k_scale is not None

    def call(q, k, v, off, kl, *scales):
        ks, vs = scales if scales else (None, None)
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=off, kv_len=kl,
                               block_q=block_q, block_kv=block_kv,
                               k_scale=ks, v_scale=vs)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "kv_heads", k.shape[2])
    bax = _claim_axis(mesh, shard, "batch", q.shape[0])
    if hax or bax:
        sp = P(bax, None, hax, None)
        in_specs = (sp, sp, sp, P(), P())
        if quant:
            in_specs += (P(bax, None, hax), P(bax, None, hax))
        call = _smap(call, mesh, in_specs, sp)
    else:
        DISPATCH_RECORDS["single"] += 1
    extra = ((k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
             if quant else ())
    return call(q, k, v, jnp.asarray(q_offset, jnp.int32),
                jnp.asarray(kv_len, jnp.int32), *extra)


def fused_paged_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, page_table: jax.Array,
                          lengths: jax.Array, *, window: int = 0,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          shard=()) -> jax.Array:
    """Paged decode attention under the plan's sharding: the KV page
    pools split over the model axis at the ``kv_heads`` dim (matching the
    ``PagedKVCache`` pool sharding) and slots over 'data' — with a batch
    claim the page table and lengths split by slot alongside q, so each
    data shard prefetches only its own slots' table rows (the pools stay
    whole on the page dim within a shard, so every row still resolves).
    Serving-only — no VJP pairing.

    Quantized KV: ``k_scale``/``v_scale`` [P, Hkv] per-page f32 scale
    pools (sharded with the pools at ``kv_heads``) — the pools are then
    int8/fp8 codes and the kernel dequantizes in-register per page."""
    from ..kernels import paged_decode_attention

    quant = k_scale is not None

    def call(q, kp, vp, tbl, lens, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_decode_attention(q, kp, vp, tbl, lens, window=window,
                                      k_scale=ks, v_scale=vs)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "kv_heads", k_pool.shape[1])
    bax = _claim_axis(mesh, shard, "batch", q.shape[0])
    if hax or bax:
        in_specs = (P(bax, None, hax, None), P(None, hax, None, None),
                    P(None, hax, None, None), P(bax, None), P(bax))
        if quant:
            in_specs += (P(None, hax), P(None, hax))
        call = _smap(call, mesh, in_specs, P(bax, None, hax, None))
    else:
        DISPATCH_RECORDS["single"] += 1
    extra = (k_scale, v_scale) if quant else ()
    return call(q, k_pool, v_pool, page_table, lengths, *extra)


def fused_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           q_off: jax.Array, *, window: int = 0,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           shard=()) -> jax.Array:
    """Speculative-verify attention under the plan's sharding: identical
    dispatch contract to ``fused_paged_attention`` (KV pools split over
    the model axis at ``kv_heads``, slots over 'data'), with the W-row
    verify window riding in the query block — one kernel launch scores
    every draft position of every slot.  Serving-only — no VJP pairing.
    Quantized KV rides the same ``k_scale``/``v_scale`` [P, Hkv] contract
    as ``fused_paged_attention``."""
    from ..kernels import paged_verify_attention

    quant = k_scale is not None

    def call(q, kp, vp, tbl, off, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_verify_attention(q, kp, vp, tbl, off, window=window,
                                      k_scale=ks, v_scale=vs)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "kv_heads", k_pool.shape[1])
    bax = _claim_axis(mesh, shard, "batch", q.shape[0])
    if hax or bax:
        in_specs = (P(bax, None, hax, None), P(None, hax, None, None),
                    P(None, hax, None, None), P(bax, None), P(bax))
        if quant:
            in_specs += (P(None, hax), P(None, hax))
        call = _smap(call, mesh, in_specs, P(bax, None, hax, None))
    else:
        DISPATCH_RECORDS["single"] += 1
    extra = (k_scale, v_scale) if quant else ()
    return call(q, k_pool, v_pool, page_table, q_off, *extra)


def fused_mamba2_ssd(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                     b: jax.Array, c: jax.Array, d_skip: jax.Array, *,
                     chunk: int = 128, shard=()) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan via the ``mamba2_scan`` Pallas kernel; sharded
    dispatch splits the (independent) SSM heads over the model axis and
    batch over 'data'."""
    from ..kernels import mamba2_ssd_pallas

    def fused(x, dt, a_log, b, c, d_skip):
        return mamba2_ssd_pallas(x, dt, a_log, b, c, d_skip, chunk=chunk)

    def eager(x, dt, a_log, b, c, d_skip):
        return mamba2_ssd(x, dt, a_log, b, c, d_skip, chunk=chunk)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "heads", x.shape[2])
    bax = _claim_axis(mesh, shard, "batch", x.shape[0])
    if hax or bax:
        fused = _smap(fused, mesh,
                      (P(bax, None, hax, None), P(bax, None, hax), P(hax),
                       P(bax, None, None), P(bax, None, None), P(hax)),
                      (P(bax, None, hax, None), P(bax, hax, None, None)))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(x, dt, a_log, b, c, d_skip)


def fused_wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = 64, shard=(),
               ) -> Tuple[jax.Array, jax.Array]:
    """RWKV6 recurrence via the ``rwkv6_wkv`` Pallas kernel; sharded
    dispatch splits the (independent) RWKV heads over the model axis and
    batch over 'data'."""
    from ..kernels import wkv6_pallas

    def fused(r, k, v, w, u):
        return wkv6_pallas(r, k, v, w, u, chunk=chunk)

    def eager(r, k, v, w, u):
        return wkv6(r, k, v, w, u)

    mesh = _shard_mesh(shard)
    hax = _claim_axis(mesh, shard, "heads", r.shape[2])
    bax = _claim_axis(mesh, shard, "batch", r.shape[0])
    if hax or bax:
        sp = P(bax, None, hax, None)
        fused = _smap(fused, mesh, (sp, sp, sp, sp, P(hax, None)),
                      (sp, P(bax, hax, None, None)))
    else:
        DISPATCH_RECORDS["single"] += 1
    return _pallas_fwd_eager_bwd(fused, eager)(r, k, v, w, u)


def fused_streamed_xent(hidden: jax.Array, head: jax.Array,
                        labels: jax.Array, vocab_size: int, *,
                        block_t: int = 256, block_v: int = 2048,
                        shard=()) -> jax.Array:
    """Streamed CE loss via the ``streamed_xent`` Pallas kernel: [T, V]
    logits never materialize in the forward; the backward recomputes the
    logits from the (hidden, head) residuals through the eager formulation
    (labels ride along as an integer primal so the VJP structure is right —
    their cotangent is the symbolic zero).

    Sharded dispatch splits the token (batch) dim over 'data': each shard
    streams its own tokens' vocab tiles, and the (nll sum, valid count)
    pair is psum'd before the division so the mean weighs every token
    once regardless of the per-shard valid counts.
    """
    from ..kernels import streamed_xent_loss, streamed_xent_parts

    mesh = _shard_mesh(shard)
    bax = _claim_axis(mesh, shard, "tokens", hidden.shape[0])

    def fused(hidden, head, labels):
        hf, (b, s) = _flat_tokens(hidden)
        return streamed_xent_loss(hf, head, labels.reshape(b * s),
                                  vocab_size=vocab_size,
                                  block_t=block_t, block_v=block_v)

    if bax:
        def fused(hidden, head, labels):            # noqa: F811 — sharded twin
            hf, (b, s) = _flat_tokens(hidden)
            lf = labels.reshape(b * s)
            lse, gold = streamed_xent_parts(
                hf, head, jnp.maximum(lf, 0), vocab_size=vocab_size,
                block_t=block_t, block_v=block_v)
            valid = lf >= 0
            nll = jnp.where(valid, lse - gold, 0.0)
            tot = lax.psum(nll.sum(), bax)
            cnt = lax.psum(valid.sum(), bax)
            return tot / jnp.maximum(cnt, 1)

        fused = _smap(fused, mesh,
                      (P(bax, None, None), P(None, None), P(bax, None)),
                      P())
    else:
        DISPATCH_RECORDS["single"] += 1

    def eager(hidden, head, labels):
        hf, (b, s) = _flat_tokens(hidden)
        logits = (hf @ head).astype(jnp.float32)
        vp = logits.shape[-1]
        logits = jnp.where((jnp.arange(vp) >= vocab_size)[None], NEG_INF,
                           logits)
        lf = labels.reshape(b * s)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lf, 0)[:, None], axis=-1)[:, 0]
        valid = lf >= 0
        nll = jnp.where(valid, lse - gold, 0.0)
        return nll.sum() / jnp.maximum(valid.sum(), 1)

    return _pallas_fwd_eager_bwd(fused, eager)(hidden, head, labels)


def wkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                 u: jax.Array, init_state: Optional[jax.Array] = None, *,
                 chunk: int = 16, min_log_w: float = -5.0,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Chunk-parallel wkv6 (§Perf rwkv6 hillclimb).

    The per-token scan reads+writes the [H, N, N] f32 state every timestep —
    the dominant memory-roofline term of rwkv6 training.  This form carries
    the state once per ``chunk`` tokens (traffic / chunk) and computes the
    intra-chunk part with matmuls via the factored decay identity

        s[t,j] = sum_k (r[t,k] e^{L[t-1,k]}) * (k[j,k] e^{-L[j,k]}),  j < t

    with L the in-chunk cumulative log-decay.  ``e^{-L}`` grows with chunk
    depth, so per-step log decay is clamped at ``min_log_w``: with chunk=16
    the factor exponent is bounded by 80 < log(f32max)=88.  The clamp
    saturates decays below e^-5 per step (a token's influence after one such
    step is < 0.7%); tests verify exact equivalence against the sequential
    recurrence under the same clamp.
    """
    bsz, s, h, n = r.shape
    c = min(chunk, s)
    if s % c != 0:
        c = math.gcd(s, c)
    nc = s // c
    f32 = jnp.float32
    rr = r.astype(f32).reshape(bsz, nc, c, h, n)
    kk = k.astype(f32).reshape(bsz, nc, c, h, n)
    vv = v.astype(f32).reshape(bsz, nc, c, h, n)
    lw = jnp.clip(jnp.log(jnp.maximum(w.astype(f32), 1e-30)),
                  min_log_w, 0.0).reshape(bsz, nc, c, h, n)
    el = jnp.cumsum(lw, axis=2)          # inclusive log-decay  (<= 0)
    elm1 = el - lw                        # exclusive (L[t-1])
    a = rr * jnp.exp(elm1)                # bounded <= |r|
    bmat = kk * jnp.exp(-el)              # bounded by e^{-min_log_w * c}
    scores = jnp.einsum("bcthn,bcjhn->bchtj", a, bmat)
    tri = jnp.tril(jnp.ones((c, c), bool), k=-1)      # strictly lower: j<t
    y_intra = jnp.einsum("bchtj,bcjhn->bcthn",
                         jnp.where(tri[None, None, None], scores, 0.0), vv)
    # Diagonal bonus term: y += (sum_k r u k) * v at each t.
    coef = jnp.einsum("bcthn,hn,bcthn->bcth", rr, u.astype(f32), kk)
    y_diag = coef[..., None] * vv
    # Inter-chunk recurrence.
    s0 = (init_state.astype(f32) if init_state is not None
          else jnp.zeros((bsz, h, n, n), f32))
    chunk_decay = jnp.exp(el[:, :, -1])                   # [B,nc,H,N]
    kdec = bmat * jnp.exp(el[:, :, -1])[:, :, None]       # k * e^{L[-1]-L[j]}
    s_updates = jnp.einsum("bcjhk,bcjhv->bchkv", kdec, vv)

    def scan_fn(state, inp):
        a_c, dec, upd = inp               # [B,c,H,N], [B,H,N], [B,H,N,N]
        y_cross = jnp.einsum("bthk,bhkv->bthv", a_c, state)
        new = state * dec[..., None] + upd
        return new, y_cross

    final, y_cross = lax.scan(
        scan_fn, s0,
        (a.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2, 3),
         s_updates.transpose(1, 0, 2, 3, 4)))
    y_cross = y_cross.transpose(1, 0, 2, 3, 4)
    y = (y_intra + y_diag + y_cross).reshape(bsz, s, h, n)
    return y.astype(r.dtype), final
