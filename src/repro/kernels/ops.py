"""Public jitted wrappers for the Pallas kernels.

These are the entry points the lowered fusion groups map to
(core/lowering.py pattern registry).  Each wrapper reshapes model-layout
tensors into the kernel layouts, pads head dims to the 128-lane width where
needed, and dispatches to interpret mode off-TPU.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .block_matmul import block_matmul
from .common import LANE, interpret_default, round_up
from .flash_attention import flash_attention_2d
from .mamba2_scan import mamba2_ssd_pallas
from .moe_experts import moe_experts_pallas
from .rmsnorm_matmul import rmsnorm_matmul
from .rwkv6_wkv import wkv6_pallas
from .stream_converter import convert_layout
from .streamed_ffn import streamed_ffn, streamed_mlp
from .streamed_xent import streamed_xent_loss, streamed_xent_parts

__all__ = [
    "block_matmul", "streamed_ffn", "streamed_mlp", "rmsnorm_matmul",
    "flash_attention", "flash_attention_2d", "streamed_xent_loss",
    "streamed_xent_parts", "mamba2_ssd_pallas", "wkv6_pallas",
    "moe_experts_pallas", "convert_layout",
]


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    kv_len=None,
                    block_q: int = 512, block_kv: int = 512,
                    q_offset=None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Model-layout flash attention with GQA.

    q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] -> [B, Sq, Hq, D].
    Query heads are grouped over their KV head so one kernel instance
    serves a (kv-head, group) pair without materializing repeated K/V.

    ``q_offset`` (chunked prefill) shifts query positions by a dynamic
    scalar so a chunk's queries attend the already-cached prefix; with it
    set, ``kv_len`` may be a traced scalar (the cache's valid fill).

    Quantized K/V (offset path): ``k_scale``/``v_scale`` [B, Skv, Hkv]
    per-position f32 scales — k/v are then int8/fp8 codes gathered from
    quantized pools, dequantized in-register by the kernel.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    interpret = interpret_default() if interpret is None else interpret
    dp = d if interpret else round_up(d, LANE)
    if dp != d:
        pad = ((0, 0), (0, 0), (0, 0), (0, dp - d))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    scale = 1.0 / math.sqrt(d)
    # Flatten heads: q -> [B*Hkv*G, Sq, D] grouped kv-head-major so that
    # program b's KV head is b // g — no repeated K/V in memory.
    qk = q.reshape(b, sq, hkv, g, dp).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * g, sq, dp)
    kk = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dp)
    vk = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, dp)
    if k_scale is not None:
        k_scale = k_scale.transpose(0, 2, 1).reshape(b * hkv, skv)
        v_scale = v_scale.transpose(0, 2, 1).reshape(b * hkv, skv)
    out = flash_attention_2d(qk, kk, vk, causal=causal, window=window,
                             kv_len=kv_len, scale=scale, kv_group=g,
                             block_q=block_q, block_kv=block_kv,
                             q_offset=q_offset, k_scale=k_scale,
                             v_scale=v_scale, interpret=interpret)
    out = out.reshape(b, hkv, g, sq, dp).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq, hq, dp)
    return out[..., :d]
