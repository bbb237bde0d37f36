"""Plain float32 forward of a dense pre-norm decoder (Qwen2 / Qwen3 family).

Written from the published equations (Hugging Face ``modeling_qwen2`` and
``modeling_qwen3``), with no kernels, cache or batching:

    h  = RMSNorm(x) * w_in
    q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)      # bias: Qwen2
    q, k = RMSNorm_hd(q) * q_norm, RMSNorm_hd(k) * k_norm  # Qwen3 only
    q, k = RoPE(q), RoPE(k)          # rotate-half, inv_freq = theta^(-2i/hd)
    x  = x + softmax(q k^T / sqrt(hd) + causal) v Wo  # GQA: kv = q // group
    h  = RMSNorm(x) * w_post
    x  = x + (silu(h Wg) * (h Wu)) Wd
    logits = (RMSNorm(x) * w_norm) W_head                # W_head = E^T if tied

Every matrix product runs at ``Precision.HIGHEST`` (on a TPU an f32 dot
otherwise runs in bfloat16 passes).  Weights arrive in the served dtype
and each layer is upcast to float32 inside the layer scan (``upcast``),
so one layer, not the whole model, is held in float32 beside them.
Attention is computed in blocks of queries so a 4,096-token row fits
beside the weights.

``quant="int8"`` is the control: every linear layer (the head included)
takes symmetric int8 operands, activations per row and weights per output
column, accumulated exactly.  It is the precision step below the
bfloat16 the configurations state.

This module gives the contract of ``bench/reference/__init__.py`` for
such a decoder, which ``qwen2.py`` and ``qwen3.py`` take: ``published``
is its one function that reads the program's parameter layout; the
forward sees only the names above.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from bench import weights
from bench.reference import Layer

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512


def _fake_int8(a: jax.Array, axis: int) -> jax.Array:
    """Round ``a`` onto a symmetric int8 grid with one scale per slice
    along ``axis`` (the reduction axis of the product it feeds)."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def linear(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """``x [..., K] @ w [K, N]`` in f32, or on int8 grids for the control."""
    if quant == "int8":
        x = _fake_int8(x, axis=-1)
        w = _fake_int8(w, axis=0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [S, H, D] rotated by ``positions`` [S] (rotate-half convention)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                                / half))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq      # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """q [S, Hq, D], k/v [S, Hkv, D] -> [S, Hq, D]; causal, GQA, f32,
    computed ``Q_BLOCK`` query rows at a time (fewer where ``S`` is not a
    multiple of it)."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)                                # [S, Hq, D]
    vv = jnp.repeat(v, g, axis=1)
    blk = math.gcd(Q_BLOCK, s)
    qb = q.reshape(s // blk, blk, hq, d)
    cols = jnp.arange(s)

    def one(args):
        i, qi = args
        rows = i * blk + jnp.arange(blk)
        sc = jnp.einsum("qhd,khd->hqk", qi, kk,
                        precision=HIGHEST) / math.sqrt(d)
        sc = jnp.where((cols[None, :] <= rows[:, None])[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HIGHEST)

    out = lax.map(one, (jnp.arange(s // blk), qb))
    return out.reshape(s, hq, d)


def upcast(a: jax.Array) -> jax.Array:
    """``a`` in float32, exactly.  A bfloat16 value is the upper half of
    the float32 one, and is widened by its bits, not by a cast: XLA folds
    a cast into the product it feeds, which then multiplies a bfloat16
    operand in other passes and rounds differently from the float32
    product the limits were set with."""
    if a.dtype != jnp.bfloat16:
        return a.astype(jnp.float32)
    bits = lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.uint32)
    return lax.bitcast_convert_type(bits << 16, jnp.float32)


def forward_logits(w: Dict, cfg: Dict, tokens: jax.Array, at: jax.Array, *,
                   qk_norm: bool, quant: Optional[str] = None) -> jax.Array:
    """Logits [len(at), vocab] of one row ``tokens`` [S] at positions ``at``.

    ``w`` holds ``embed`` [V, d] and ``head`` [d, V]; ``layers`` stacked
    over the layer axis (``input_norm``, ``q``, ``k``, ``v``, ``o``,
    optional ``q_bias``/``k_bias``/``v_bias``, optional ``q_norm``/
    ``k_norm``, ``post_norm``, ``gate``, ``up``, ``down``); ``norm`` [d].
    Any float dtype: each is upcast to float32 where it is used.
    """
    f32 = lambda a: jax.tree.map(upcast, a)   # noqa: E731
    hq = cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // hq
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    s = tokens.shape[0]
    positions = jnp.arange(s)
    x = f32(jnp.take(w["embed"], tokens, axis=0))                # [S, d]

    def layer(x, lw):
        lw = f32(lw)
        h = rms_norm(x, lw["input_norm"], eps)
        q = linear(h, lw["q"], quant)
        k = linear(h, lw["k"], quant)
        v = linear(h, lw["v"], quant)
        if "q_bias" in lw:
            q, k, v = q + lw["q_bias"], k + lw["k_bias"], v + lw["v_bias"]
        q = q.reshape(s, hq, hd)
        k = k.reshape(s, hkv, hd)
        v = v.reshape(s, hkv, hd)
        if qk_norm:
            q = rms_norm(q, lw["q_norm"], eps)
            k = rms_norm(k, lw["k_norm"], eps)
        q, k = rope(q, positions, theta), rope(k, positions, theta)
        o = causal_attention(q, k, v).reshape(s, hq * hd)
        x = x + linear(o, lw["o"], quant)
        h = rms_norm(x, lw["post_norm"], eps)
        f = jax.nn.silu(linear(h, lw["gate"], quant)) * linear(h, lw["up"],
                                                               quant)
        return x + linear(f, lw["down"], quant), None

    x, _ = lax.scan(layer, x, w["layers"])
    h = rms_norm(jnp.take(x, at, axis=0), f32(w["norm"]), eps)  # [n, d]
    return linear(h, f32(w["head"]), quant)


def published(params, cfg: Dict) -> Dict:
    """The engine's tree of such a decoder in the names ``forward_logits``
    takes: one homogeneous stack of layers, matrices and biases in the
    served dtype, norms in float32."""
    if params["rest"] or len(params["blocks"]) != 1:
        raise ValueError("expected one homogeneous stack of layers")
    blk = params["blocks"][0]
    attn, mlp = blk["attn"], blk["mlp"]
    layers = {
        "input_norm": weights.norm(blk["ln1"]["scale"]),
        "q": attn["wq"], "k": attn["wk"], "v": attn["wv"], "o": attn["wo"],
        "post_norm": weights.norm(blk["ln2"]["scale"]),
        "gate": mlp["wg"], "up": mlp["wu"], "down": mlp["wd"],
    }
    if "bq" in attn:
        layers.update(q_bias=attn["bq"], k_bias=attn["bk"],
                      v_bias=attn["bv"])
    if "q_norm" in attn:
        layers.update(q_norm=weights.norm(attn["q_norm"]),
                      k_norm=weights.norm(attn["k_norm"]))
    return {"embed": weights.embedding(params, cfg),
            "head": weights.head(params, cfg),
            "layers": layers,
            "norm": weights.norm(params["final_norm"]["scale"])}


def layers(cfg: Dict) -> List[Layer]:
    """Every layer alike: full attention, q/k/v/o and a gated FFN of
    ``intermediate_size``, each weight read once a tick and used for two
    operations per token."""
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    ff = cfg["intermediate_size"]
    n = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff
    return [Layer(window=0, heads=hq, kv_heads=hkv, head_dim=hd, weights=n,
                  flops=2 * n)] * cfg["num_hidden_layers"]
