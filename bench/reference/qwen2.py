"""Qwen2 / Qwen1.5 (``model_type: qwen2``): q/k/v biases, no qk-norm;
head_dim is hidden_size / num_attention_heads.  The dense decoder of
``dense.py``."""

from bench.reference import dense
from bench.reference.dense import layers, published  # noqa: F401

QK_NORM = False
QKV_BIAS = True


def program_fields(cfg: dict) -> dict:
    """``ModelConfig`` fields of the engine that ``config.json`` sets."""
    if cfg["hidden_act"] != "silu" or cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the engine computes silu and RMSNorm eps 1e-6 only")
    return {
        "num_layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "d_ff": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "qk_norm": QK_NORM,
        "qkv_bias": QKV_BIAS,
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def forward_logits(w, cfg, tokens, at, quant=None):
    return dense.forward_logits(w, cfg, tokens, at, qk_norm=QK_NORM,
                                quant=quant)
