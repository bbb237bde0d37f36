"""Qwen2 / Qwen1.5 (``model_type: qwen2``): q/k/v biases, no qk-norm;
head_dim is hidden_size / num_attention_heads."""

QK_NORM = False
QKV_BIAS = True


def program_fields(cfg: dict) -> dict:
    """``ModelConfig`` fields of the engine that ``config.json`` sets."""
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
