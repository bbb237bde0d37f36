"""Qwen3 (``model_type: qwen3``): GQA, qk-norm over head_dim, no biases."""

QK_NORM = True
QKV_BIAS = False


def program_fields(cfg: dict) -> dict:
    """``ModelConfig`` fields of the engine that ``config.json`` sets."""
    return {
        "num_layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "d_ff": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "rope_theta": float(cfg["rope_theta"]),
        "qk_norm": QK_NORM,
        "qkv_bias": bool(cfg["attention_bias"]),
        "tie_embeddings": bool(cfg["tie_word_embeddings"]),
    }
