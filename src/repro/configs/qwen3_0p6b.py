"""qwen3-0.6b — dense transformer with qk-norm and GQA.

Published config: huggingface.co/Qwen/Qwen3-0.6B, ``config.json`` —
28 layers, hidden_size=1024, 16 query heads over 8 KV heads,
head_dim=128 (so q_dim = 2048 != hidden_size), intermediate_size=3072,
vocab_size=151936, rope_theta=1e6, tied embeddings.
"""

from .base import DENSE, ModelConfig

CONFIG = ModelConfig(
    name="qwen3-0.6b",
    family=DENSE,
    num_layers=28,
    d_model=1024,
    num_heads=16,
    num_kv_heads=8,
    head_dim=128,
    d_ff=3072,
    vocab_size=151936,
    qk_norm=True,
    rope="rope",
    rope_theta=1e6,
    tie_embeddings=True,
)
