"""Operations and bytes of the served model, from the configuration file.

Counts follow the published equations (``reference/dense.py``): a matrix
product of ``[n, k] @ [k, m]`` is ``2 n k m`` operations; attention of a
token at position ``p`` (0-based, so it sees ``p + 1`` keys) is ``4 (p + 1)
heads head_dim`` per layer (scores and the weighted sum).  Norms, RoPE and
softmax are left out.  Only the last prompt token and each decoded token
go through the output head, as in the engine.
"""

from __future__ import annotations

from typing import Dict

BF16_BYTES = 2


def widths(cfg: Dict) -> Dict[str, int]:
    hq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // hq
    return {"d": cfg["hidden_size"], "hq": hq,
            "hkv": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"]}


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o, gate, up, down)."""
    w = widths(cfg)
    dq, dkv = w["hq"] * w["hd"], w["hkv"] * w["hd"]
    return w["d"] * (dq + 2 * dkv) + dq * w["d"] + 3 * w["d"] * w["ff"]


def head_flops(cfg: Dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict, first: int, last: int) -> int:
    """Attention operations, all layers, of positions ``first..last-1``."""
    w = widths(cfg)
    keys = (last * (last + 1) - first * (first + 1)) // 2   # sum of p + 1
    return 4 * keys * w["hq"] * w["hd"] * w["layers"]


def prompt_flops(cfg: Dict, first: int, last: int) -> int:
    """Prefill of prompt positions ``first..last-1`` (``first`` > 0 where a
    cached prefix was claimed), with the head on the last position."""
    if last <= first:
        return 0
    n = last - first
    return (2 * n * layer_matmul_params(cfg) * cfg["num_hidden_layers"]
            + attention_flops(cfg, first, last) + head_flops(cfg))


def decode_flops(cfg: Dict, first: int, n: int) -> int:
    """``n`` decoded tokens fed at positions ``first..first+n-1``."""
    if n <= 0:
        return 0
    return (2 * n * layer_matmul_params(cfg) * cfg["num_hidden_layers"]
            + attention_flops(cfg, first, first + n) + n * head_flops(cfg))


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one token, every layer, bfloat16."""
    w = widths(cfg)
    return 2 * w["layers"] * w["hkv"] * w["hd"] * BF16_BYTES


def live_kv_bytes(cfg: Dict, prompt_len: int, decoded: int) -> int:
    """K/V bytes the decode ticks of one request must read: the tick that
    feeds position ``p`` reads the ``p + 1`` cached tokens, for ``p`` from
    ``prompt_len`` to ``prompt_len + decoded - 1``."""
    n, a = decoded, prompt_len
    tokens = n * (a + 1) + n * (n - 1) // 2
    return tokens * kv_bytes_per_token(cfg)


def decode_weight_bytes(cfg: Dict) -> int:
    """Weight bytes one decode tick must read: every layer's matrices and
    the output head, bfloat16 (norms and the embedding row left out)."""
    return BF16_BYTES * (layer_matmul_params(cfg) * cfg["num_hidden_layers"]
                         + cfg["hidden_size"] * cfg["vocab_size"])
