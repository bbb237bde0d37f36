"""Operations and bytes of the served model, from the configuration file.

Counts sum over the layers that the configuration's reference module
gives (``layers(cfg)``, one ``bench.reference.Layer`` each): its matrix
operations per token and the matrix weights a decode tick reads; its
attention, where a token at position ``p`` (0-based) sees ``p + 1`` keys,
or ``min(p + 1, window)`` in a windowed layer, costs ``4 keys heads
head_dim`` (scores and the weighted sum), and reading the K/V of those
keys ``2 keys kv_heads head_dim`` bfloat16 values.  Norms, RoPE and
softmax are left out.  Only the last prompt token and each decoded token
go through the output head, as in the engine.
"""

from __future__ import annotations

from typing import Dict, List

from bench.reference import Layer, reference_module

BF16_BYTES = 2


def layers(cfg: Dict) -> List[Layer]:
    return reference_module(cfg).layers(cfg)


def _keys(layer: Layer, first: int, last: int) -> int:
    """Keys seen by positions ``first..last-1`` together, in ``layer``."""
    def upto(n):                        # positions 0..n-1
        w = layer.window
        if not w or n <= w:
            return n * (n + 1) // 2
        return w * (w + 1) // 2 + (n - w) * w
    return upto(last) - upto(first)


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one layer's matrix products, where every layer has the
    same."""
    sizes = {layer.weights for layer in layers(cfg)}
    if len(sizes) != 1:
        raise ValueError(f"the layers read different weights: {sizes}")
    return sizes.pop()


def head_flops(cfg: Dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict, first: int, last: int) -> int:
    """Attention operations, all layers, of positions ``first..last-1``."""
    return sum(4 * _keys(layer, first, last) * layer.heads * layer.head_dim
               for layer in layers(cfg))


def _matmul_flops(cfg: Dict, n: int) -> int:
    return n * sum(layer.flops for layer in layers(cfg))


def prompt_flops(cfg: Dict, first: int, last: int) -> int:
    """Prefill of prompt positions ``first..last-1`` (``first`` > 0 where a
    cached prefix was claimed), with the head on the last position."""
    if last <= first:
        return 0
    return (_matmul_flops(cfg, last - first)
            + attention_flops(cfg, first, last) + head_flops(cfg))


def decode_flops(cfg: Dict, first: int, n: int) -> int:
    """``n`` decoded tokens fed at positions ``first..first+n-1``."""
    if n <= 0:
        return 0
    return (_matmul_flops(cfg, n)
            + attention_flops(cfg, first, first + n) + n * head_flops(cfg))


def _kv_bytes_per_key(layer: Layer) -> int:
    return 2 * layer.kv_heads * layer.head_dim * BF16_BYTES


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one token, every layer, bfloat16."""
    return sum(_kv_bytes_per_key(layer) for layer in layers(cfg))


def live_kv_bytes(cfg: Dict, prompt_len: int, decoded: int) -> int:
    """K/V bytes the decode ticks of one request must read: the tick that
    feeds position ``p`` reads the keys position ``p`` sees in each layer,
    for ``p`` from ``prompt_len`` to ``prompt_len + decoded - 1``."""
    a = prompt_len
    return sum(_keys(layer, a, a + decoded) * _kv_bytes_per_key(layer)
               for layer in layers(cfg))


def decode_weight_bytes(cfg: Dict) -> int:
    """Weight bytes one decode tick must read: every layer's matrices and
    the output head, bfloat16 (norms and the embedding row left out)."""
    return BF16_BYTES * (sum(layer.weights for layer in layers(cfg))
                         + cfg["hidden_size"] * cfg["vocab_size"])
