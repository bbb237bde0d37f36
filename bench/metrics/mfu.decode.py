"""Whole step, decode: decode operations per second over the chips' peak.

Operations (``bench/flops.py``) of every decoded token, attention
included, over the engine's ``decode_dispatch_s`` span sum (dispatch to
token read-back, device and host) x chips x bf16 peak, traced window.
Bounds what any decode kernel's roofline gain can move.  Moves
``tpot_p90_ms``.
"""

from bench import flops


def read(r):
    t = r.counters["decode_dispatch_s"]
    if t <= 0 or not r.reqs:
        return None
    ops = sum(flops.decode_flops(r.cfg, len(q.prompt), len(q.out_tokens) - 1)
              for q in r.reqs)
    return 100.0 * ops / (t * r.chips * r.peaks["bf16_flops"])
