"""Whole step, served: model operations per second over the chips' peak.

Operations (``bench/flops.py``) of every prompt token computed, not
claimed from the prefix cache, and of every decoded token, attention
included, over the traced window's host-clock length x chips x peak.
The cache's claimed tokens (``prefix_hit_pages`` x page size) are spread
evenly over the window's requests.  Moves ``output_tok_per_s``.
"""

from bench import flops


def read(r):
    if not r.reqs or r.window_s <= 0:
        return None
    claimed = r.counters["prefix_hit_pages"] * r.page_size / len(r.reqs)
    ops = 0
    for q in r.reqs:
        plen = len(q.prompt)
        first = min(int(claimed), plen - 1)
        ops += flops.prompt_flops(r.cfg, first, plen)
        ops += flops.decode_flops(r.cfg, plen, len(q.out_tokens) - 1)
    return 100.0 * ops / (r.window_s * r.chips * r.peaks["bf16_flops"])
