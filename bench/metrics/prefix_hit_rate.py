"""Prefix cache: share of admitted prompt pages claimed from the cache.

``prefix_hit_pages`` over ``prompt_pages``, registry deltas over the traced
window.  Moves ``ttft_p90_ms``.
"""


def read(r):
    pages = r.counters["prompt_pages"]
    if pages <= 0:
        return None
    return 100.0 * r.counters["prefix_hit_pages"] / pages
