"""Kernels: the paged decode-attention kernel's share of device busy time.

Device time of the ``paged_attention`` Pallas calls over the union of all
device op intervals, traced window.  Moves ``tpot_p90_ms``.
"""

from bench import trace


def read(r):
    busy = trace.busy_ns(r.trace.ops)
    t = trace.time_by_kind(r.trace.ops).get("paged_attention", 0.0)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
