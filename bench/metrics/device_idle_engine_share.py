"""Scheduler: share of the traced window in which the device sat idle
while the engine did host work.

Idle intervals between the device's ops (as ``device_idle_share`` reads
them) that lie inside the engine's host-phase spans and outside its
device waits (``bench/spans.py``), over the window: the part of the idle
share that the engine's own host loop holds the chip back, a subset of
``device_idle_share``.  A program without the spans is read from the
runtime's events (``bench/spans.py``).  Moves ``output_tok_per_s``.
"""

from bench import spans, trace


def read(r):
    lo, hi = r.window_ns
    work = spans.host_work(r.trace.host, lo, hi)
    if hi <= lo or not work or not r.trace.ops:
        return None
    idle = spans.intersect(trace.gaps(r.trace.ops, lo, hi), work)
    return 100.0 * spans.length(idle) / (hi - lo)
