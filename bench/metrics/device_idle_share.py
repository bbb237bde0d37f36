"""Device: share of the traced window in which no op ran on the chip.

1 - (union of device op intervals) / (window from the first batch's
submission to the last one's return), from the profiler trace.  Moves
``output_tok_per_s``.
"""

from bench import trace


def read(r):
    lo, hi = r.window_ns
    if hi <= lo or not r.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_ns(r.trace.ops) / (hi - lo))
