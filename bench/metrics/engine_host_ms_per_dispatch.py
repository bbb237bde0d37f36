"""Scheduler: the engine's host time per program dispatch.

Time in the traced window that the engine spent in its host-phase spans
(admission, chunk staging, enqueues, decode preparation, harvest,
retirement, prefix insertion; device waits excluded, ``bench/spans.py``)
over the number of program enqueue spans (prefill chunks and decode
blocks).  A program without the spans is read from the runtime's events
(``bench/spans.py``).  Moves ``output_tok_per_s``.
"""

from bench import spans


def read(r):
    lo, hi = r.window_ns
    n = spans.dispatches(r.trace, lo, hi)
    if not n:
        return None
    return spans.length(spans.host_work(r.trace.host, lo, hi)) / n / 1e6
