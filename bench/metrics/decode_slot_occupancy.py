"""Scheduler: share of decode slot-ticks that delivered a token.

Decoded tokens (``generated`` less the first tokens, which come from
prefill) over ``scan_ticks`` x slots, from the engine's registry over the
traced window.  Moves ``output_tok_per_s``.
"""


def read(r):
    ticks = r.counters["scan_ticks"]
    if ticks <= 0:
        return None
    decoded = r.counters["generated"] - r.counters["prefills"]
    return 100.0 * decoded / (ticks * int(r.mix["slots"]))
