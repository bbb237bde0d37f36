"""Model step: device time of one prefill-chunk program call.

Sum of the device durations of the engine's chunk program (XLA module
``jit__chunk_fwd``) over its calls in the traced window.  Moves
``ttft_p90_ms``.
"""

from bench import trace


def read(r):
    calls = trace.modules_named(r.trace, "_chunk_fwd")
    if not calls:
        return None
    return sum(m.dur for m in calls) / len(calls) / 1e6
