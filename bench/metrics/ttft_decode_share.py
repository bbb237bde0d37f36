"""Scheduler: share of the time to a batch's last first token that the
device spent in decode blocks.

For each traced batch, from its ``bench batch`` annotation's start to
the end of the last ``req.first_token`` span inside it: the device time
of the decode program (XLA module ``jit__decode_n``) in that interval,
over its length, summed over batches.  The rest of the interval is
prefill chunks, other programs and idle time, so the share says how much
the decode blocks of admitted requests delay the prompts still waiting.
A program without the spans ends the interval at the batch's last
prefill program (``bench/spans.py``).  Moves ``ttft_p90_ms``.
"""

from bench import spans, trace


def read(r):
    decode = trace.modules_named(r.trace, "_decode_n")
    busy = total = 0.0
    for b in (e for e in r.trace.host if e.name.startswith(spans.BATCH)):
        end = spans.last_first_token(r.trace, b)
        if end is not None:
            busy += trace.busy_ns(trace.clip(decode, b.start, end))
            total += end - b.start
    if total <= 0:
        return None
    return 100.0 * busy / total
