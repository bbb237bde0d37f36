"""Model step: the decode program's share of its roofline.

The least time the chip needs for the window's decode work, the larger of
bytes over HBM bandwidth and operations over peak: every useful tick
reads all weights and the output head once (``ticks`` x
``decode_weight_bytes``) plus the live K/V of every decoded token; the
operations are those of every decoded token (``bench/flops.py``).  Over the
device time of the decode program (XLA module ``jit__decode_n``) in the
traced window.  Moves ``tpot_p90_ms``.
"""

from bench import flops, trace


def read(r):
    calls = trace.modules_named(r.trace, "_decode_n")
    t = sum(m.dur for m in calls) / 1e9
    if t <= 0 or not r.reqs:
        return None
    nbytes = r.counters["ticks"] * flops.decode_weight_bytes(r.cfg)
    ops = 0
    for q in r.reqs:
        n = len(q.out_tokens) - 1
        nbytes += flops.live_kv_bytes(r.cfg, len(q.prompt), n)
        ops += flops.decode_flops(r.cfg, len(q.prompt), n)
    least = max(nbytes / r.peaks["hbm_bytes_per_s"],
                ops / r.peaks["bf16_flops"])
    return 100.0 * least / t
