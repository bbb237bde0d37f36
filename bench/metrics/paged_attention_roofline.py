"""Kernels: the paged decode-attention kernel's share of its roofline.

The least time the chip needs to read the K/V that the window's decode
ticks must attend to (every finished request's live tokens at each of its
decode ticks, ``bench/flops.py``) at HBM bandwidth, over the device time
of the ``paged_attention`` Pallas calls in the traced window.  Attention
is bound by those bytes at decode (two operations per byte read), so the
bandwidth term is the roofline.  Moves ``tpot_p90_ms``.
"""

from bench import flops, trace


def read(r):
    t = trace.time_by_kind(r.trace.ops).get("paged_attention", 0.0) / 1e9
    if t <= 0 or not r.reqs:
        return None
    need = sum(flops.live_kv_bytes(r.cfg, len(q.prompt),
                                   len(q.out_tokens) - 1) for q in r.reqs)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
