"""Model step: host-clock time per decode tick.

The engine's ``decode_dispatch_s`` span sum (it ends at the token
read-back, so it covers device and host) over the ``scan_ticks`` it ran,
deltas over the traced window.  Moves ``tpot_p90_ms``.
"""


def read(r):
    ticks = r.counters["scan_ticks"]
    if ticks <= 0:
        return None
    return 1e3 * r.counters["decode_dispatch_s"] / ticks
