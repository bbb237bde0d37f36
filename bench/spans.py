"""The serving engine's host phases on the profiler's timeline.

The program puts each host phase of ``ServingEngine.generate`` on the
profiler's trace as a ``jax.profiler.TraceAnnotation`` (vocabulary in
``src/repro/obs/events.py``).  The names below are the benchmark's copy of
that vocabulary, held to the program's by ``bench/tests/test_spans.py``:
a program without the spans, or with them renamed, reads nothing here,
and the metrics built on them fall silent.

Leaf spans tile one ``generate()`` pass: ``HOST_PHASES`` are host work
(admission, chunk staging, the program enqueues, decode preparation,
harvest, retirement, prefix insertion), ``DEVICE_WAITS`` are read-backs
that block until a device program ends.  ``req.first_token`` marks each
request's first token.  Intervals are ``(start, end)`` pairs in
nanoseconds, sorted and disjoint.

A program that puts none of these spans on the timeline (one older than
them) is read from the runtime's own events instead: host work is the
batch annotations' time outside the read-backs (``READBACKS``), a
dispatch is a run of one of the engine's programs (``PROGRAMS``), and a
batch's last first token is the end of its last prefill program.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from bench import trace

ENQUEUES = ("dispatch.prefill_chunk", "engine.decode_enqueue",
            "engine.verify_enqueue", "engine.prefill_enqueue")
HOST_PHASES = ("engine.admit", "engine.chunk_stage", "engine.prefix_insert",
               "engine.decode_prep", "engine.harvest",
               "engine.retire") + ENQUEUES
DEVICE_WAITS = ("wait.first_token", "wait.decode", "wait.verify",
                "wait.prefill")
FIRST_TOKEN = "req.first_token"
BATCH = "bench batch "                  # the harness's window marker

# The runtime's own events, for a program without the spans above: the
# engine's jitted programs (one device module per enqueue), those that
# prefill, and the host events of a device-to-host read.
PROGRAMS = ("_chunk_fwd", "_decode_n", "_verify_fwd", "_prefill_into")
PREFILLS = ("_chunk_fwd", "_prefill_into")
READBACKS = ("np.asarray(jax.Array)", "CommonPjRtBuffer::ToLiteral")

Intervals = List[Tuple[float, float]]


def named(host: Sequence[trace.Event], names: Sequence[str], lo: float,
          hi: float) -> List[trace.Event]:
    """The host events called one of ``names``, clipped to ``[lo, hi)``."""
    return trace.clip([e for e in host if e.name in names], lo, hi)


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """The intervals covered by both ``a`` and ``b``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            out.append((s, t))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(iv: Intervals) -> float:
    return sum(t - s for s, t in iv)


def has_spans(host: Sequence[trace.Event], lo: float, hi: float) -> bool:
    """Whether the program put its enqueue spans in ``[lo, hi)``."""
    return bool(named(host, ENQUEUES, lo, hi))


def host_work(host: Sequence[trace.Event], lo: float, hi: float
              ) -> Intervals:
    """Where in ``[lo, hi)`` the engine did host work: inside a host-phase
    span and outside every device wait (a wait nested in a phase, as a
    whole-prompt prefill's inside admission, counts as a wait).  Without
    the spans: inside a batch annotation and outside every read-back."""
    if has_spans(host, lo, hi):
        work = trace.union(named(host, HOST_PHASES, lo, hi))
        waits = named(host, DEVICE_WAITS, lo, hi)
    else:
        batches = [e for e in host if e.name.startswith(BATCH)]
        work = trace.union(trace.clip(batches, lo, hi))
        waits = named(host, READBACKS, lo, hi)
    return intersect(work, trace.gaps(waits, lo, hi))


def programs(tr: trace.Trace, names: Sequence[str]) -> List[trace.Event]:
    """Device module runs of the engine's jitted programs ``names``."""
    return [m for p in names for m in trace.modules_named(tr, p)]


def dispatches(tr: trace.Trace, lo: float, hi: float) -> int:
    """Program enqueues in ``[lo, hi)``: enqueue spans, or without them
    the runs of the engine's programs."""
    if has_spans(tr.host, lo, hi):
        return len(named(tr.host, ENQUEUES, lo, hi))
    return len(trace.clip(programs(tr, PROGRAMS), lo, hi))


def last_first_token(tr: trace.Trace, batch: trace.Event
                     ) -> Optional[float]:
    """When the batch's last first token came: the end of its last
    ``req.first_token`` span, or without the spans the end of the last
    prefill program that started inside it."""
    if has_spans(tr.host, batch.start, batch.end):
        ends = [e.end for e in tr.host if e.name == FIRST_TOKEN
                and batch.start <= e.start < batch.end]
    else:
        ends = [e.end for e in programs(tr, PREFILLS)
                if batch.start <= e.start < batch.end]
    return max(ends) if ends else None
