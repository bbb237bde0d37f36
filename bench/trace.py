"""Reduction of a profiler trace to device intervals, kernels and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
records: the device's op and module events (``/device:TPU:<n>`` planes,
lines ``XLA Ops`` and ``XLA Modules``) and the host's events (every line of
``/host:CPU``: the runtime's own activity, with the harness's
``TraceAnnotation`` around each batch among them).  All times are nanoseconds
on the trace's one timeline.

Kernels are told apart by the operand signature the TPU trace prints for a
Pallas call (``custom_call_target="tpu_custom_call"``), since the trace
carries no kernel name.  Each kernel the benchmark names has a file
``bench/kernels/<kernel>.json`` whose ``signature`` is a regular expression
over the call's operands written as ``dtype:rank`` words (``s32:1 s32:2
bf16:4 bf16:4 bf16:4`` for the paged decode kernel); a kernel is added by
adding a file.  A Pallas call that no signature matches reads
``pallas_call`` (``UNNAMED``), and a run counts such calls as a fault of
the yardstick; one that two signatures match raises.  An XLA op reads as
its opcode or fusion kind.  Loop and call ops (``while``, ``conditional``,
``call``) contain other ops and are left out of busy time.
"""

from __future__ import annotations

import functools
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

CONTAINERS = ("while", "conditional", "call")
UNNAMED = "pallas_call"
KERNELS = Path(__file__).parent / "kernels"
_LAYOUT = "operand_layout_constraints={"
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPCODE = re.compile(r"^%?([\w.-]+) = .*?\}?\s([\w-]+)\(")


@dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)       # device, leaf ops
    modules: List[Event] = field(default_factory=list)   # device programs
    host: List[Event] = field(default_factory=list)      # python events
    devices: int = 0


def _operands(name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, shape) of each operand in a custom call's layout list."""
    at = name.find(_LAYOUT)
    if at < 0:
        return []
    at += len(_LAYOUT)
    depth, end = 1, at
    while end < len(name) and depth:
        depth += {"{": 1, "}": -1}.get(name[end], 0)
        end += 1
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(name[at:end - 1])]


@functools.lru_cache(maxsize=None)
def signatures() -> Tuple[Tuple[str, "re.Pattern"], ...]:
    """(kernel, compiled signature) of every ``bench/kernels/*.json``."""
    return tuple((f.stem, re.compile(json.loads(f.read_text())["signature"]))
                 for f in sorted(KERNELS.glob("*.json")))


def kernel_of(operands: Sequence[Tuple[str, Tuple[int, ...]]]) -> str:
    """The kernel whose signature matches a Pallas call's operands."""
    words = " ".join(f"{dt}:{len(s)}" for dt, s in operands)
    hits = [k for k, rx in signatures() if rx.search(words)]
    if len(hits) > 1:
        raise ValueError(f"operands {words!r} match kernels {hits}")
    return hits[0] if hits else UNNAMED


def op_kind(name: str) -> str:
    """Kernel or op kind of one ``XLA Ops`` event name (its HLO text)."""
    if 'custom_call_target="tpu_custom_call"' in name:
        return kernel_of(_operands(name))
    m = _OPCODE.match(name)
    if not m:
        return name.split(" ", 1)[0].lstrip("%")
    inst, opcode = m.groups()
    if opcode == "fusion":
        return re.sub(r"\.\d+$", "", inst)
    return opcode


def load(path: str) -> Trace:
    """Device ops, device modules and host Python events of one trace."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tr.devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops += [Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events
                               if op_kind(e.name) not in CONTAINERS]
                elif line.name == "XLA Modules":
                    tr.modules += [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host += [Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
    return tr


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` inside ``[lo, hi)``."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union(events))


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of ``[lo, hi)`` between the device's ops."""
    out, at = [], lo
    for a, b in union(events):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def host_label(host: Sequence[Event], lo: float, hi: float) -> str:
    """What the host was doing over a gap: the innermost Python event that
    covers the gap's midpoint (the latest-starting one that contains it)."""
    mid = 0.5 * (lo + hi)
    best: Optional[Event] = None
    for e in host:
        if e.start <= mid < e.end and (best is None or e.start >= best.start):
            best = e
    return best.name if best is not None else "(no host event)"


def time_by_kind(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in events:
        k = op_kind(e.name)
        out[k] = out.get(k, 0.0) + e.dur
    return out


def inside(events: Sequence[Event], spans: Sequence[Event]) -> List[Event]:
    """``events`` that start inside one of ``spans``."""
    iv = sorted((s.start, s.end) for s in spans)
    out, j = [], 0
    for e in sorted(events, key=lambda e: e.start):
        while j < len(iv) and iv[j][1] <= e.start:
            j += 1
        if j < len(iv) and iv[j][0] <= e.start:
            out.append(e)
    return out


def modules_named(tr: Trace, program: str) -> List[Event]:
    """Module events of the jitted function ``program`` (``jit_<name>``)."""
    return [m for m in tr.modules
            if m.name.split("(", 1)[0] == f"jit_{program}"]
