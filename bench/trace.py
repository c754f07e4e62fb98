"""Reduction of a profiler trace to device busy time, idle gaps and step
times.

Times are nanoseconds on the trace's clock.  ``Trace`` holds what the
reduction reads: per chip, the device operations and the executions of
compiled programs; and the host spans the harness recorded.  The loader
takes them from the ``.xplane.pb`` that ``jax.profiler`` writes; the
arithmetic below works on plain ``(start, end, name)`` lists, so a test
can hand-build a trace.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

Span = Tuple[int, int, str]

#: trace line of a device plane that holds one event per XLA operation
OPS_LINE = "XLA Ops"
#: trace line of a device plane that holds one event per program execution
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    #: chip -> device operations
    ops: Dict[str, List[Span]]
    #: chip -> executions of compiled programs
    modules: Dict[str, List[Span]]
    #: host spans (harness annotations), on the same clock
    host: List[Span]


def load(xplane_path: str) -> Trace:
    """Read the planes of one profile written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    ops: Dict[str, List[Span]] = {}
    modules: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name in (OPS_LINE, MODULES_LINE):
                dst = (ops if line.name == OPS_LINE else modules
                       ).setdefault(plane.name, [])
            elif plane.name.startswith("/host:"):
                dst = host
            else:
                continue
            for ev in line.events:
                start = int(ev.start_ns)
                name = short_name(ev.name) if on_device else ev.name
                dst.append((start, start + int(ev.duration_ns), name))
    return Trace(ops=ops, modules=modules, host=host)


def short_name(hlo: str) -> str:
    """An operation's HLO text cut to its name, result shape and opcode:
    ``%fusion.1 = f32[8323072]{0:T(1024)} fusion(...), kind=...`` becomes
    ``fusion.1 = f32[8323072] fusion``."""
    text = re.sub(r"\{[^{}]*\}", "", hlo).lstrip("%")
    m = re.match(r"(\S+) = (\(.*?\)|\S+) ([\w-]+)\(", text)
    return " ".join(m.groups()[:1] + ("=",) + m.groups()[1:]) if m \
        else text[:80]


def clip(spans: List[Span], lo: int, hi: int) -> List[Span]:
    """Spans cut to the window ``[lo, hi)``; those outside it dropped."""
    return [(max(s, lo), min(e, hi), n) for s, e, n in spans
            if e > lo and s < hi]


def merge(spans: List[Span]) -> List[Tuple[int, int]]:
    """Union of the spans' intervals, as sorted disjoint intervals."""
    out: List[List[int]] = []
    for s, e, _ in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(spans: List[Span], lo: int, hi: int) -> int:
    """Time within ``[lo, hi)`` in which at least one span runs."""
    return sum(e - s for s, e in merge(clip(spans, lo, hi)))


def idle_gaps(spans: List[Span], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The intervals of ``[lo, hi)`` in which no span runs."""
    gaps, t = [], lo
    for s, e in merge(clip(spans, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def label(gap: Tuple[int, int], host: List[Span]) -> str:
    """The host span that covers most of the gap ("host.other" where no
    recorded span does)."""
    s, e = gap
    best, name = 0, "host.other"
    for hs, he, hn in host:
        cover = min(e, he) - max(s, hs)
        if cover > best:
            best, name = cover, hn
    return name


def op_seconds(spans: List[Span], lo: int, hi: int) -> Dict[str, float]:
    """Device seconds per operation name within the window."""
    out: Dict[str, float] = {}
    for s, e, n in clip(spans, lo, hi):
        out[n] = out.get(n, 0.0) + (e - s) / 1e9
    return out


def executions(spans: List[Span], prefix: str, lo: int, hi: int
               ) -> List[Span]:
    """Executions of the programs whose name starts with ``prefix`` that
    start inside the window (whole, not cut)."""
    return [sp for sp in spans if sp[2].startswith(prefix) and lo <= sp[0] < hi]
