"""Reduction of the program's own spans (``repro.core.spans``) in a
profiler trace to the per-layer metrics that read them.

The program annotates its layer boundaries with ``jet.*`` spans while
``repro.core.spans`` is on; each span is a host event of the trace, on the
device planes' clock, with its attributes as event stats.  This module
loads them (:func:`load`) and reduces them; the arithmetic works on plain
:class:`Ev` lists, so a test can hand-build a trace.  Times are
nanoseconds on the trace's clock.

Self time (a span's time minus what the spans nested in it cover, so a
collection inside a conversion counts as ``jet.gc``) comes from
:func:`flatten`, which cuts each thread's nested spans into pieces, each
owned by the innermost span open over it.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import trace as tracing

#: name prefix of the program's spans
PREFIX = "jet."


@dataclasses.dataclass(frozen=True)
class Ev:
    start: int
    end: int
    name: str
    attrs: Dict[str, Any]
    #: the thread (trace line) the span ran on
    line: int = 0


@dataclasses.dataclass
class ProgramTrace:
    """What one traced run holds of the program's spans."""
    #: the measured window
    lo: int
    hi: int
    #: every ``jet.*`` span of the trace, whole (not cut to the window)
    spans: List[Ev]


def load(xplane_path: str, window_span: str
         ) -> Tuple[List[Ev], Optional[Tuple[int, int]]]:
    """The ``jet.*`` host spans of one profile with their stats, and the
    interval of the host span named ``window_span`` (None if absent)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    spans: List[Ev] = []
    window = None
    line_no = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            for ev in line.events:
                name = ev.name
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if name.startswith(PREFIX):
                    spans.append(Ev(s, e, name, dict(ev.stats), line_no))
                elif name == window_span:
                    window = (s, e)
    return spans, window


# -- self time ---------------------------------------------------------------

def flatten(spans: Sequence[Ev]) -> List[tracing.Span]:
    """The spans cut into disjoint pieces, each named by the innermost
    span open over it (per thread).  A span that outlives the span it
    started in is cut at its parent's end."""
    pieces: List[tracing.Span] = []
    lines: Dict[int, List[Ev]] = {}
    for ev in spans:
        lines.setdefault(ev.line, []).append(ev)
    for evs in lines.values():
        evs.sort(key=lambda ev: (ev.start, -ev.end))
        stack: List[Tuple[int, str]] = []     # (end, name), innermost last
        t = 0
        for ev in evs:
            while stack and stack[-1][0] <= ev.start:
                end, name = stack.pop()
                if end > t:
                    pieces.append((t, end, name))
                    t = end
            if stack and ev.start > t:
                pieces.append((t, ev.start, stack[-1][1]))
            t = ev.start
            end = min(ev.end, stack[-1][0]) if stack else ev.end
            stack.append((end, ev.name))
        while stack:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
    return pieces


def self_ns(spans: Sequence[Ev], lo: int, hi: int) -> Dict[str, int]:
    """Self time per span name within ``[lo, hi)``."""
    out: Dict[str, int] = {}
    for s, e, n in tracing.clip(flatten(spans), lo, hi):
        out[n] = out.get(n, 0) + e - s
    return out


def self_share(spans: Sequence[Ev], prefix: str, lo: int, hi: int
               ) -> Optional[float]:
    """% of the window in the self time of spans named ``prefix``*."""
    if hi <= lo:
        return None
    own = self_ns(spans, lo, hi)
    return 100.0 * sum(v for n, v in own.items()
                       if n.startswith(prefix)) / (hi - lo)


def counts(spans: Sequence[Ev], lo: int, hi: int) -> Dict[str, int]:
    """Spans per name that start within ``[lo, hi)``; ``jet.gc`` also
    per generation, as ``jet.gc.gen<n>``."""
    out: Dict[str, int] = {}
    for ev in spans:
        if lo <= ev.start < hi:
            names = [ev.name]
            if ev.name == "jet.gc":
                names.append(f"jet.gc.gen{ev.attrs.get('generation')}")
            for n in names:
                out[n] = out.get(n, 0) + 1
    return dict(sorted(out.items()))


def label_gaps(gaps: Sequence[Tuple[int, int]], spans: Sequence[Ev],
               fallback: Sequence[str]) -> List[List[Any]]:
    """``[label, seconds, share]`` per gap: the span name whose self time
    covers most of the gap, and the share of the gap it covers; where no
    span covers any of it, the gap's label in ``fallback`` (the vertex
    whose tasklet call covered it) and 0."""
    pieces = flatten(spans)
    out = []
    for (s, e), other in zip(gaps, fallback):
        cover: Dict[str, int] = {}
        for ps, pe, n in tracing.clip(pieces, s, e):
            cover[n] = cover.get(n, 0) + pe - ps
        name = max(cover, key=cover.get) if cover else other
        out.append([name, (e - s) / 1e9, cover.get(name, 0) / (e - s)])
    return out


# -- readers -----------------------------------------------------------------

def _named(spans: Sequence[Ev], name: str) -> List[Ev]:
    return [ev for ev in spans if ev.name == name]


def source_lag_ms(pt: ProgramTrace) -> Optional[float]:
    """Highest lag of the source behind its schedule, over the blocks it
    generated in the window."""
    lags = [ev.attrs["lag_us"] for ev in _named(pt.spans,
                                                 "jet.source.generate")
            if pt.lo <= ev.start < pt.hi]
    return max(lags) / 1000.0 if lags else None


def gc_pause_ms(pt: ProgramTrace) -> Optional[float]:
    """Time in garbage collections within the window."""
    if pt.hi <= pt.lo:
        return None
    gcs = _named(pt.spans, "jet.gc")
    return sum(e - s for s, e, _ in tracing.clip(
        [(ev.start, ev.end, ev.name) for ev in gcs], pt.lo, pt.hi)) / 1e6


def answer_chains(pt: ProgramTrace) -> Dict[int, Dict[str, Any]]:
    """Per window end answered in the window (its ``jet.hot_items.emit``
    ends there): that emit, the first ``jet.bridge.submit`` of any
    instance whose watermark closed the end, and the
    ``jet.bridge.convert`` spans that turned its counts into results."""
    submits = sorted((ev for ev in _named(pt.spans, "jet.bridge.submit")
                      if "window_end" in ev.attrs), key=lambda ev: ev.start)
    converts = _named(pt.spans, "jet.bridge.convert")
    out: Dict[int, Dict[str, Any]] = {}
    for emit in _named(pt.spans, "jet.hot_items.emit"):
        if not pt.lo <= emit.end < pt.hi:
            continue
        w = emit.attrs["window_end"]
        first = next((ev for ev in submits if ev.attrs["window_end"] >= w
                      and ev.start <= emit.start), None)
        out[w] = {"emit": emit, "submit": first, "converts": [
            ev for ev in converts
            if ev.attrs["window_end"] <= w <= ev.attrs["last_window_end"]
            and ev.end <= emit.start]}
    return out


def answer_path_ms(pt: ProgramTrace) -> Optional[float]:
    """Median, over the window ends answered in the window, of the time
    from the first step that closed the end to the answer's offer."""
    paths = [(c["emit"].end - c["submit"].start) / 1e6
             for c in answer_chains(pt).values() if c["submit"] is not None]
    return statistics.median(paths) if paths else None


def readback_bytes_per_step(counters: Optional[Dict[str, int]]
                            ) -> Optional[float]:
    """Bytes copied to the host per emitting step, from the counters of
    ``ExecutionContext.stats()`` differenced over the window."""
    if not counters or counters.get("emitting_steps", 0) <= 0:
        return None
    return counters["readback_bytes"] / counters["emitting_steps"]


#: metric name -> its reader over a :class:`ProgramTrace`
METRICS = {
    "source_lag_ms": source_lag_ms,
    "gc_pause_ms": gc_pause_ms,
    "answer_path_ms": answer_path_ms,
    "bridge_pack_share": lambda pt: self_share(
        pt.spans, "jet.bridge.submit", pt.lo, pt.hi),
    "bridge_readback_share": lambda pt: self_share(
        pt.spans, "jet.bridge.readback", pt.lo, pt.hi),
    "bridge_convert_share": lambda pt: self_share(
        pt.spans, "jet.bridge.convert", pt.lo, pt.hi),
    "hot_items_share": lambda pt: self_share(
        pt.spans, "jet.hot_items.", pt.lo, pt.hi),
}
