"""Program spans: the engine's own annotations in a ``jax.profiler`` trace.

One process-wide switch, off by default.  While it is on, the call sites
below open a ``jax.profiler.TraceAnnotation`` around their work, and a
``gc.callbacks`` hook opens one around every garbage collection.  An
annotation lands in the profiler's host plane, on the device planes'
clock, so a span and the device operations it waits on line up with no
offset arithmetic; its keyword attributes ride as TraceMe metadata (stats
of the event in ``jax.profiler.ProfileData``).  This module keeps no
buffer of its own: the profiler holds the events and writes them when the
trace stops, and with no trace running an annotation records nothing.

Turning spans on around one's own trace::

    import jax
    from repro.core import spans

    jax.profiler.start_trace(logdir)
    spans.enable()
    ...                       # step the cluster
    spans.disable()
    jax.profiler.stop_trace()

Cost: an annotation costs about a microsecond even with no trace running,
so every call site tests :data:`on` first and builds nothing when it is
off (the shared :data:`OFF` stands in for the span).  Sites are per
batch, per device step or per window end, never per event or per row.
The switch is per process: worker processes of the multi-process backend
keep their own.

Spans (every span of one window end carries the same ``window_end``):

========================  ===============================================
``jet.source.generate``   ``PacedGeneratorSource`` block path: one
                          generated block (``events``, ``lag_us``: how far
                          the source ran behind its schedule)
``jet.bridge.submit``     ``DeviceWindowProcessor._submit``: pad or copy,
                          stage, dispatch (``rows``, ``wm``, and
                          ``window_end`` on the first step whose watermark
                          closes that end)
``jet.bridge.readback``   the host copies of a step's output (``bytes``)
``jet.bridge.convert``    the loop that turns counts into results
                          (``results``, ``window_end`` and
                          ``last_window_end``)
``jet.bridge.flush``      ``DeviceWindowProcessor._flush_emit``, opened
                          only while results wait: moving them into the
                          outbox (``results`` moved, ``left`` still
                          buffered where the outbox refused)
``jet.hot_items.process`` ``MaxPerWindowProcessor.process`` (``items``)
``jet.hot_items.emit``    one answer offered (``window_end``)
``jet.gc``                a garbage collection (``generation``,
                          ``collected``)
========================  ===============================================
"""

from __future__ import annotations

import gc
from typing import Any, Optional

#: the switch every call site tests before it builds a span
on = False

_Annotation: Any = None
#: the ``jet.gc`` span of the collection in progress
_gc_span: Optional[Any] = None


class _Off:
    """The span of a call site while spans are off: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


#: shared stand-in for a span while spans are off
OFF = _Off()


def span(name: str, **attrs):
    """A ``TraceAnnotation`` named ``name``; call only while :data:`on`."""
    return _Annotation(name, **attrs)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = _Annotation("jet.gc", generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.set_metadata(collected=info["collected"])
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def enable() -> None:
    """Turn spans on, and the garbage-collection hook with them."""
    global on, _Annotation
    if on:
        return
    from jax.profiler import TraceAnnotation
    _Annotation = TraceAnnotation
    gc.callbacks.append(_on_gc)
    on = True


def disable() -> None:
    """Turn spans off and remove the garbage-collection hook."""
    global on
    if not on:
        return
    on = False
    gc.callbacks.remove(_on_gc)
