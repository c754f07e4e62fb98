"""Program spans (``repro.core.spans``) and the device window's readback
counters.

Off, the switch builds no annotation and leaves ``gc.callbacks`` alone.
On, under ``jax.profiler`` on the CPU backend, a device-placed NEXMark Q5
job with its hot-items stage leaves one submit -> convert -> emit chain
per answered window end, all carrying the same ``window_end``; without
that stage, the drain of its per-key results into the outbox is spanned.
"""

import gc
import pathlib

import jax
import numpy as np
import pytest

from repro.core import (CollectorSink, DeviceWindowProcessor, JetCluster,
                        JobConfig, PacedGeneratorSource, VirtualClock, spans)
from repro.core.engine import JOB_COMPLETED
from repro.core.sources import SCALAR_BURST_CUTOFF
from repro.nexmark import NexmarkGenerator, queries

BUCKETS, BATCH = 64, 256


def _q5_job(with_global_max=True, threads=1, total=8_000):
    rate = 40_000
    gen = NexmarkGenerator(rate=rate, n_keys=40)
    # 2 ms a step: 80 events due a step, bursts wide enough for blocks
    cluster = JetCluster(n_nodes=1, cooperative_threads=threads,
                         clock=VirtualClock(auto_step=0.002))
    out = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total),
        lambda: CollectorSink(out), window_ms=100, slide_ms=20,
        with_global_max=with_global_max, placement="device",
        device=dict(n_key_buckets=BUCKETS, batch_size=BATCH))
    job = cluster.submit(p.to_dag(), JobConfig())
    for _ in range(4_000_000):
        if job.status == JOB_COMPLETED:
            break
        cluster.step()
    assert job.status == JOB_COMPLETED
    return job, out


def _program_spans(logdir):
    """``(start ns, end ns, name, stats)`` of every ``jet.*`` host event."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("jet."):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name,
                                dict(ev.stats)))
    return out


@pytest.fixture
def spans_off():
    spans.disable()
    yield
    spans.disable()


def test_off_builds_no_annotation_and_leaves_gc_alone(monkeypatch,
                                                       spans_off):
    built = []

    class Counting:
        def __init__(self, name, **attrs):
            built.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(spans, "_Annotation", Counting)
    callbacks = list(gc.callbacks)
    _q5_job()
    gc.collect()
    assert built == []
    assert gc.callbacks == callbacks


def test_enable_and_disable_are_idempotent(spans_off):
    before = list(gc.callbacks)
    spans.enable()
    spans.enable()
    assert spans.on
    assert gc.callbacks.count(spans._on_gc) == 1
    spans.disable()
    spans.disable()
    assert not spans.on
    assert gc.callbacks == before


def test_every_answer_has_one_chain_with_its_window_end(tmp_path,
                                                        spans_off):
    jax.profiler.start_trace(str(tmp_path))
    spans.enable()
    try:
        job, out = _q5_job()
        gc.collect()
    finally:
        spans.disable()
        jax.profiler.stop_trace()
    evs = _program_spans(tmp_path)

    def named(name):
        return sorted((e for e in evs if e[2] == name), key=lambda e: e[0])

    answered = sorted(ev.value[0] for ev in out)
    assert len(answered) > 10 and len(set(answered)) == len(answered)
    emits = named("jet.hot_items.emit")
    assert sorted(e[3]["window_end"] for e in emits) == answered
    submits = [e for e in named("jet.bridge.submit") if "window_end" in
               e[3]]
    # one instance: each step that carries an end closes a higher one
    ends = [e[3]["window_end"] for e in submits]
    assert ends == sorted(set(ends))
    converts = named("jet.bridge.convert")
    for emit in emits:
        w = emit[3]["window_end"]
        conv = [c for c in converts
                if c[3]["window_end"] <= w <= c[3]["last_window_end"]]
        assert len(conv) == 1, (w, conv)
        sub = next(s for s in submits if s[3]["window_end"] >= w)
        assert sub[0] <= conv[0][0] <= emit[0], w

    # every count the device emitted reached the hot-items stage
    assert sum(c[3]["results"] for c in converts) == \
        sum(p[3]["items"] for p in named("jet.hot_items.process")) > 0
    # the source's blocks, each spanned once; a burst too small for a
    # block takes the scalar path, unspanned
    gens = named("jet.source.generate")
    assert 8_000 - SCALAR_BURST_CUTOFF < \
        sum(g[3]["events"] for g in gens) <= 8_000
    assert all(g[3]["lag_us"] >= 0 for g in gens)
    dev, = [t.processor for t in job.execution.tasklets
            if isinstance(t.processor, DeviceWindowProcessor)]
    assert sum(r[3]["bytes"] for r in named("jet.bridge.readback")) == \
        dev.telemetry()["readback_bytes"]
    collections = named("jet.gc")
    assert collections and {"generation", "collected"} <= \
        set(collections[-1][3])


@pytest.mark.parametrize("threads", [1, 2])
def test_flush_spans_carry_every_per_key_result(tmp_path, spans_off,
                                                monkeypatch, threads):
    """Per-key form: the ``results`` of ``jet.bridge.flush`` sum to
    ``key_results``, and a flush of an empty buffer opens no span."""
    real = DeviceWindowProcessor._flush_emit
    calls = []

    def flush(self):
        calls.append(len(self._emit_buf))
        return real(self)
    monkeypatch.setattr(DeviceWindowProcessor, "_flush_emit", flush)
    jax.profiler.start_trace(str(tmp_path))
    spans.enable()
    try:
        job, out = _q5_job(with_global_max=False, threads=threads)
    finally:
        spans.disable()
        jax.profiler.stop_trace()
    flushes = [e[3] for e in _program_spans(tmp_path)
               if e[2] == "jet.bridge.flush"]
    assert sum(f["results"] for f in flushes) == \
        job.execution.stats()["key_results"] == len(out) > 0
    assert all(f["results"] + f["left"] > 0 for f in flushes)
    # every call that found results opened one span, and the calls on an
    # empty buffer (the watermark path's) opened none
    assert len(flushes) == sum(1 for n in calls if n)
    assert 0 in calls


def test_readback_counters_count_each_harvested_step(spans_off,
                                                     monkeypatch):
    real = DeviceWindowProcessor._convert
    sliced = []

    def convert(self, out):
        valid = np.asarray(out["valid"])
        if valid.any():
            # the bucket that covers the last valid row
            last = int(np.nonzero(valid)[0][-1])
            sliced.append(min(1 << last.bit_length(), len(valid)))
        return real(self, out)
    monkeypatch.setattr(DeviceWindowProcessor, "_convert", convert)
    job, out = _q5_job(with_global_max=False)
    dev = [t.processor for t in job.execution.tasklets
           if isinstance(t.processor, DeviceWindowProcessor)]
    assert len(dev) == 1
    p = dev[0]
    rows = p._spec.emit_buffer_rows
    tel = p.telemetry()
    assert 0 < tel["emitting_steps"] == len(sliced) <= p.steps
    # every step's validity mask comes back; an emitting step adds the
    # window ends and result rows of its emitted prefix, rounded up
    assert tel["readback_bytes"] == \
        p.steps * rows + sum(s * 4 + s * BUCKETS * 4 for s in sliced)
    assert min(sliced) < rows
    stats = job.execution.stats()
    assert stats["readback_bytes"] == tel["readback_bytes"]
    assert stats["emitting_steps"] == tel["emitting_steps"]
    assert {ev.value.window_end for ev in out}



def test_stats_sums_each_instances_telemetry(spans_off):
    job, _ = _q5_job(with_global_max=False, threads=2)
    devs = [t.processor for t in job.execution.tasklets
            if isinstance(t.processor, DeviceWindowProcessor)]
    assert len(devs) == 2
    stats = job.execution.stats()
    for name in ("readback_bytes", "emitting_steps"):
        assert stats[name] == sum(p.telemetry()[name] for p in devs) > 0
    # a processor without counters of its own adds no key
    others = [t.processor for t in job.execution.tasklets
              if not isinstance(t.processor, DeviceWindowProcessor)]
    assert others and all(p.telemetry() == {} for p in others)
