"""The reduction of the program's spans on hand-built traces and on one
recorded on the CPU, and the probe's counters over a window."""

import gc
import time

import pytest

from bench import harness, program_spans as ps, spans_probe
from bench.tests.test_bench_cells import SEED, tiny
from repro.streaming import VectorWindowSpec


def ev(s, e, name, line=0, **attrs):
    return ps.Ev(s, e, name, attrs, line)


def test_self_time_leaves_a_nested_collection_to_the_collector():
    spans = [ev(0, 100, "jet.bridge.convert"),
             ev(30, 50, "jet.gc", generation=2),
             ev(100, 130, "jet.bridge.submit"),
             ev(110, 120, "jet.gc", generation=0),
             # another thread: nests on its own
             ev(10, 60, "jet.hot_items.process", line=1)]
    assert ps.flatten(spans[:4]) == [
        (0, 30, "jet.bridge.convert"), (30, 50, "jet.gc"),
        (50, 100, "jet.bridge.convert"), (100, 110, "jet.bridge.submit"),
        (110, 120, "jet.gc"), (120, 130, "jet.bridge.submit")]
    assert ps.self_ns(spans, 0, 200) == {
        "jet.bridge.convert": 80, "jet.gc": 30, "jet.bridge.submit": 20,
        "jet.hot_items.process": 50}
    # cut to the window [40, 115)
    assert ps.self_ns(spans, 40, 115) == {
        "jet.bridge.convert": 50, "jet.gc": 15, "jet.bridge.submit": 10,
        "jet.hot_items.process": 20}
    assert ps.self_share(spans, "jet.bridge.convert", 0, 200) == 40.0
    assert ps.self_share(spans, "jet.hot_items.", 0, 200) == 25.0
    pt = ps.ProgramTrace(0, 200, spans)
    assert ps.gc_pause_ms(pt) == 30 / 1e6
    assert ps.METRICS["bridge_pack_share"](pt) == 10.0


def test_a_span_that_outlives_its_parent_is_cut_at_its_end():
    spans = [ev(0, 50, "jet.source.generate"), ev(40, 70, "jet.gc")]
    assert ps.flatten(spans) == [(0, 40, "jet.source.generate"),
                                 (40, 50, "jet.gc")]


def test_answer_path_runs_from_the_first_step_closing_the_end():
    spans = [
        # two instances' steps; the first to carry end 2000 is at 100
        ev(100, 110, "jet.bridge.submit", window_end=2000),
        ev(105, 112, "jet.bridge.submit", window_end=2000, line=1),
        ev(150, 160, "jet.bridge.convert", window_end=2000,
           last_window_end=2000, results=9),
        ev(300, 310, "jet.hot_items.emit", window_end=2000),
        # end 4000 closed by a step carrying a higher end
        ev(1000, 1010, "jet.bridge.submit", window_end=6000),
        ev(1020, 1040, "jet.bridge.convert", window_end=4000,
           last_window_end=6000, results=9),
        ev(1100, 1200, "jet.hot_items.emit", window_end=4000),
        ev(1201, 1300, "jet.hot_items.emit", window_end=6000),
        # answered after the window closes: not counted
        ev(5000, 5010, "jet.hot_items.emit", window_end=8000),
    ]
    pt = ps.ProgramTrace(0, 2000, spans)
    chains = ps.answer_chains(pt)
    assert sorted(chains) == [2000, 4000, 6000]
    assert chains[2000]["submit"].start == 100
    assert [c.start for c in chains[6000]["converts"]] == [1020]
    # 210, 200 and 300 ns
    assert ps.answer_path_ms(pt) == pytest.approx(210 / 1e6)
    assert ps.answer_path_ms(ps.ProgramTrace(0, 50, spans)) is None


def test_source_lag_is_the_highest_in_the_window():
    spans = [ev(5, 6, "jet.source.generate", events=4096, lag_us=900),
             ev(20, 21, "jet.source.generate", events=4096, lag_us=123280),
             ev(40, 41, "jet.source.generate", events=4096, lag_us=7000),
             ev(90, 91, "jet.source.generate", events=4096, lag_us=999999)]
    assert ps.source_lag_ms(ps.ProgramTrace(10, 50, spans)) == \
        123.28
    assert ps.source_lag_ms(ps.ProgramTrace(50, 60, spans)) is None


def test_counts_take_the_spans_that_start_in_the_window():
    spans = [ev(0, 10, "jet.bridge.submit"),
             ev(5, 8, "jet.gc", generation=0),
             ev(20, 90, "jet.gc", generation=2),
             ev(30, 40, "jet.gc", generation=0, line=1),
             ev(95, 130, "jet.bridge.submit")]
    assert ps.counts(spans, 5, 100) == {
        "jet.bridge.submit": 1, "jet.gc": 3, "jet.gc.gen0": 2,
        "jet.gc.gen2": 1}
    assert ps.counts(spans, 200, 300) == {}


def test_gaps_take_the_covering_span_else_the_vertex_label():
    spans = [ev(0, 100, "jet.source.generate"),
             ev(20, 90, "jet.gc", generation=2),
             ev(200, 240, "jet.bridge.convert"),
             ev(250, 260, "jet.hot_items.emit", window_end=2000)]
    gaps = [(0, 100), (190, 280), (400, 500)]
    fallback = ["bids-0_rekey-2", "win_agg_dev-3.device", "sink-6"]
    assert ps.label_gaps(gaps, spans, fallback) == [
        ["jet.gc", 100e-9, 0.7], ["jet.bridge.convert", 90e-9, 40 / 90],
        ["sink-6", 100e-9, 0.0]]


def test_readback_per_step_needs_an_emitting_step():
    assert ps.readback_bytes_per_step(
        {"readback_bytes": 300, "emitting_steps": 2}) == 150
    assert ps.readback_bytes_per_step(
        {"readback_bytes": 300, "emitting_steps": 0}) is None
    assert ps.readback_bytes_per_step(None) is None


def test_probe_differences_the_counters_over_the_window():
    cell = tiny("q5-jet.saturated")
    before = (harness._vertex_seconds, harness._reduce_trace)
    out = spans_probe.probe(cell, SEED, 1.5, "off", time.monotonic(),
                            require_tpu=False)
    assert (harness._vertex_seconds, harness._reduce_trace) == before
    assert out["correct"] and set(out["end_to_end"]) == {
        "events_per_s", "setup_s"}
    c = out["counters"]
    assert c["emitting_steps"] > 0
    # an emitting step reads back the window ends and results of every
    # emission row; each step, emitting or not, its validity mask
    buckets = 64
    rows = VectorWindowSpec(size_ms=1000, slide_ms=200,
                            n_key_buckets=buckets, max_windows_per_step=8,
                            ring_margin=8).emit_buffer_rows
    per_emit = rows * 4 + rows * buckets * 4
    assert per_emit + rows <= c["readback_bytes_per_step"] < 2 * per_emit
    assert "program" not in out


def test_load_reads_the_spans_of_a_recorded_trace(tmp_path):
    import jax
    from repro.core import spans
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            spans.enable()
            try:
                with spans.span("jet.bridge.convert", window_end=2000,
                                last_window_end=2000, results=7):
                    gc.collect()
            finally:
                spans.disable()
    finally:
        jax.profiler.stop_trace()
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    evs, window = ps.load(path, harness.WINDOW_SPAN)
    conv, = [e for e in evs if e.name == "jet.bridge.convert"]
    assert conv.attrs == {"window_end": 2000, "last_window_end": 2000,
                          "results": 7}
    coll = [e for e in evs if e.name == "jet.gc"
            and e.attrs["generation"] == 2]
    assert coll and "collected" in coll[-1].attrs
    assert conv.start <= coll[-1].start and coll[-1].end <= conv.end
    assert window[0] <= conv.start and conv.end <= window[1]
    own = ps.self_ns(evs, *window)
    assert own["jet.bridge.convert"] + own["jet.gc"] >= conv.end - conv.start
    assert own["jet.gc"] >= coll[-1].end - coll[-1].start
