"""The per-key cell, ``q5-jet-keys.saturated``, on the CPU at a tiny size:
Q5's first stage with every count per (window end, auction) delivered to
the sink and compared one by one with the plain reference."""

import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_cells import run, tiny

CELL = "q5-jet-keys.saturated"


def _received(line):
    """``{(window end, auction): count}`` the sink received up to the due
    horizon, and how many results that was."""
    cols = line["_cols"]
    due = cols["end"] <= line["_due"]
    pairs = list(zip(cols["end"][due].tolist(), cols["key"][due].tolist()))
    return dict(zip(pairs, cols["value"][due].tolist())), len(pairs)


@pytest.fixture(scope="module")
def clean():
    """One run of the tiny cell, unaltered: ``(cell, result line)``."""
    cell = tiny(CELL)
    return cell, run(cell)


def test_cell_delivers_every_due_count_once(clean):
    cell, line = clean
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    slide = cell.config["slide_ms"]
    totals = line["_totals"][:line["_due"] // slide]
    rows, keys = np.nonzero(totals)
    want = {((r + 1) * slide, k): totals[r, k]
            for r, k in zip(rows.tolist(), keys.tolist())}
    got, n = _received(line)
    assert len(want) > 100
    assert got == want and n == len(want)
    assert line["attempted"] >= n


def _convert_then(monkeypatch, alter):
    """The bridge's conversion, with ``alter(events)`` applied to the
    results each emitting step adds to the buffer."""
    from repro.core.device_window import DeviceWindowProcessor
    real = DeviceWindowProcessor._convert

    def convert(self, out):
        n = len(self._emit_buf)
        real(self, out)
        added = list(self._emit_buf)[n:]
        if added:
            for _ in added:
                self._emit_buf.pop()
            self._emit_buf.extend(alter(added))
    monkeypatch.setattr(DeviceWindowProcessor, "_convert", convert)


def test_a_dropped_result_is_missing(monkeypatch):
    # each emitting step loses its first bucket's result
    _convert_then(monkeypatch, lambda evs: evs[1:])
    line = run(tiny(CELL))
    assert not line["correct"]
    assert line["checks"]["missing"]["value"] > 0


def test_a_count_off_by_one_is_a_wrong_value(monkeypatch):
    altered = []

    def plus_one(evs):
        if not altered:
            evs[0].value.value += 1
            altered.append(evs[0].value)
        return evs
    _convert_then(monkeypatch, plus_one)
    line = run(tiny(CELL))
    assert altered
    assert not line["correct"]
    assert line["checks"]["wrong_values"]["value"] == 1


def test_cell_reads_its_own_per_layer_metrics():
    names = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert names == {"window_vertex_share.keys", "sink_vertex_share.keys",
                     "device_idle_share.keys", "step_device_ms.keys",
                     "step_roofline.keys"}


@pytest.mark.parametrize("name", ["window_vertex_share.keys",
                                  "sink_vertex_share.keys"])
def test_engine_shares_read_the_sampled_vertex_time(clean, name):
    cell, line = clean
    value = harness.read_metric(cell.root, name, line["_obs"])
    assert 0 < value < 100
