"""Whole benchmark runs on the CPU at a tiny size, through the harness's
internal entry (which skips only the look for a chip)."""

import hashlib
import itertools
import json
import pathlib
import shutil
import time

import numpy as np
import pytest

from bench import harness
from bench.control import control_numbers

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 99


def tiny(name, root=ROOT, n_auctions=50, **config):
    """The cell at a size the CPU runs in a second: few keys, small device
    batches and a window of 1 s by 200 ms."""
    cell = harness.load_cell(name, root)
    cell.config = dict(cell.config, n_auctions=n_auctions,
                       device={"n_key_buckets": 64, "batch_size": 256},
                       window_ms=1000, slide_ms=200, **config)
    cell.traffic = dict(cell.traffic, rate=20_000, warmup_s=0.5)
    return cell


def run(cell, trace=False, seconds=1.5):
    return harness.run(cell, SEED, seconds, trace, time.monotonic(),
                       require_tpu=False)


@pytest.mark.parametrize("name", ["q5-hop.paced", "q5-jet.saturated"])
def test_cell_runs_and_is_correct(name):
    line = run(tiny(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in
                                    harness.load_cell(name).end_to_end}
    assert line["device"]["platform"] == "cpu"
    assert [k for k in line if not k.startswith("_")][-1] == "checks"


def test_control_fails_where_the_program_passes():
    # 5 auctions at 20k events/s: window totals far above bf16's 256
    line = run(tiny("q5-hop.paced", n_auctions=5))
    assert line["correct"]
    assert min(line["_totals"][-1]) > 256
    ctrl = control_numbers(line)
    assert ctrl["wrong_values"] > 0, ctrl


def _state_unchanged(monkeypatch):
    from repro.streaming.executor import StreamExecutor

    def step(self, state, batch, valid_count=None):
        spec = self.cfg.window
        rows = spec.emit_buffer_rows
        return state, {"results": np.zeros((rows, spec.n_key_buckets),
                                           np.float32),
                       "window_ends": np.zeros(rows, np.int32),
                       "valid": np.zeros(rows, bool)}
    monkeypatch.setattr(StreamExecutor, "step", step)


def _half_batch(monkeypatch):
    from repro.streaming.executor import StreamExecutor
    real = StreamExecutor.stage_batch

    def stage(self, batch):
        valid = np.array(batch["valid"])
        valid[len(valid) // 2:] = False
        return real(self, dict(batch, valid=valid))
    monkeypatch.setattr(StreamExecutor, "stage_batch", stage)


def _count_altered(monkeypatch):
    from repro.core.device_window import DeviceWindowProcessor
    real = DeviceWindowProcessor._convert

    def convert(self, out):
        n = len(self._emit_buf)
        real(self, out)
        for ev in itertools.islice(self._emit_buf, n, None):
            ev.value.value += 1
    monkeypatch.setattr(DeviceWindowProcessor, "_convert", convert)


def _answer_altered(monkeypatch):
    from repro.nexmark.queries import MaxPerWindowProcessor
    real = MaxPerWindowProcessor.try_process_watermark

    def on_watermark(self, wm):
        seen = self.__dict__.setdefault("_altered", set())
        for w, (key, count) in list(self.best.items()):
            if w not in seen:
                seen.add(w)
                self.best[w] = (key, count + 1)
        return real(self, wm)
    monkeypatch.setattr(MaxPerWindowProcessor, "try_process_watermark",
                        on_watermark)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _count_altered, _answer_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    line = run(tiny("q5-hop.paced"))
    assert not line["correct"]
    assert line["failed"] > 0


def _tree_digest(path):
    h = hashlib.sha256()
    for p in sorted(path.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(path)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def test_new_config_traffic_and_metric_are_files_and_entries(tmp_path):
    before = _tree_digest(ROOT / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp_path / "bench" / sub)
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "q5-hop.json").read_text())
    cfg.update(name="q5-tumble", window_ms=400, slide_ms=400)
    (b / "configs" / "q5-tumble.json").write_text(json.dumps(cfg))
    (b / "traffic" / "trickle.json").write_text(json.dumps(
        {"rate": 5000, "warmup_s": 0.3, "due": "schedule"}))
    (b / "metrics" / "device_steps_per_s.py").write_text(
        "def read(obs):\n    return obs.steps / obs.seconds\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "q5-tumble", "source": "test",
                            "file": "bench/configs/q5-tumble.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "q5-tumble.trickle",
                              "config": "q5-tumble", "traffic": "trickle",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "device_steps_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["q5-tumble.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("q5-tumble.trickle", tmp_path)
    cell.config["device"] = {"n_key_buckets": 64, "batch_size": 256}
    cell.config["n_auctions"] = 50
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["metrics"]["device_steps_per_s"]["value"] > 0
    assert "setup_s" in line["metrics"]
    assert "latency_p50_ms" not in line["metrics"]
    assert _tree_digest(ROOT / "bench") == before
