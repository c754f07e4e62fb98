"""One run of one benchmark cell: set up, measure, check, report.

Everything that belongs to one configuration, traffic mix or metric is a
file the harness finds by the name ``BENCHMARK.json`` gives it:

* ``configs[].file``: the deployment (query, window, keys, guarantee,
  layout, device sizes); its ``query`` names ``bench/queries/<query>.py``,
  which builds the job in the program, says what the sink reads from each
  item, and holds the comparison and the plain reference;
* ``bench/traffic/<traffic>.json``: the offered load (rate, warm-up, the
  lag behind the schedule under which the pipeline counts as settled, and
  which answers are due when the window closes);
* ``bench/metrics/<metric>.py``: a reader ``read(obs) -> float | None`` of
  one metric from the :class:`Observation` of the run.

A run (``bench/run.py`` has enabled the compile cache): the chip is
checked, the cell's job is submitted on a wall-clock ``JetCluster`` (the
device vertices compile and warm their step in ``init``), the traffic
warms up until the pipeline has settled, the window is measured, the answers due are drained, device
memory is read, the job is torn down, and every item received is compared
with the reference.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import check, trace as tracing
from .feed import Feed
from .nexmark import NexmarkStream, bids_between
from .sink import ResultColumns, make_sink

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: how long after the window closes the harness waits for answers due
DRAIN_LIMIT_S = 60.0
#: host spans shorter than this are not kept in a traced run
SPAN_MIN_NS = 20_000
#: seconds a traced run runs between starting the profiler and the window
TRACE_SETTLE_S = 1.0
#: longest a warm-up waits, past ``warmup_s``, for the pipeline to settle
SETTLE_LIMIT_S = 60.0
#: host annotation that marks the measured window in the trace
WINDOW_SPAN = "bench.window"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r}; known: {sorted(workloads)}")
    wl = workloads[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[wl["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, wl, config, traffic, e2e, per_layer, root)


def read_metric(root: pathlib.Path, name: str, obs: "Observation"):
    """Run ``bench/metrics/<name>.py``'s reader over the observation."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


@dataclasses.dataclass
class TraceSummary:
    """The traced window, reduced (one chip's numbers averaged over the
    chips used)."""
    window_s: float
    busy_s: float
    op_seconds: Dict[str, float]
    #: (start ns, end ns, name) of each step-program execution in the window
    steps: List[tracing.Span]
    #: the ten longest idle gaps: [host span they fell in, seconds]
    longest_gaps: List[List[Any]]


@dataclasses.dataclass
class Observation:
    """What a run saw, for the metric readers."""
    cell: Cell
    device_kind: str
    setup_s: float
    #: wall times: the schedule's anchor and the window's open and close
    anchor: float
    t_open: float
    t_close: float
    #: measured window, wall seconds
    seconds: float
    #: latency (ms) of every result due in the window, where the traffic
    #: makes answers due by the schedule; else None
    latency_ms: Optional[np.ndarray]
    #: NEXMark events, and bids among them, whose event time the sink's
    #: watermark passed during the window
    events: int
    bids: int
    #: window ends the sink's watermark passed during the window
    window_ends: int
    #: device steps dispatched during the window (all instances)
    steps: int
    #: sampled worker seconds per vertex during the window
    vertex_seconds: Dict[str, float]
    #: (2, n): wall time and lag (ms) of each generator call in the window
    gen_lag: np.ndarray
    trace: Optional[TraceSummary] = None


def _vertex_seconds(cluster) -> Dict[str, float]:
    """Sampled worker seconds per vertex so far, from each cooperative
    worker's ``hot_tasklets`` (the timing ``vertex_time_share`` sums)."""
    out: Dict[str, float] = {}
    for node in cluster.nodes.values():
        for worker in node.workers:
            for name, secs, _ in worker.hot_tasklets(len(worker.tasklets)):
                vertex = name.rsplit("#", 1)[0]
                out[vertex] = out.get(vertex, 0.0) + secs
    return out


def _run_until(cluster, job, deadline: float) -> None:
    from repro.core import JOB_FAILED
    step, clock = cluster.step, time.monotonic
    while clock() < deadline:
        for _ in range(16):
            step()
        if job.status == JOB_FAILED:
            raise RuntimeError("the benchmark job failed")


def _settle(cluster, job, cols: ResultColumns, feed: Feed,
            settle_ms: float) -> None:
    """Step until the sink's watermark trails the schedule by less than
    ``settle_ms``, for at most ``SETTLE_LIMIT_S``: a backlog left by the
    start (first executions, first transfers) drains before the window
    opens, not inside it."""
    deadline = time.monotonic() + SETTLE_LIMIT_S
    while time.monotonic() < deadline:
        behind = (time.monotonic() - feed.anchor()) * 1000.0 - cols.frontier()
        if behind < settle_ms:
            return
        _run_until(cluster, job, time.monotonic() + 0.005)


def _drain(cluster, job, cols: ResultColumns, due_end: int) -> None:
    """Step until the sink's watermark covers ``due_end`` (every answer due
    has then arrived), for at most ``DRAIN_LIMIT_S``."""
    deadline = time.monotonic() + DRAIN_LIMIT_S
    while cols.frontier() < due_end and time.monotonic() < deadline:
        _run_until(cluster, job, time.monotonic() + 0.005)


class SpanLog:
    """Host spans of a traced run: each tasklet call of at least
    ``SPAN_MIN_NS``, named by its vertex, on ``perf_counter_ns``."""

    def __init__(self):
        self.spans: List[tracing.Span] = []

    def wrap(self, tasklet) -> None:
        call, name, spans = tasklet.call, tasklet.vertex_name, self.spans
        perf = time.perf_counter_ns

        def timed():
            t0 = perf()
            r = call()
            t1 = perf()
            if t1 - t0 >= SPAN_MIN_NS:
                spans.append((t0, t1, name))
            return r
        tasklet.call = timed


def _reduce_trace(logdir: str, spans: List[tracing.Span],
                  perf_open: int) -> TraceSummary:
    """The traced window is the harness's ``WINDOW_SPAN`` annotation; the
    tasklet spans move onto the trace's clock by the offset between that
    annotation's start and ``perf_open``, read just before it."""
    paths = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    tr = tracing.load(str(paths[-1]))
    marks = [sp for sp in tr.host if sp[2] == WINDOW_SPAN]
    if len(marks) != 1:
        raise RuntimeError(f"{len(marks)} {WINDOW_SPAN!r} spans in the trace")
    lo, hi = marks[0][0], marks[0][1]
    offset = lo - perf_open
    host = [(s + offset, e + offset, n) for s, e, n in spans]
    chips = sorted(tr.ops)
    if not chips:
        raise RuntimeError("the trace holds no device operations")
    busy = [tracing.busy_ns(tr.ops[c], lo, hi) for c in chips]
    ops: Dict[str, float] = {}
    for c in chips:
        for n, s in tracing.op_seconds(tr.ops[c], lo, hi).items():
            ops[n] = ops.get(n, 0.0) + s / len(chips)
    steps = [sp for c in chips
             for sp in tracing.executions(tr.modules.get(c, []),
                                          "jit_step", lo, hi)]
    gaps = sorted(tracing.idle_gaps(tr.ops[chips[0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        op_seconds=ops, steps=steps,
        longest_gaps=[[tracing.label(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps])


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_proc: float, require_tpu: bool = True) -> Dict[str, Any]:
    """One run; returns the result line (and, under ``_obs``/``_cols``,
    what the control and the tests read)."""
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.workload["chips"]):
        raise NoChip(f"the cell needs {cell.workload['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} x {devices[0].platform} "
                     f"({devices[0].device_kind})")
    used = devices[:cell.workload["chips"]]
    from repro.core import (DeviceWindowProcessor, JetCluster, JobConfig,
                            PacedGeneratorSource, WallClock)
    cfg, traffic = cell.config, cell.traffic
    query = importlib.import_module(f"bench.queries.{cfg['query']}")
    rate = traffic["rate"]
    stream = NexmarkStream(rate=rate, n_keys=cfg["n_auctions"], seed=seed)
    feed = Feed(stream)
    cols = ResultColumns()
    cluster = JetCluster(n_nodes=cfg["nodes"],
                         cooperative_threads=cfg["cooperative_threads"],
                         clock=WallClock())
    pipeline = query.build(cfg, lambda: PacedGeneratorSource(feed, rate=rate),
                           make_sink(cols, query.fields(cfg)))
    job = cluster.submit(pipeline.to_dag(), JobConfig(
        processing_guarantee=cfg["guarantee"]))
    dev = [t.processor for t in job.execution.tasklets
           if isinstance(t.processor, DeviceWindowProcessor)]
    if not dev:
        raise RuntimeError("the job has no device window vertex")

    _run_until(cluster, job, time.monotonic() + traffic["warmup_s"])
    t_settle = time.monotonic()
    if "settle_ms" in traffic:
        _settle(cluster, job, cols, feed, traffic["settle_ms"])
    setup_s = time.monotonic() - t_proc
    settle_s = time.monotonic() - t_settle
    spans = SpanLog()
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=opts)
        for t in job.execution.tasklets:
            spans.wrap(t)
        # the source catches up on the pause that starting the profiler
        # made before the traced window opens
        _run_until(cluster, job, time.monotonic() + TRACE_SETTLE_S)
        perf_open = time.perf_counter_ns()
        mark = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        mark.__enter__()
    t_open = time.monotonic()
    steps0 = sum(p.steps for p in dev)
    vsec0 = _vertex_seconds(cluster)
    _run_until(cluster, job, t_open + seconds)
    t_close = time.monotonic()
    if trace:
        mark.__exit__(None, None, None)
    steps1 = sum(p.steps for p in dev)
    vsec1 = _vertex_seconds(cluster)

    slide = cfg["slide_ms"]
    anchor = feed.anchor()
    if traffic["due"] == "schedule":
        # every window whose end was due by the schedule before the close
        due_end = int((t_close - anchor) * 1000.0) // slide * slide
        if due_end * 1e-3 + anchor >= t_close:
            due_end -= slide
    elif traffic["due"] == "frontier":
        # above capacity: what the window stage took in and closed
        due_end = cols.frontier(t_close)
    else:
        raise ValueError(f"unknown due rule {traffic['due']!r}")
    _drain(cluster, job, cols, due_end)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        try:
            summary = _reduce_trace(logdir, spans.spans, perf_open)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    cluster.shutdown()
    del job, cluster, dev, pipeline

    got = cols.columns()
    f_open, f_close = cols.frontier(t_open), cols.frontier(t_close)
    # no window can close past the schedule's event time by now: a larger
    # end received is an error the comparison counts, not a size to build
    latest = int((time.monotonic() - anchor) * 1000.0)
    n_ends = min(max(int(got["end"].max(initial=0)), due_end), latest) \
        // slide
    totals = query.reference(cfg, stream, n_ends)
    numbers = query.compare(cfg, got, totals, due_end)
    correct = check.verdict(numbers)
    attempted = int(len(got["end"]) + numbers["missing"])
    failed = sum(numbers.values())
    if attempted == 0:
        correct = False

    latency = None
    if traffic["due"] == "schedule":
        ideal = anchor + got["end"] / 1000.0
        in_window = (ideal >= t_open) & (ideal < t_close)
        latency = (got["t"][in_window] - ideal[in_window]) * 1000.0
        if not len(latency):
            correct = False
    seq_open = stream.first_seq_at(f_open + 1)
    seq_close = stream.first_seq_at(f_close + 1)
    obs = Observation(
        cell=cell, device_kind=used[0].device_kind, setup_s=setup_s,
        anchor=anchor, t_open=t_open, t_close=t_close,
        seconds=t_close - t_open, latency_ms=latency,
        events=seq_close - seq_open, bids=bids_between(seq_open, seq_close),
        window_ends=f_close // slide - f_open // slide,
        steps=steps1 - steps0,
        vertex_seconds={v: vsec1.get(v, 0.0) - vsec0.get(v, 0.0)
                        for v in vsec1},
        gen_lag=feed.lag_ms(t_open, t_close), trace=summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = read_metric(cell.root, m["name"], obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    line: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                            "failed": int(failed), "metrics": metrics,
                            "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        top = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        line["breakdown"] = {"device_ops": [[n, s] for n, s in top],
                             "idle_gaps": summary.longest_gaps}
    line["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                      for k, v in numbers.items()}
    line["_obs"], line["_cols"], line["_totals"] = obs, got, totals
    line["_settle_s"] = settle_s
    line["_due"] = due_end
    return line
