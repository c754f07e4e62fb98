"""The paper's headline metric: event-time -> emission latency at the
99.99th percentile, plus events/s/core, on BOTH tiers.

Methodology (paper §7.1): the latency clock for a window result starts at
the *ideal occurrence time* of its window end — the generator's pacing
schedule pins event time to wall time — and stops when the engine emits
the result at the sink.  Scheduling delay, batching delay, snapshot
pauses: everything the engine does shows up in the number.  Latencies are
recorded into an HdrHistogram-style log-bucketed histogram so the p99.99
is a real measured quantile, not an interpolation over a handful of
samples.

Results land in ``BENCH_latency.json`` at the repo root so successive PRs
accumulate a perf trajectory.
"""

from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List, Optional

import numpy as np

REPORT_PCTS = (50.0, 90.0, 99.0, 99.9, 99.99)
#: a p99.99 needs at least this many samples before the quantile is a
#: measurement rather than "roughly the max of a small run"
P9999_MIN_SAMPLES = 10_000


class LatencyHistogram:
    """HdrHistogram-style fixed-precision histogram of microsecond values.

    Values are bucketed logarithmically by magnitude with
    ``2**sub_bucket_bits`` linear sub-buckets per power of two, giving a
    bounded relative error (~1/2**sub_bucket_bits) across the whole range
    with O(1) record cost and compact storage — the same scheme
    HdrHistogram uses, sized here for 1 us .. ~60 s.
    """

    def __init__(self, max_value_us: int = 60_000_000,
                 sub_bucket_bits: int = 7):
        self.sub_bucket_bits = sub_bucket_bits
        self.sub_bucket_count = 1 << sub_bucket_bits
        # number of magnitude buckets needed to cover max_value_us
        buckets = 1
        top = self.sub_bucket_count
        while top < max_value_us:
            top <<= 1
            buckets += 1
        self.bucket_count = buckets
        self.max_value_us = max_value_us
        # bucket 0 holds values [0, sub_bucket_count) at resolution 1;
        # bucket b >= 1 holds [sub_bucket_count * 2**(b-1), ... * 2**b)
        # in sub_bucket_count/2 live sub-buckets of width 2**b
        self.counts = np.zeros(
            (buckets + 1) * self.sub_bucket_count, dtype=np.int64)
        self.total = 0
        self.min_us = float("inf")
        self.max_us = 0.0

    def _index(self, v: int) -> int:
        if v < self.sub_bucket_count:
            return v
        bucket = v.bit_length() - self.sub_bucket_bits
        sub = v >> bucket
        return (bucket << self.sub_bucket_bits) + sub

    def record(self, value_us: float) -> None:
        v = int(value_us)
        if v < 0:
            v = 0
        elif v > self.max_value_us:
            v = self.max_value_us
        self.counts[self._index(v)] += 1
        self.total += 1
        if value_us < self.min_us:
            self.min_us = value_us
        if value_us > self.max_us:
            self.max_us = value_us

    def record_many(self, values_us) -> None:
        for v in values_us:
            self.record(v)

    def percentile(self, pct: float) -> float:
        """Value (us) at the given percentile, upper-bucket-edge biased."""
        if self.total == 0:
            return float("nan")
        target = int(np.ceil(pct / 100.0 * self.total))
        running = 0
        nz = np.nonzero(self.counts)[0]
        for idx in nz:
            running += int(self.counts[idx])
            if running >= target:
                bucket = idx >> self.sub_bucket_bits
                sub = idx & (self.sub_bucket_count - 1)
                width = 1 if bucket == 0 else 1 << bucket
                base = sub if bucket == 0 else sub << bucket
                return float(base + width - 1)
        return self.max_us

    def summary_ms(self) -> Dict[str, float]:
        out = {f"p{p:g}": round(self.percentile(p) / 1000.0, 3)
               for p in REPORT_PCTS}
        out["min"] = round(0.0 if self.total == 0 else self.min_us / 1000.0, 3)
        out["max"] = round(self.max_us / 1000.0, 3)
        out["samples"] = self.total
        if self.total < P9999_MIN_SAMPLES:
            # 1 in 10k: with fewer samples the quantile is just the max of
            # a small run — report it as unreliable instead of meaningless
            out["p99.99"] = None
            out["warning"] = (f"p99.99 unreliable: {self.total} samples "
                              f"< {P9999_MIN_SAMPLES}")
        return out


# ---------------------------------------------------------------------------
# Host tier: NEXMark Q5 through the cooperative tasklet engine
# ---------------------------------------------------------------------------


def host_q5_latency(rate: float = 20_000, duration_s: float = 4.0,
                    window_ms: int = 1000, slide_ms: int = 20,
                    n_keys: int = 100, threads: int = 2,
                    warmup_s: float = 1.0, disorder_ms: int = 0,
                    disorder_seed: int = 7,
                    block_size: Optional[int] = None,
                    placement: str = "host",
                    device: Optional[Dict] = None) -> Dict:
    """Paced Q5 on the host tier; returns percentiles + events/s/core.

    ``disorder_ms`` > 0 runs the generator through a seeded bounded shuffle
    (events arrive up to that much event time out of order) with a matching
    watermark lag — the p99.99 then includes the completeness wait the lag
    imposes, which is the honest cost of disorder tolerance.

    ``placement="device"`` swaps the host two-stage window plan for the
    device-offloaded window vertex (core/device_window.py): EventBlocks
    pack into padded device batches, the compiled StreamExecutor
    aggregates, and results cross back to host events — the end-to-end
    ``host_to_device`` bridge measurement.

    The whole cluster simulation runs on one OS thread, so aggregate
    events/s == events/s/core."""
    from repro.core import (JetCluster, JobConfig, PacedGeneratorSource,
                            WallClock)
    from repro.core.engine import JOB_COMPLETED
    from repro.nexmark import (DisorderedNexmarkGenerator, NexmarkGenerator,
                               queries)
    from .common import _SinkAdapter

    clock = WallClock()
    cluster = JetCluster(n_nodes=1, cooperative_threads=threads, clock=clock)
    gen = NexmarkGenerator(rate=rate, n_keys=n_keys)
    if disorder_ms > 0:
        gen = DisorderedNexmarkGenerator(gen, max_skew_ms=disorder_ms,
                                         seed=disorder_seed)
    hist = LatencyHistogram()
    total = int(rate * duration_s)
    t0_holder = [None]
    cut_holder = [None]
    end_holder = [None]

    def sink(ev):
        now = clock.now()
        # window result event time is window_end - 1 (ms since t0)
        ideal = t0_holder[0] + (ev.ts + 1) / 1000.0
        # drop warmup and the end-of-stream flush (windows emitted early
        # when the finite source completes have ideal times in the future)
        if cut_holder[0] <= now and ideal <= end_holder[0]:
            hist.record((now - ideal) * 1e6)

    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total,
                                     wm_lag=disorder_ms,
                                     block_size=block_size),
        lambda: _SinkAdapter(sink), window_ms=window_ms, slide_ms=slide_ms,
        placement=placement, device=device)
    # submit BEFORE anchoring t0: processor init (incl. the device
    # vertex's one-time XLA compile) must not count against event latency
    # — the paced source anchors its own schedule on its first slice,
    # which happens after init, so t0 and the schedule stay aligned
    job = cluster.submit(p.to_dag(), JobConfig())
    t0_holder[0] = clock.now()
    cut_holder[0] = t0_holder[0] + warmup_s
    end_holder[0] = t0_holder[0] + total / rate
    deadline = time.monotonic() + duration_s * 3 + 10
    t_start = time.monotonic()
    while job.status != JOB_COMPLETED and time.monotonic() < deadline:
        cluster.step()
    wall = time.monotonic() - t_start
    if job.status != JOB_COMPLETED:
        # percentiles of a job cut at its deadline describe a backlog,
        # not the paced run
        raise RuntimeError(
            f"q5 {placement} at {rate} ev/s did not complete within "
            f"{deadline - t_start:.0f} s (status {job.status})")
    stats = job.execution.stats()
    engine = {k: stats[k] for k in ("items_in", "items_out", "calls",
                                    "idle_calls")}
    # sampled per-tasklet timing, aggregated per vertex: where the
    # remaining host-tier time goes (feeds the next perf PR)
    engine["per_vertex_time_share"] = cluster.vertex_time_share()
    return {
        "tier": "host" if placement == "host" else "host_to_device",
        "query": "q5", "rate": rate,
        "window_ms": window_ms, "slide_ms": slide_ms,
        "disorder_ms": disorder_ms,
        "events_per_sec_per_core": round(total / wall, 0),
        "latency_ms": hist.summary_ms(),
        "engine": engine,
    }


def host_q5_saturation(n_events: int = 800_000, threads: int = 2,
                       probe_rate: float = 2_000_000,
                       block_size: Optional[int] = None,
                       backend: str = "inproc") -> float:
    """Max sustained events/s/core: pace far beyond capacity (every event
    is always due) and measure the wall time to drain a fixed stream.

    ``block_size=0`` forces the scalar per-event datapath (the A/B
    baseline for the columnar EventBlock path); the default auto-enables
    columnar blocks.  ``backend="mp"`` runs the same fixed stream across
    ``threads`` real worker processes over shared-memory rings (the
    coordinator loop stays on this thread)."""
    from repro.core import (JetCluster, PacedGeneratorSource, WallClock)
    from repro.core.engine import JOB_COMPLETED
    from repro.nexmark import NexmarkGenerator, queries
    from .common import _SinkAdapter

    cluster = JetCluster(n_nodes=1, cooperative_threads=threads,
                         clock=WallClock(), backend=backend)
    gen = NexmarkGenerator(rate=probe_rate, n_keys=100)
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=probe_rate,
                                     max_events=n_events,
                                     block_size=block_size),
        lambda: _SinkAdapter(lambda ev: None), window_ms=1000, slide_ms=20)
    try:
        job = cluster.submit(p.to_dag())
        t0 = time.monotonic()
        deadline = t0 + 120
        while job.status != JOB_COMPLETED and time.monotonic() < deadline:
            cluster.step()
        wall = time.monotonic() - t0
    finally:
        cluster.shutdown()
    return n_events / wall


def host_q5_saturation_ab(n_events: int = 600_000, threads: int = 2,
                          rounds: int = 2) -> Dict[str, float]:
    """Interleaved A/B saturation: scalar datapath vs columnar EventBlock
    datapath, alternated on the same machine in the same process (the
    PR 2 methodology), reporting the best round of each arm."""
    scalar, blocked = [], []
    for _ in range(rounds):
        scalar.append(host_q5_saturation(n_events, threads, block_size=0))
        blocked.append(host_q5_saturation(n_events, threads))
    return {
        "saturation_events_per_sec_per_core": round(max(blocked), 0),
        "saturation_scalar_events_per_sec_per_core": round(max(scalar), 0),
        "saturation_block_speedup": round(max(blocked) / max(scalar), 2),
        "saturation_rounds": rounds,
    }


# ---------------------------------------------------------------------------
# Multiprocess backend: same host-tier Q5 across real worker processes
# ---------------------------------------------------------------------------


def mp_q5_latency(rate: float = 20_000, duration_s: float = 4.0,
                  workers: int = 2, window_ms: int = 1000,
                  slide_ms: int = 20, n_keys: int = 100,
                  warmup_s: float = 1.0,
                  block_size: Optional[int] = None) -> Dict:
    """Paced Q5 on the multiprocess backend: ``workers`` real OS processes
    exchanging EventBlocks over shared-memory rings, coordinator on this
    thread.

    The in-process harness can close over a parent-side sink; here the
    sink runs inside a forked worker, so the latency clock is rebuilt from
    shipped data instead: ``CollectorSink(with_time=True)`` stamps each
    result with the child's wall clock at emission (same machine, same
    clock domain), results ship to the coordinator incrementally, and t0
    is the paced source's schedule anchor reported back with the worker's
    final stats (``MultiprocessBackend.source_start``)."""
    from repro.core import (CollectorSink, JetCluster, JobConfig,
                            PacedGeneratorSource, WallClock)
    from repro.core.engine import JOB_COMPLETED
    from repro.nexmark import NexmarkGenerator, queries

    cluster = JetCluster(n_nodes=1, cooperative_threads=workers,
                         clock=WallClock(), backend="mp")
    gen = NexmarkGenerator(rate=rate, n_keys=n_keys)
    total = int(rate * duration_s)
    out: list = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total,
                                     block_size=block_size),
        lambda: CollectorSink(out, with_time=True),
        window_ms=window_ms, slide_ms=slide_ms)
    try:
        job = cluster.submit(p.to_dag(), JobConfig())
        deadline = time.monotonic() + duration_s * 3 + 10
        t_start = time.monotonic()
        while job.status != JOB_COMPLETED and time.monotonic() < deadline:
            cluster.step()
        wall = time.monotonic() - t_start
        t0 = cluster.backend.source_start(job.execution)
    finally:
        cluster.shutdown()
    if job.status != JOB_COMPLETED:
        raise RuntimeError(
            f"mp q5 at {rate} ev/s did not complete within "
            f"{deadline - t_start:.0f} s (status {job.status})")
    hist = LatencyHistogram()
    if t0 is not None:
        cut = t0 + warmup_s
        end = t0 + total / rate
        for t_arr, ev in out:
            ideal = t0 + (ev.ts + 1) / 1000.0
            # same filters as the in-process harness: drop warmup and the
            # end-of-stream flush (ideal times in the future)
            if cut <= t_arr and ideal <= end:
                hist.record((t_arr - ideal) * 1e6)
    return {
        "tier": "host_mp", "backend": "mp", "query": "q5", "rate": rate,
        "workers": workers, "window_ms": window_ms, "slide_ms": slide_ms,
        "events_per_sec": round(total / wall, 0),
        "latency_ms": hist.summary_ms(),
    }


def mp_saturation_curve(n_events: int = 200_000,
                        workers=(1, 2, 4)) -> Dict:
    """Blocked-Q5 saturation at each worker-process count — the scaling
    shape of the shared-memory substrate.  The host's core count is
    recorded alongside: on a single-core box the curve can only show the
    coordination overhead of extra processes, not parallel speedup, and
    the record must say so."""
    import os
    curve = {}
    for w in workers:
        curve[str(w)] = round(host_q5_saturation(
            n_events=n_events, threads=w, backend="mp"), 0)
    return {
        "figure": "mp_saturation_curve", "backend": "mp",
        "cpus": os.cpu_count(), "n_events": n_events,
        "saturation_events_per_sec_by_workers": curve,
    }


# ---------------------------------------------------------------------------
# Device tier: vectorized Q5 through the compiled StreamExecutor
# ---------------------------------------------------------------------------


def device_q5_latency(steps: int = 2000, batch: int = 4096,
                      n_keys: int = 4096, warmup: int = 50) -> Dict:
    """Per-step event->emission latency of the compiled datapath.

    Each step ingests 10 ms of event time; the latency clock starts when
    the batch exists on the host (its events' generation instant) and
    stops when the emitted window results are materialized host-side —
    staging, compute and readback all show up in the number.  Throughput
    is measured separately over the *pipelined* path (``run_stream``-style
    prefetching, no per-step sync).
    """
    import jax
    from repro.streaming import (StreamExecutor, StreamJobConfig,
                                 VectorWindowSpec)

    spec = VectorWindowSpec(size_ms=1000, slide_ms=10, n_key_buckets=n_keys,
                            max_windows_per_step=2, ring_margin=8)
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=batch))
    rng = np.random.RandomState(0)

    def make_batch(i):
        ts = i * 10 + np.sort(rng.randint(0, 10, batch)).astype(np.int32)
        return {"ts": ts,
                "key": rng.randint(0, n_keys, batch).astype(np.int32),
                "value": np.ones((batch,), np.float32),
                "valid": np.ones((batch,), bool),
                "wm": np.asarray(-1, np.int32)}

    hist = LatencyHistogram()
    state = ex.init_state()
    # compile + warmup
    for i in range(warmup):
        staged, cnt = ex.stage_batch(make_batch(i))
        state, out = ex.step(state, staged, valid_count=cnt)
    jax.block_until_ready(state["panes"])

    # latency mode: one batch at a time, synced at the sink
    for i in range(warmup, warmup + steps):
        b = make_batch(i)
        t_gen = time.perf_counter()
        staged, cnt = ex.stage_batch(b)
        state, out = ex.step(state, staged, valid_count=cnt)
        valid = np.asarray(out["valid"])        # sink materialization
        if valid.any():
            np.asarray(out["results"])
        t_emit = time.perf_counter()
        hist.record((t_emit - t_gen) * 1e6)

    # throughput mode: pipelined ingestion, no per-step sync
    n_tp = max(steps // 2, 100)
    batches = [make_batch(warmup + steps + i) for i in range(n_tp)]
    t0 = time.perf_counter()
    nxt = ex.stage_batch(batches[0])
    for i in range(n_tp):
        staged, cnt = nxt
        if i + 1 < n_tp:
            nxt = ex.stage_batch(batches[i + 1])
        state, out = ex.step(state, staged, valid_count=cnt)
    jax.block_until_ready(state["panes"])
    dt = time.perf_counter() - t0
    return {
        "tier": "device", "query": "q5-vectorized", "batch": batch,
        "keys": n_keys, "steps": steps,
        "events_per_sec_per_core": round(n_tp * batch / dt, 0),
        "latency_ms": hist.summary_ms(),
    }


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def run(quick: bool = True, disorder_ms: int = 100,
        backend: str = "inproc", workers: Optional[int] = None) -> Dict:
    host_rate = 20_000
    duration = 4.0 if quick else 10.0
    threads = workers or 2
    if backend == "mp":
        # the knob swaps the substrate under the paced host run itself
        host = mp_q5_latency(rate=host_rate, duration_s=duration,
                             workers=threads)
    else:
        host = host_q5_latency(rate=host_rate, duration_s=duration,
                               threads=threads)
        host.update(host_q5_saturation_ab(
            n_events=600_000 if quick else 2_000_000))
    result = {
        "meta": {
            "metric": "event-time -> emission latency (ms), "
                      "HdrHistogram-style recording",
            "pcts": list(REPORT_PCTS),
            "host_config": {"query": "q5", "rate": host_rate,
                            "window_ms": 1000, "slide_ms": 20},
            "quick": quick,
            "backend": backend,
            "workers": threads,
        },
        "host": host,
    }
    # multiprocess substrate, always measured so the trajectory tracks it:
    # paced percentiles at the default worker count plus the saturation
    # curve across 1/2/4 worker processes.  These fork their workers, so
    # they run BEFORE any section below opens the accelerator: a chip
    # belongs to one process, and a child forked after the parent opened
    # it would fail or hang
    if backend != "mp":
        result["host_mp"] = mp_q5_latency(rate=host_rate,
                                          duration_s=duration,
                                          workers=threads)
    result["mp_saturation"] = mp_saturation_curve(
        n_events=200_000 if quick else 600_000)
    if disorder_ms > 0:
        # the paper's "handles out-of-order streams" claim, measured: same
        # query under bounded skew with a matching watermark lag
        result["host_disordered"] = host_q5_latency(
            rate=host_rate, duration_s=4.0 if quick else 10.0,
            disorder_ms=disorder_ms)
    # the host->device bridge: the same paced Q5 but the window vertex
    # offloaded to the device tier (EventBlocks -> padded device batches
    # -> StreamExecutor -> WindowResult events), so the bridge's
    # throughput and p99.99 trend alongside the pure host/device numbers
    result["host_to_device"] = host_q5_latency(
        rate=host_rate, duration_s=4.0 if quick else 10.0,
        placement="device",
        device={"n_key_buckets": 128, "batch_size": 1024})
    # >= 10k steps even in quick mode: at millions of events/s this stays
    # well under a minute and makes the headline p99.99 a real measurement
    # (1k steps used to report it null+warning in CI)
    result["device"] = device_q5_latency(steps=10_000)
    return result


def write_report(result: Dict,
                 path: Optional[pathlib.Path] = None) -> pathlib.Path:
    if path is None:
        path = pathlib.Path(__file__).resolve().parents[1] / \
            "BENCH_latency.json"
    # merge over the existing report: sections other harnesses own (e.g.
    # the "chaos" section from benchmarks/bench_chaos.py) must survive a
    # latency-only refresh
    try:
        merged = json.loads(path.read_text())
        if not isinstance(merged, dict):
            merged = {}
    except (FileNotFoundError, ValueError):
        merged = {}
    merged.update(result)
    path.write_text(json.dumps(merged, indent=1, default=float) + "\n")
    return path


def rows(quick: bool = True, disorder_ms: int = 100,
         backend: str = "inproc",
         workers: Optional[int] = None) -> List[Dict]:
    """CSV-row shaped output for benchmarks.run."""
    result = run(quick, disorder_ms=disorder_ms, backend=backend,
                 workers=workers)
    write_report(result)
    append_trajectory(result)
    out = []
    for tier in ("host", "host_mp", "host_disordered", "host_to_device",
                 "device"):
        r = result.get(tier)
        if r is None:
            continue
        lat = r["latency_ms"]
        row = {"figure": f"latency_{tier}",
               "events_per_sec_per_core":
                   r.get("events_per_sec_per_core", r.get("events_per_sec")),
               **{k: lat[k] for k in ("p50", "p99", "p99.9", "p99.99")},
               "samples": lat["samples"]}
        if r.get("backend"):
            row["backend"] = r["backend"]
        if r.get("workers"):
            row["workers"] = r["workers"]
        if lat.get("warning"):
            row["warning"] = lat["warning"]
        if r.get("disorder_ms"):
            row["disorder_ms"] = r["disorder_ms"]
        for k in ("saturation_events_per_sec_per_core",
                  "saturation_scalar_events_per_sec_per_core",
                  "saturation_block_speedup"):
            if k in r:
                row[k] = r[k]
        out.append(row)
    sat = result.get("mp_saturation")
    if sat:
        row = {"figure": "mp_saturation_curve", "cpus": sat["cpus"],
               "n_events": sat["n_events"]}
        for w, v in sat["saturation_events_per_sec_by_workers"].items():
            row[f"workers_{w}_events_per_sec"] = v
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# Cross-PR perf trajectory
# ---------------------------------------------------------------------------


def append_trajectory(result: Dict,
                      path: Optional[pathlib.Path] = None) -> pathlib.Path:
    """Append one per-run record (git SHA, saturation A/B, paced and device
    percentiles) to the cumulative ``BENCH_trajectory.json`` so perf
    regressions across PRs are visible at a glance."""
    import subprocess
    if path is None:
        path = pathlib.Path(__file__).resolve().parents[1] / \
            "BENCH_trajectory.json"
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=path.parent, capture_output=True, text=True,
            timeout=10).stdout.strip() or "unknown"
    except Exception:
        sha = "unknown"
    host = result.get("host", {})
    lat = host.get("latency_ms", {})
    device = result.get("device", {})
    bridge = result.get("host_to_device", {})
    record = {
        "sha": sha,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "quick": result.get("meta", {}).get("quick"),
        "host_saturation_events_per_sec_per_core":
            host.get("saturation_events_per_sec_per_core"),
        "host_saturation_scalar_events_per_sec_per_core":
            host.get("saturation_scalar_events_per_sec_per_core"),
        "host_paced_rate": host.get("rate"),
        "host_p50_ms": lat.get("p50"),
        "host_p99_ms": lat.get("p99"),
        "host_p99.99_ms": lat.get("p99.99"),
        "device_events_per_sec_per_core":
            device.get("events_per_sec_per_core"),
        "device_p99.99_ms": device.get("latency_ms", {}).get("p99.99"),
        "host_to_device_events_per_sec_per_core":
            bridge.get("events_per_sec_per_core"),
        "host_to_device_p50_ms":
            bridge.get("latency_ms", {}).get("p50"),
        "host_to_device_p99.99_ms":
            bridge.get("latency_ms", {}).get("p99.99"),
    }
    # multiprocess substrate: paced percentiles + per-worker-count
    # saturation curve (dict keyed by worker-process count), with the
    # host's core count so single-core records are not misread as
    # failed scaling
    mp = result.get("host_mp") or (
        host if host.get("backend") == "mp" else {})
    sat = result.get("mp_saturation", {})
    record.update({
        "mp_workers": mp.get("workers"),
        "mp_paced_events_per_sec": mp.get("events_per_sec"),
        "mp_paced_p50_ms": mp.get("latency_ms", {}).get("p50"),
        "mp_paced_p99.99_ms": mp.get("latency_ms", {}).get("p99.99"),
        "mp_saturation_events_per_sec_by_workers":
            sat.get("saturation_events_per_sec_by_workers"),
        "cpus": sat.get("cpus"),
    })
    try:
        records = json.loads(path.read_text())
        if not isinstance(records, list):
            records = []
    except (FileNotFoundError, ValueError):
        records = []
    records.append(record)
    path.write_text(json.dumps(records, indent=1, default=float) + "\n")
    return path


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--disorder", type=int, default=100, metavar="SKEW_MS",
                    help="bounded-shuffle skew for the disordered host run "
                         "(0 disables it)")
    ap.add_argument("--backend", choices=("inproc", "mp"), default="inproc",
                    help="substrate for the paced host run (the mp "
                         "saturation curve is measured either way)")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="cooperative threads (inproc) / worker processes "
                         "(mp) for the paced host run; default 2")
    args = ap.parse_args()
    result = run(quick=not args.full, disorder_ms=args.disorder,
                 backend=args.backend, workers=args.workers)
    p = write_report(result)
    t = append_trajectory(result)
    print(json.dumps(result, indent=1, default=float))
    print(f"# wrote {p}")
    print(f"# appended {t}")
