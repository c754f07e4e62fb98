#!/usr/bin/env python3
"""Smoke run of the device-placed NEXMark Q5 pipeline on a TPU.

    python3 chip_smoke.py [--seed N]           # one chip: phases A, B, C
    python3 chip_smoke.py --four-chips         # the SPMD executor on four

One chip: NEXMark Q5 (bids per auction, sliding 1 s window, 10 ms slide)
runs through the ordinary ``Pipeline`` path on an in-process ``JetCluster``
under a virtual clock, once with the window vertex placed on the device and
once on the host.  The host run is the reference: the two result sets must
be equal row for row, with no event dropped.

* A -- counting Q5, 100k events/s over the generator's 10,000 auctions,
  exactly-once with periodic snapshots (the device state is saved through
  the snapshot store while the job runs);
* B -- the same with ``summing(bid_price)``: window sums in the tens of
  thousands, which the device must return exact;
* C -- exactly-once through ``kill_node`` on two nodes, at a smaller size:
  the device run with the kill equals the unfailed host run.

``--four-chips`` runs only the SPMD ``StreamExecutor`` over a 4-device
``data`` mesh, in both exchanges, against a single-device executor fed the
same batches, plus the ring-replicated snapshot/restore identity.

Data comes from the seeded NEXMark generator.  The last line of standard
output is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed on a TPU; any failure, or a platform other than ``tpu``,
exits non-zero.  Timings printed on the way are smoke-run telemetry, not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: Q5 at the NEXMark generator's own key count (10,000 auctions) and the
#: paper-extreme window; 16,384 buckets give every auction its own bucket
PHASES = {
    "A": dict(agg="count", rate=100_000, n_keys=10_000, seconds=2.0,
              n_key_buckets=16_384, batch_size=8_192,
              guarantee="exactly_once", snapshot_interval_s=0.25),
    "B": dict(agg="sum", rate=100_000, n_keys=10_000, seconds=2.0,
              n_key_buckets=16_384, batch_size=8_192,
              guarantee="exactly_once", snapshot_interval_s=0.25),
    "C": dict(agg="count", rate=20_000, n_keys=1_000, seconds=1.0,
              n_key_buckets=1_024, batch_size=1_024, n_nodes=2,
              guarantee="exactly_once", snapshot_interval_s=0.1,
              kill_after_snapshots=2),
}
WINDOW_MS, SLIDE_MS = 1000, 10


class PhaseMismatch(AssertionError):
    """The device run disagreed with the host reference."""


def _rows(out) -> np.ndarray:
    """Window results as one sorted structured array (ts, end, key, value)."""
    n = len(out)
    rows = np.empty(n, dtype=[("ts", np.int64), ("end", np.int64),
                              ("key", np.int64), ("value", np.float64)])
    rows["ts"] = np.fromiter((ev.ts for ev in out), np.int64, n)
    rows["end"] = np.fromiter((ev.value.window_end for ev in out), np.int64, n)
    rows["key"] = np.fromiter((ev.key for ev in out), np.int64, n)
    rows["value"] = np.fromiter((ev.value.value for ev in out), np.float64, n)
    return np.sort(rows)


def run_q5(placement: str, *, agg: str, rate: int, n_keys: int,
           seconds: float, n_key_buckets: int, batch_size: int,
           n_nodes: int = 1, guarantee: str = "none",
           snapshot_interval_s: float = 1.0,
           kill_after_snapshots: int | None = None, seed: int = 0):
    """One Q5 job to completion; returns (sorted result rows, telemetry)."""
    from repro.core import (CollectorSink, DeviceWindowProcessor, JetCluster,
                            JobConfig, PacedGeneratorSource, Pipeline,
                            VirtualClock, sliding, summing)
    from repro.core.engine import JOB_COMPLETED, JOB_FAILED
    from repro.nexmark import NexmarkGenerator, queries

    gen = NexmarkGenerator(rate=rate, n_keys=n_keys, seed=seed)
    total = int(rate * seconds)
    cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=2,
                         clock=VirtualClock(auto_step=0.001))
    out: list = []
    device = dict(n_key_buckets=n_key_buckets, batch_size=batch_size)

    def source():
        return PacedGeneratorSource(gen, rate=rate, max_events=total)

    def sink():
        return CollectorSink(out)

    if agg == "count":
        p = queries.q5(source, sink, window_ms=WINDOW_MS, slide_ms=SLIDE_MS,
                       placement=placement, device=device)
    else:
        p = Pipeline.create()
        (p.read_from(source, name="bids")
            .filter(queries.is_bid)
            .with_key(queries.bid_auction)
            .window(sliding(WINDOW_MS, SLIDE_MS))
            .aggregate(summing(queries.bid_price), placement=placement,
                       device=device)
            .write_to(sink))
    t0 = time.perf_counter()
    job = cluster.submit(p.to_dag(), JobConfig(
        processing_guarantee=guarantee,
        snapshot_interval_s=snapshot_interval_s))
    setup_s = time.perf_counter() - t0       # device vertices compile here
    killed = False
    while job.status != JOB_COMPLETED:
        if job.status == JOB_FAILED:
            raise RuntimeError(f"{placement} Q5 job failed")
        cluster.step()
        if (kill_after_snapshots is not None and not killed and out
                and job.snapshots_taken >= kill_after_snapshots):
            cluster.kill_node(cluster.node_ids[-1])
            killed = True
    wall_s = time.perf_counter() - t0
    if kill_after_snapshots is not None and not killed:
        raise RuntimeError("the job completed before the node kill")
    procs = [t.processor for t in job.execution.tasklets]
    dev = [q for q in procs if isinstance(q, DeviceWindowProcessor)]
    if (placement == "device") != bool(dev):
        raise RuntimeError(f"placement={placement!r} but the job has "
                           f"{len(dev)} device window vertices")
    stats = {
        "placement": placement, "events": total, "results": len(out),
        "device_steps": sum(q.steps for q in dev),
        "bucket_collisions": sum(q.bucket_collisions for q in dev),
        "dropped_late": sum(getattr(q, "late_dropped", 0) for q in procs),
        "dropped_conflict": sum(getattr(q, "conflict_dropped", 0)
                                for q in procs),
        "snapshots": job.snapshots_taken, "killed": killed,
        "setup_s": setup_s, "wall_s": wall_s,
    }
    return _rows(out), stats


def run_phase(name: str, *, seed: int = 0, **cfg) -> list:
    """Device run vs host reference on the same seeded stream.

    With ``kill_after_snapshots`` the device run loses a node and the host
    reference runs unfailed; a restart may re-emit results emitted after
    the restored snapshot, so that comparison is over distinct rows."""
    kill = cfg.pop("kill_after_snapshots", None)
    dev_rows, dev = run_q5("device", seed=seed, kill_after_snapshots=kill,
                           **cfg)
    host_rows, host = run_q5("host", seed=seed, **cfg)
    if kill is not None:
        dev_rows, host_rows = np.unique(dev_rows), np.unique(host_rows)
    problems = []
    if len(host_rows) == 0:
        problems.append("the host reference produced no results")
    if not np.array_equal(dev_rows, host_rows):
        only_dev = np.setdiff1d(dev_rows, host_rows)
        only_host = np.setdiff1d(host_rows, dev_rows)
        problems.append(
            f"result sets differ: {len(dev_rows)} device rows, "
            f"{len(host_rows)} host rows; only on device "
            f"{only_dev[:5].tolist()}, only on host {only_host[:5].tolist()}")
    for stats in (dev, host):
        for counter in ("dropped_late", "dropped_conflict",
                        "bucket_collisions"):
            if stats[counter]:
                problems.append(f"{stats['placement']} {counter}="
                                f"{stats[counter]}")
    if problems:
        raise PhaseMismatch(f"phase {name}: " + "; ".join(problems))
    return [dict(phase=name, **dev), dict(phase=name, **host)]


# ---------------------------------------------------------------- 4 chips --

def _spmd_batches(seed: int, n_steps: int, batch: int, n_buckets: int,
                  rate: int):
    """Bid batches of the seeded NEXMark stream, keyed by auction bucket."""
    from repro.nexmark import NexmarkGenerator
    from repro.nexmark.generator import KIND_BID
    gen = NexmarkGenerator(rate=rate, seed=seed)
    seq = 0
    for _ in range(n_steps):
        blk = gen.gen_block(np.arange(seq, seq + 2 * batch, dtype=np.int64))
        bids = np.nonzero(blk.cols["kind"] == KIND_BID)[0][:batch]
        seq += int(bids[-1]) + 1
        yield {"ts": blk.ts[bids].astype(np.int32),
               "key": (blk.key[bids] % n_buckets).astype(np.int32),
               "value": np.ones(batch, np.float32),
               "valid": np.ones(batch, bool),
               "wm": np.asarray(-1, np.int32)}


def run_spmd(devices, *, seed: int = 0, n_steps: int = 24,
             batch: int = 65_536, n_buckets: int = 16_384,
             rate: int = 2_000_000) -> dict:
    """The SPMD executor over ``devices`` (a ``data`` mesh) in both
    exchanges vs a single-device executor on the same batches, plus the
    ring-replicated snapshot/restore identity taken mid-stream."""
    from jax.sharding import Mesh
    from repro.streaming import (StreamExecutor, StreamJobConfig,
                                 VectorWindowSpec)

    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    spec = VectorWindowSpec(size_ms=WINDOW_MS, slide_ms=SLIDE_MS,
                            n_key_buckets=n_buckets, max_windows_per_step=8,
                            ring_margin=16)
    batches = list(_spmd_batches(seed, n_steps, batch, n_buckets, rate))
    flush_wm = int(batches[-1]["ts"].max()) + 2 * WINDOW_MS
    idle = {"ts": np.zeros(batch, np.int32), "key": np.zeros(batch, np.int32),
            "value": np.zeros(batch, np.float32),
            "valid": np.zeros(batch, bool),
            "wm": np.asarray(flush_wm, np.int32)}

    def drive(ex, check_snapshot=False):
        state = ex.init_state()
        ends, buckets, values = [], [], []
        snap_checked = False
        for i, b in enumerate(batches + [idle] * 16):
            staged, cnt = ex.stage_batch(b)
            state, out = ex.step(state, staged, valid_count=cnt)
            valid = np.asarray(out["valid"])
            res = np.asarray(out["results"])
            for r in np.nonzero(valid)[0].tolist():
                nz = np.nonzero(res[r])[0]
                ends.append(np.full(len(nz), int(np.asarray(
                    out["window_ends"])[r]), np.int64))
                buckets.append(nz.astype(np.int64))
                values.append(res[r][nz].astype(np.float64))
            if check_snapshot and i == len(batches) // 2:
                _check_ring_snapshot(ex, state, n)
                snap_checked = True
        if check_snapshot and not snap_checked:
            raise RuntimeError("snapshot identity was never checked")
        rows = np.empty(sum(len(e) for e in ends),
                        dtype=[("end", np.int64), ("key", np.int64),
                               ("value", np.float64)])
        rows["end"] = np.concatenate(ends) if ends else []
        rows["key"] = np.concatenate(buckets) if ends else []
        rows["value"] = np.concatenate(values) if ends else []
        drops = (int(state["dropped_late"]), int(state["dropped_conflict"]))
        return np.sort(rows), drops

    t0 = time.perf_counter()
    ref_rows, ref_drops = drive(StreamExecutor(
        StreamJobConfig(window=spec, batch_size=batch), mesh=None))
    report = {"phase": "4-chip", "events": n_steps * batch,
              "results": len(ref_rows), "single_device_s":
              time.perf_counter() - t0}
    if len(ref_rows) == 0 or ref_drops != (0, 0):
        raise PhaseMismatch(f"single-device reference: {len(ref_rows)} rows,"
                            f" drops {ref_drops}")
    for exchange in ("reduce", "route"):
        t0 = time.perf_counter()
        rows, drops = drive(StreamExecutor(
            StreamJobConfig(window=spec, batch_size=batch,
                            exchange=exchange), mesh=mesh),
            check_snapshot=exchange == "reduce")
        report[f"{exchange}_s"] = time.perf_counter() - t0
        if drops != (0, 0) or not np.array_equal(rows, ref_rows):
            raise PhaseMismatch(
                f"{n}-device {exchange} exchange: {len(rows)} rows vs "
                f"{len(ref_rows)} single-device, drops {drops}")
    return report


def _check_ring_snapshot(ex, state, n: int) -> None:
    """restore(snapshot(s)) == s, and the backup of key shard i lives on
    shard i+1 of the ring."""
    panes = np.asarray(state["panes"])
    if not panes.any():
        raise RuntimeError("snapshot taken over empty panes proves nothing")
    backup = ex.snapshot(state)
    b = np.asarray(backup["panes"])
    w = panes.shape[1] // n
    for i in range(n):
        j = (i - 1) % n
        if not np.array_equal(b[:, i * w:(i + 1) * w],
                              panes[:, j * w:(j + 1) * w]):
            raise PhaseMismatch(f"backup shard {i} != shard {j}")
    restored = np.asarray(ex.restore(backup)["panes"])
    if not np.array_equal(restored, panes):
        raise PhaseMismatch("restore(snapshot(state)) != state")


# ------------------------------------------------------------------ main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD executor on four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, SRC)
    try:
        from repro.jax_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the system from {SRC}: {e}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    d0 = devices[0]
    want = 4 if args.four_chips else 1
    if d0.platform != "tpu" or len(devices) != want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} x {d0.platform} ({d0.device_kind})",
              file=sys.stderr)
        return 1
    try:
        if args.four_chips:
            reports = [run_spmd(devices[:4], seed=args.seed)]
        else:
            reports = []
            for name, cfg in PHASES.items():
                reports += run_phase(name, seed=args.seed, **cfg)
                print(json.dumps(reports[-2]), flush=True)
                print(json.dumps(reports[-1]), flush=True)
    except Exception:
        traceback.print_exc()
        return 1
    if args.four_chips:
        print(json.dumps(reports[0]), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
