"""Chaos soak harness: paced NEXMark Q5 under seeded fault injection.

Every scenario runs the same paced Q5 twice — once clean, once under a
:class:`~repro.runtime.chaos.ChaosSchedule` derived from an integer seed
— and checks the two result sets are identical (exactly-once: zero loss,
zero spurious results; raw replay overlap is reported separately).  For
each fired fault the harness records the **recovery gap**: wall time from
the injection instant to the first result arriving after it, which under
a kill covers detection, teardown, restore-from-committed-snapshot,
re-fork and replay.  Inter-arrival gap percentiles over the whole run
(p50..p99.99) put those gaps in context against the no-chaos baseline.

Scenarios:

* ``chaos_q5`` — the seeded schedule (kill / stall / raise / drop_ack /
  delay_ack, every kind at least once per schedule) against the mp
  substrate, plus an in-process run of the same schedule (kinds the
  substrate cannot express are skipped and recorded);
* ``rescale_q5`` — elastic ``add_node`` mid-run (cooperative whole-job
  restart) with the same equality check and gap measurement;
* ``active_active_flip`` — hot-standby replica loss (§4.6): kill the
  primary mid-stream, the standby keeps emitting; dedup-by-record-id
  output must still be complete;
* ``corruption_q5`` — the durable snapshot chain under seeded *storage*
  faults: each corruption fault (bit-flip / truncate / manifest delete)
  damages the newest committed on-disk snapshot and is chased by a
  worker kill in the same tick, so recovery must detect the damage and
  fall back down the verified chain.  Per fault the harness records the
  victim snapshot id, the recovery gap, and (from the job's recovery
  log) the skipped ids + reasons proving the fallback was *verified*,
  not lucky;
* ``poison_q5`` — a record that deterministically crashes its vertex:
  the crash-loop escalation ladder must fingerprint it, pinpoint the
  exact record, quarantine it to the dead-letter queue (exactly-once
  accounting) and complete within the restart budget with output equal
  to a run that never saw the record.

The results print as one JSON document on stdout; ``--smoke`` (the CI
gate) can additionally dump the recovery diagnostics of its corruption +
poison passes via ``--diagnostics PATH`` for the CI artifact.
"""

from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List, Optional

import numpy as np

GAP_PCTS = (50.0, 90.0, 99.0, 99.9, 99.99)


def _result_key(ev):
    return (ev.ts, ev.key, ev.value.window_end, ev.value.value)


def _unique(out) -> List:
    return sorted({_result_key(ev) for _, ev in out})


def _gap_stats(arrivals: List[float]) -> Dict[str, float]:
    """Inter-arrival gap percentiles (ms) across the whole run."""
    if len(arrivals) < 2:
        return {}
    gaps = np.diff(np.sort(np.asarray(arrivals))) * 1000.0
    stats = {f"p{p:g}": round(float(np.percentile(gaps, p)), 3)
             for p in GAP_PCTS}
    stats["max"] = round(float(gaps.max()), 3)
    stats["samples"] = int(len(gaps))
    return stats


def _paced_q5(backend: str, rate: float, total: int, threads: int,
              n_nodes: int, seed: Optional[int] = None,
              n_faults: int = 5, rescale_at: Optional[int] = None,
              window_ms: int = 100, slide_ms: int = 20,
              timeout_s: float = 300.0, kinds=None,
              snapshot_dir=None, schedule=None,
              restart_policy=None) -> Dict:
    """One paced Q5 run; chaos when ``seed`` or an explicit ``schedule``
    is set, elastic rescale when ``rescale_at`` is set, durable on-disk
    snapshot chain when ``snapshot_dir`` is set.  Returns raw sink output
    plus job/fault bookkeeping."""
    from repro.core import (CollectorSink, JetCluster, JobConfig,
                            PacedGeneratorSource, GUARANTEE_EXACTLY_ONCE)
    from repro.core.engine import JOB_COMPLETED
    from repro.nexmark import NexmarkGenerator, queries
    from repro.runtime.chaos import ChaosController, ChaosSchedule

    gen = NexmarkGenerator(rate=rate, n_keys=40)
    cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=threads,
                         backend=backend, snapshot_dir=snapshot_dir)
    out: list = []
    p = queries.q5(
        lambda: PacedGeneratorSource(gen, rate=rate, max_events=total),
        lambda: CollectorSink(out, with_time=True),
        window_ms=window_ms, slide_ms=slide_ms)
    # a tight barrier deadline so a chaos-dropped ack aborts (and is seen
    # to abort) within the run instead of outliving it
    job = cluster.submit(p.to_dag(), JobConfig(
        processing_guarantee=GUARANTEE_EXACTLY_ONCE,
        snapshot_interval_s=0.1, barrier_timeout_s=0.75,
        restart_policy=restart_policy))
    controller = None
    if schedule is None and seed is not None:
        # expected unique results ~= total/1000ms * slide panes; using the
        # raw sink length as the logical clock only needs rough proportions
        expected = max(200, (total * 1000 // int(rate)) // slide_ms)
        schedule = ChaosSchedule.from_seed(
            seed, n_faults, expected,
            **({} if kinds is None else {"kinds": kinds}))
    if schedule is not None:
        controller = ChaosController(cluster, job, out, schedule)
    rescaled_at: Optional[float] = None
    try:
        deadline = time.monotonic() + timeout_s
        while job.status != JOB_COMPLETED:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"chaos run did not complete (status {job.status}, "
                    f"faults {controller.log if controller else None})")
            cluster.step()
            if controller is not None:
                controller.tick()
            if (rescale_at is not None and rescaled_at is None
                    and len(out) >= rescale_at and job.snapshots_taken > 0):
                cluster.add_node()
                rescaled_at = time.monotonic()
    finally:
        cluster.shutdown()
    return {
        "out": out,
        "faults": controller.schedule.faults if controller else [],
        "rescaled_at": rescaled_at,
        "auto_restarts": job.auto_restarts,
        "cooperative_restarts": job.restarts - job.auto_restarts,
        "snapshots_aborted": job.snapshots_aborted,
        "failures": [repr(f) for f in job.failures],
        # restores with skipped ids + reasons, escalations, dead letters
        "recovery": job.recovery_diagnostics(),
    }


def _recovery_gap_ms(arrivals: List[float], t_fire: float) -> Optional[float]:
    after = [t for t in arrivals if t > t_fire]
    if not after:
        return None
    return round((min(after) - t_fire) * 1000.0, 3)


def _verify(clean_out, chaos_out) -> Dict:
    clean_u, chaos_u = _unique(clean_out), _unique(chaos_out)
    lost = len(set(clean_u) - set(chaos_u))
    spurious = len(set(chaos_u) - set(clean_u))
    return {
        "results_match": clean_u == chaos_u,
        "unique_results": len(chaos_u),
        "lost": lost,
        "spurious": spurious,
        # raw re-emissions from post-restore replay (deduplicated above;
        # nonzero is EXPECTED for collector sinks under restarts)
        "replay_duplicates": len(chaos_out) - len({
            _result_key(ev) for _, ev in chaos_out}),
    }


def chaos_q5(backend: str = "mp", seed: int = 1, n_faults: int = 5,
             rate: float = 60_000, total: int = 48_000,
             threads: int = 2, n_nodes: int = 2) -> Dict:
    clean = _paced_q5(backend, rate, total, threads, n_nodes)
    chaos = _paced_q5(backend, rate, total, threads, n_nodes, seed=seed,
                      n_faults=n_faults)
    arrivals = [t for t, _ in chaos["out"]]
    faults = []
    for f in chaos["faults"]:
        rec = {"kind": f.kind, "at_result": f.at_result,
               "fired": f.fired, "skipped": f.skipped}
        if f.fired:
            rec["fired_at_result"] = f.fired_at_result
            rec["recovery_gap_ms"] = _recovery_gap_ms(arrivals, f.fired_at)
        faults.append(rec)
    return {
        "scenario": "chaos_q5", "backend": backend, "seed": seed,
        "rate": rate, "total_events": total, "workers": threads,
        "nodes": n_nodes,
        "faults": faults,
        "fault_kinds_fired": sorted({f["kind"] for f in faults
                                     if f["fired"]}),
        "auto_restarts": chaos["auto_restarts"],
        "snapshots_aborted": chaos["snapshots_aborted"],
        "verification": _verify(clean["out"], chaos["out"]),
        "arrival_gap_ms": _gap_stats(arrivals),
        "clean_arrival_gap_ms": _gap_stats([t for t, _ in clean["out"]]),
    }


def rescale_q5(backend: str = "mp", rate: float = 60_000,
               total: int = 36_000, threads: int = 2,
               n_nodes: int = 2, rescale_at: int = 200) -> Dict:
    clean = _paced_q5(backend, rate, total, threads, n_nodes)
    scaled = _paced_q5(backend, rate, total, threads, n_nodes,
                       rescale_at=rescale_at)
    arrivals = [t for t, _ in scaled["out"]]
    gap = (None if scaled["rescaled_at"] is None
           else _recovery_gap_ms(arrivals, scaled["rescaled_at"]))
    return {
        "scenario": "rescale_q5", "backend": backend, "rate": rate,
        "total_events": total, "workers": threads,
        "nodes_before": n_nodes, "nodes_after": n_nodes + 1,
        "rescale_recovery_gap_ms": gap,
        "cooperative_restarts": scaled["cooperative_restarts"],
        "verification": _verify(clean["out"], scaled["out"]),
    }


def corruption_q5(backend: str = "mp", seed: int = 1, n_faults: int = 2,
                  rate: float = 60_000, total: int = 48_000,
                  threads: int = 2, n_nodes: int = 2) -> Dict:
    """Seeded snapshot-corruption soak: each corruption fault damages the
    newest committed on-disk snapshot and is chased by a kill in the same
    tick, so the very next recovery must fall back through the damage.
    Verified exactly-once against a clean run; per fault the record shows
    the victim snapshot id and recovery gap, and ``fallback`` proves every
    corrupted id was rejected with a verification reason."""
    import tempfile

    from repro.core.engine import RestartPolicy
    from repro.runtime.chaos import CORRUPTION_KINDS, ChaosSchedule

    clean = _paced_q5(backend, rate, total, threads, n_nodes)
    expected = max(200, (total * 1000 // int(rate)) // 20)
    schedule = ChaosSchedule.corruption_from_seed(seed, n_faults, expected)
    with tempfile.TemporaryDirectory(prefix="jet-chaos-snap-") as d:
        damaged = _paced_q5(
            backend, rate, total, threads, n_nodes, schedule=schedule,
            snapshot_dir=d,
            restart_policy=RestartPolicy(max_restarts=4 * n_faults))
    arrivals = [t for t, _ in damaged["out"]]
    faults = []
    for f in schedule.faults:
        rec = {"kind": f.kind, "at_result": f.at_result,
               "fired": f.fired, "skipped": f.skipped}
        if f.fired:
            rec["fired_at_result"] = f.fired_at_result
            rec["recovery_gap_ms"] = _recovery_gap_ms(arrivals, f.fired_at)
            if "snapshot_id" in f.params:
                rec["snapshot_id"] = f.params["snapshot_id"]
        faults.append(rec)
    recovery = damaged["recovery"]
    skipped = [s for r in recovery["recovery_log"]
               if r["event"] == "restore" for s in r["skipped"]]
    corrupted = sorted({f.params["snapshot_id"] for f in schedule.faults
                        if f.fired and f.kind in CORRUPTION_KINDS})
    rejected = {s["snapshot_id"] for s in skipped
                if "verification failed" in s["reason"]
                or "restore load failed" in s["reason"]}
    return {
        "scenario": "corruption_q5", "backend": backend, "seed": seed,
        "rate": rate, "total_events": total, "workers": threads,
        "nodes": n_nodes,
        "faults": faults,
        "corrupted_snapshots": corrupted,
        "fallback": {
            # every corrupted epoch was rejected for cause, none restored
            "all_corrupted_rejected": all(sid in rejected
                                          for sid in corrupted),
            "skipped": skipped,
            "max_depth": max((r["fallback_depth"]
                              for r in recovery["recovery_log"]
                              if "fallback_depth" in r), default=0),
        },
        "auto_restarts": damaged["auto_restarts"],
        "verification": _verify(clean["out"], damaged["out"]),
        "arrival_gap_ms": _gap_stats(arrivals),
        "recovery": recovery,
    }


class _PoisonGate:
    """Pass-through processor that raises (or, for the expected-run twin,
    silently drops) on one specific record — the deterministic poison.
    The trap matches (ts, key, pickled value): the exact identity the
    engine's quarantine filter uses."""

    def __init__(self, trap, raise_on_hit: bool):
        self.trap = trap
        self.raise_on_hit = raise_on_hit

    def _hit(self, ev) -> bool:
        import pickle
        t = self.trap
        if ev.ts != t[0] or ev.key != t[1]:
            return False
        return pickle.dumps(ev.value, protocol=4) == t[2]

    def process(self, ordinal, inbox):
        ob = self.outbox
        while len(inbox):
            ev = inbox.peek()
            if self._hit(ev):
                if self.raise_on_hit:
                    raise RuntimeError("poison record reached the gate")
                inbox.remove()
                continue
            if not ob.offer(ev):
                return
            inbox.remove()


def poison_q5(rate: float = 20_000, total: int = 8_000,
              poison_seq: int = 900, threads: int = 2,
              n_nodes: int = 2, timeout_s: float = 300.0) -> Dict:
    """Deterministic poison record against the escalation ladder: a gate
    vertex crashes on one specific bid every replay; the engine must
    fingerprint the recurrence, pinpoint the record, quarantine it
    (dead-letter, exactly once) and complete within the restart budget
    with output equal to a run that never saw the record."""
    import pickle

    from repro.core import (CollectorSink, JetCluster, JobConfig,
                            PacedGeneratorSource, Processor,
                            GUARANTEE_EXACTLY_ONCE)
    from repro.core.engine import JOB_COMPLETED, JOB_FAILED, RestartPolicy
    from repro.core.pipeline import Pipeline
    from repro.core.window import counting, sliding
    from repro.nexmark import NexmarkGenerator
    from repro.nexmark.queries import bid_auction, is_bid

    gen = NexmarkGenerator(rate=rate, n_keys=40)
    seq = poison_seq
    while not is_bid(gen(seq)[2]):
        seq += 1
    ts, key, value = gen(seq)
    trap = (ts, key, pickle.dumps(value, protocol=4))

    class Gate(_PoisonGate, Processor):
        pass

    def one_run(raise_on_hit: bool):
        cluster = JetCluster(n_nodes=n_nodes, cooperative_threads=threads,
                             backend="inproc")
        out: list = []
        p = Pipeline.create()
        (p.read_from(lambda: PacedGeneratorSource(
                NexmarkGenerator(rate=rate, n_keys=40),
                rate=rate, max_events=total), name="bids")
            .custom_transform("gate", lambda: Gate(trap, raise_on_hit))
            .filter(is_bid)
            .with_key(bid_auction)
            .window(sliding(100, 20))
            .aggregate(counting())
            .write_to(lambda: CollectorSink(out, with_time=True)))
        job = cluster.submit(p.to_dag(), JobConfig(
            processing_guarantee=GUARANTEE_EXACTLY_ONCE,
            snapshot_interval_s=0.1,
            restart_policy=RestartPolicy(max_restarts=8,
                                         fingerprint_threshold=2)))
        try:
            deadline = time.monotonic() + timeout_s
            while job.status not in (JOB_COMPLETED, JOB_FAILED):
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"poison run stuck: {job.recovery_diagnostics()}")
                cluster.step()
        finally:
            cluster.shutdown()
        return out, job, job.status == JOB_COMPLETED

    expected_out, _, expected_done = one_run(raise_on_hit=False)
    out, job, completed = one_run(raise_on_hit=True)
    recovery = job.recovery_diagnostics()
    return {
        "scenario": "poison_q5", "backend": "inproc", "rate": rate,
        "total_events": total, "poison_seq": seq,
        "poison_record": repr(value),
        "completed": completed and expected_done,
        "quarantined": len(job.dead_letters),
        "auto_restarts": job.auto_restarts,
        "restart_budget": 8,
        "escalations": [e for e in recovery["recovery_log"]
                        if e["event"] == "escalation"],
        # the surviving stream vs a run that never saw the record
        "verification": _verify(expected_out, out),
        "recovery": recovery,
    }


def active_active_flip(rate: float = 2_000, total: int = 2_000,
                       kill_after_results: int = 50) -> Dict:
    """Hot-standby flip (§4.6, in-process replicas): primary dies
    mid-stream, the standby keeps emitting — the recovery gap is the
    output stream's ordinary cadence, no restore involved."""
    from repro.core import PacedGeneratorSource
    from repro.core.engine import JOB_COMPLETED
    from repro.core.processor import SinkProcessor
    from repro.nexmark import NexmarkGenerator, queries
    from repro.snapshot import ActiveActiveRunner

    arrivals: List[float] = []

    def build(sink_consumer):
        def consume(ev):
            arrivals.append(time.monotonic())
            sink_consumer(ev)
        gen = NexmarkGenerator(rate=rate, n_keys=40)
        return queries.q5(
            lambda: PacedGeneratorSource(gen, rate=rate, max_events=total),
            lambda: SinkProcessor(consume), window_ms=100, slide_ms=20)

    runner = ActiveActiveRunner(
        build, id_fn=lambda ev: (ev.ts, ev.key, ev.value.window_end),
        n_nodes=2)
    killed_at: Optional[float] = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        runner.step()
        if (killed_at is None
                and len(runner.output.results) >= kill_after_results
                and runner.jobs[0].status != JOB_COMPLETED):
            runner.kill_replica(0)
            killed_at = time.monotonic()
        done = [j.status == JOB_COMPLETED
                for i, j in enumerate(runner.jobs) if i != runner.failed]
        if done and all(done):
            break
    n_results = len(runner.output.results)
    survivors = {rep for rep, _ in runner.output.results.values()}
    return {
        "scenario": "active_active_flip", "rate": rate,
        "total_events": total,
        "flip_recovery_gap_ms": (None if killed_at is None
                                 else _recovery_gap_ms(arrivals, killed_at)),
        "results": n_results,
        "standby_contributed": 1 in survivors,
        "duplicates_deduped": runner.output.duplicates,
        "primary_killed": killed_at is not None,
    }


def run(quick: bool = True, seeds=(1, 2, 3)) -> Dict:
    from repro.core.shm_ring import sweep_leaked_rings

    swept = sweep_leaked_rings()
    seeds = seeds[:1] if quick else seeds
    mp_runs = [chaos_q5("mp", seed=s) for s in seeds]
    section = {
        "meta": {
            "metric": "recovery gap (ms) per injected fault; exactly-once "
                      "verification against a clean run",
            "quick": quick,
            "seeds": list(seeds),
            "swept_leaked_rings": len(swept),
        },
        "mp": mp_runs,
        "inproc": chaos_q5("inproc", seed=seeds[0]),
        "rescale": rescale_q5("mp"),
        "active_active": active_active_flip(),
        "corruption": corruption_q5("mp", seed=seeds[0]),
        "poison": poison_q5(),
    }
    return section


def smoke(seed: int = 1) -> Dict:
    """CI gate: one seeded worker kill + one delayed barrier ack against
    2 mp workers, one seeded snapshot corruption (+ chasing kill) over a
    durable chain, and one deterministic poison record — each verified
    exactly-once against a clean run.  Writes no reports; the caller
    exits nonzero when ``ok`` is False."""
    from repro.runtime.chaos import KIND_DELAY_ACK, KIND_KILL
    from repro.core.shm_ring import sweep_leaked_rings

    sweep_leaked_rings()
    kinds = (KIND_KILL, KIND_DELAY_ACK)
    clean = _paced_q5("mp", 60_000, 36_000, 2, 1)
    chaos = _paced_q5("mp", 60_000, 36_000, 2, 1, seed=seed,
                      n_faults=len(kinds), kinds=kinds)
    fired = sorted({f.kind for f in chaos["faults"] if f.fired})
    verification = _verify(clean["out"], chaos["out"])
    corruption = corruption_q5("mp", seed=seed, n_faults=1,
                               total=36_000, n_nodes=1)
    poison = poison_q5()
    return {
        "scenario": "smoke", "seed": seed, "fault_kinds_fired": fired,
        "auto_restarts": chaos["auto_restarts"],
        "snapshots_aborted": chaos["snapshots_aborted"],
        "verification": verification,
        "corruption": corruption,
        "poison": poison,
        "ok": (verification["results_match"] and set(fired) == set(kinds)
               and corruption["verification"]["results_match"]
               and corruption["fallback"]["all_corrupted_rejected"]
               and bool(corruption["corrupted_snapshots"])
               and poison["completed"] and poison["quarantined"] == 1
               and poison["verification"]["results_match"]),
    }


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all seeds (default: one seed, quick mode)")
    ap.add_argument("--seeds", type=int, nargs="*", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: 1 kill + 1 delayed ack on 2 mp "
                         "workers, plus a snapshot-corruption and a "
                         "poison-record pass; nonzero "
                         "exit on verification failure")
    ap.add_argument("--diagnostics", type=pathlib.Path, default=None,
                    help="with --smoke: dump the corruption + poison "
                         "recovery diagnostics (restores, skipped "
                         "snapshots + reasons, escalations, dead "
                         "letters) to this JSON file (the CI artifact)")
    args = ap.parse_args()
    if args.smoke:
        import sys
        result = smoke()
        print(json.dumps(result, indent=1, default=float))
        if args.diagnostics is not None:
            args.diagnostics.write_text(json.dumps({
                "ok": result["ok"],
                "corruption": result["corruption"],
                "poison": result["poison"],
            }, indent=1, default=float) + "\n")
            print(f"# wrote {args.diagnostics}")
        sys.exit(0 if result["ok"] else 1)
    seeds = tuple(args.seeds) if args.seeds else (1, 2, 3)
    section = run(quick=not args.full, seeds=seeds)
    print(json.dumps(section, indent=1, default=float))
