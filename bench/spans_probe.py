#!/usr/bin/env python3
"""One run of a benchmark cell, with the program's own spans read.

    python3 bench/spans_probe.py --workload q5-jet.saturated --seed 7 \\
        --seconds 50 --mode spans

Runs the cell through ``bench.harness.run``, as ``bench/run.py`` does, in
one of three modes: ``off`` (the untraced run), ``profiler`` (the traced
run of ``--trace 1``) and ``spans`` (that traced run with
``repro.core.spans`` turned on right after the profiler starts and off
before it stops).  The last line of standard output is one JSON object:

* ``end_to_end``: the cell's end-to-end metrics, in every mode, so that
  the three modes give what tracing costs;
* ``per_layer`` and ``breakdown``: the harness's own, in the traced modes;
* ``counters``: the device window's readback counters
  (``ExecutionContext.stats()``) differenced over the window, and
  ``readback_bytes_per_step`` from them;
* ``program`` (``spans`` mode): the readers of :mod:`bench.program_spans`
  over the window, ``idle_gaps_by_span`` (the harness's ten longest idle
  gaps, each named by the program span whose self time covers most of
  it, else by the harness's vertex label) and ``chains`` (window ends
  answered in the window, and how many of them have a whole
  submit -> convert -> emit chain), ``self_s`` (self time per span name)
  and ``counts`` (spans per name that start in the window, and
  ``jet.gc`` per generation).

Needs the chip, as ``bench/run.py`` does; the benchmark's own runs never
run this.  Against a program without ``repro.core.spans`` the ``spans``
mode reads what ``profiler`` reads.
"""

import time

T_PROC = time.monotonic()        # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("readback_bytes", "emitting_steps")


def _counters(cluster):
    out = dict.fromkeys(COUNTERS, 0)
    for job in cluster.jobs:
        if job.execution is not None:
            stats = job.execution.stats()
            for k in COUNTERS:
                out[k] += stats.get(k, 0)
    return out


def _program(logdir, summary):
    """The program-span readings of one traced window."""
    from bench import harness, program_spans as ps, trace as tracing
    path = str(sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))[-1])
    spans, window = ps.load(path, harness.WINDOW_SPAN)
    lo, hi = window
    ops = tracing.load(path).ops
    # the harness's ten longest gaps, in its order, with its labels
    gaps = sorted(tracing.idle_gaps(ops[sorted(ops)[0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    pt = ps.ProgramTrace(lo, hi, spans)
    chains = ps.answer_chains(pt)
    metrics = {}
    for name, read in ps.METRICS.items():
        value = read(pt)
        if value is not None:
            metrics[name] = value
    return {
        "metrics": metrics,
        "idle_gaps_by_span": ps.label_gaps(
            gaps, spans, [g[0] for g in summary.longest_gaps]),
        "chains": {"answered": len(chains), "whole": sum(
            1 for c in chains.values()
            if c["submit"] is not None and c["converts"])},
        "self_s": {n: v / 1e9 for n, v in sorted(
            ps.self_ns(spans, lo, hi).items())},
        "counts": ps.counts(spans, lo, hi),
        "spans": len(spans),
    }


@contextlib.contextmanager
def _hooks(mode, seen):
    """Within: the harness reads the counters beside its worker timings
    (it reads those at the window's open and close) and, in ``spans``
    mode, turns spans on with the profiler and reads them from its
    trace."""
    import jax
    from bench import harness
    try:
        from repro.core import spans
    except ImportError:
        spans = None
    vertex_seconds = harness._vertex_seconds
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    reduce_trace = harness._reduce_trace

    def timings(cluster):
        seen["counters"].append(_counters(cluster))
        return vertex_seconds(cluster)

    def start_trace(*a, **kw):
        start(*a, **kw)
        spans.enable()

    def stop_trace():
        spans.disable()
        stop()

    def reduce(logdir, host, perf_open):
        summary = reduce_trace(logdir, host, perf_open)
        seen["program"] = _program(logdir, summary)
        return summary

    harness._vertex_seconds = timings
    if mode == "spans" and spans is not None:
        jax.profiler.start_trace = start_trace
        jax.profiler.stop_trace = stop_trace
        harness._reduce_trace = reduce
    try:
        yield
    finally:
        harness._vertex_seconds = vertex_seconds
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        harness._reduce_trace = reduce_trace


def probe(cell, seed, seconds, mode, t_proc, require_tpu=True):
    """One run of ``cell`` in ``mode``; returns the result object."""
    from bench import harness, program_spans as ps
    seen = {"counters": []}
    with _hooks(mode, seen):
        line = harness.run(cell, seed, seconds, mode != "off", t_proc,
                           require_tpu=require_tpu)
    e2e = {}
    for m in cell.end_to_end:
        value = harness.read_metric(cell.root, m["name"], line["_obs"])
        if value is not None:
            e2e[m["name"]] = float(value)
    c0, c1 = seen["counters"][0], seen["counters"][-1]
    counters = {k: c1[k] - c0[k] for k in COUNTERS}
    counters["readback_bytes_per_step"] = ps.readback_bytes_per_step(
        counters)
    out = {"workload": cell.name, "seed": seed, "mode": mode,
           "correct": line["correct"], "end_to_end": e2e,
           "counters": counters, "device": line["device"]}
    if mode != "off":
        out["per_layer"] = {k: v["value"] for k, v in line["metrics"].items()}
        out["breakdown"] = line["breakdown"]
    if "program" in seen:
        out["program"] = seen["program"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("off", "profiler", "spans"),
                    required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        out = probe(cell, args.seed, args.seconds, args.mode, T_PROC)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
