"""Benchmark entry point: one section per paper table/figure plus the
device tier and the roofline summary.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) and a
human-readable summary. ``--full`` lengthens runs; default is quick mode.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import traceback


def _csv_rows(rows, key_metric="p99.99", scale=1000.0):
    out = []
    for r in rows:
        name = r.get("figure", "bench")
        for k in ("query", "rate", "nodes", "mode", "jobs", "batch"):
            if k in r:
                name += f".{k}={r[k]}"
        if r.get(key_metric) is not None:
            us = r[key_metric] * scale       # ms -> us
        elif "p99.9" in r:
            # p99.99 reported unreliable (<10k samples): fall back a decade
            us = (r["p99.9"] or 0.0) * scale
        elif "us_per_call" in r:
            us = r["us_per_call"]
        elif "us_per_step" in r:
            us = r["us_per_step"]
        else:
            us = 0.0
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("figure",))
        out.append(f"{name},{us:.3f},{derived}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="smoke target: only the p99.99 latency harness "
                         "(both tiers); emits BENCH_latency.json")
    ap.add_argument("--skip-host", action="store_true",
                    help="skip the wall-clock host-tier figures")
    ap.add_argument("--backend", choices=("inproc", "mp"), default="inproc",
                    help="execution substrate for the paced host-tier run: "
                         "cooperative in-process simulation (default) or "
                         "real worker processes over shared-memory rings")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="cooperative threads (inproc) / worker processes "
                         "(mp) for the paced host-tier run; default 2")
    args = ap.parse_args()
    quick = not args.full

    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    from . import bench_device_tier, bench_figures, bench_latency, roofline

    all_rows = []
    print("name,us_per_call,derived")

    latency_rows = lambda: bench_latency.rows(  # noqa: E731
        quick=quick, backend=args.backend, workers=args.workers)
    if args.quick:
        # CI smoke target: the latency harness alone keeps the perf
        # trajectory (BENCH_latency.json) accumulating per PR; it runs
        # the host tier (both substrates: inproc + mp saturation curve),
        # the device tier AND the host_to_device bridge (the device-placed
        # window vertex), taking precedence over --skip-host
        sections = [("latency", latency_rows)]
    else:
        sections = []
        if not args.skip_host:
            # the latency harness drives the wall-clock host tier too
            sections.append(("latency", latency_rows))
            sections += [
                ("fig7",
                 lambda: bench_figures.fig7_throughput_vs_latency(quick)),
                ("fig8", lambda: bench_figures.fig8_scaleout_latency(quick)),
                ("fig9",
                 lambda: bench_figures.fig9_latency_distribution(quick)),
                ("fig10",
                 lambda: bench_figures.fig10_scaleout_throughput(quick)),
                ("fig13",
                 lambda: bench_figures.fig13_fault_tolerance_overhead(quick)),
                ("sec7.7", lambda: bench_figures.sec77_multitenancy(quick)),
            ]
        sections += [
            ("device_q5",
             lambda: bench_device_tier.bench_vector_q5(quick=quick)),
            ("kernels", lambda: bench_device_tier.bench_kernels(quick=quick)),
        ]

    failed = []
    for name, fn in sections:
        try:
            rows = fn()
        except Exception as e:  # pragma: no cover
            # report and go on with the other sections, but the run fails
            traceback.print_exc()
            print(f"{name},0.0,ERROR={e!r}", flush=True)
            failed.append(name)
            continue
        all_rows.extend(rows)
        for line in _csv_rows(rows):
            print(line, flush=True)

    # roofline summary (from the dry-run artifacts, if present)
    rl = roofline.full_table()
    for r in rl:
        print(f"roofline.{r['arch']}.{r['shape']},"
              f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.1f},"
              f"dominant={r['dominant']};useful={r['useful_ratio']:.2f};"
              f"bound={r['roofline_fraction_bound']:.3f};"
              f"gib={r['temp_gib_per_chip']:.1f}", flush=True)

    out = pathlib.Path(__file__).resolve().parents[1] / "experiments"
    out.mkdir(exist_ok=True)
    (out / "bench_results.json").write_text(
        json.dumps({"figures": all_rows, "roofline": rl}, indent=1,
                   default=float))
    print(f"# wrote {out / 'bench_results.json'}", file=sys.stderr)
    # the latency section appends the per-run record (git SHA, saturation
    # A/B, paced + device percentiles) to the cumulative cross-PR log
    traj = out.parent / "BENCH_trajectory.json"
    if traj.exists():
        n = len(json.loads(traj.read_text()))
        print(f"# perf trajectory: {traj} ({n} records)", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
