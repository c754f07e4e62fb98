#!/usr/bin/env python3
"""Readings from which the limits of ``bench/check.py`` are set.

    python3 bench/control.py --workload q5-jet.saturated --seeds 11,12,13 \\
        --seconds 10

Runs the cell once per seed in one process (set-up is paid once per seed,
compiles come from the cache) and prints, per seed, one JSON line with
the numbers compared for the program and for the control.  The control is
the reference put in the program's place and computed one precision
below the float32 the configuration states: its window totals in
bfloat16, answering at the same window ends the program answered.
It has to come out as not correct.  Needs the chip, as ``bench/run.py``
does; the benchmark's own runs never run this.
"""

import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_numbers(line):
    """The numbers compared, with the bfloat16 reference in the program's
    place at the window ends (and keys) the program sent."""
    import importlib
    import ml_dtypes
    import numpy as np
    cols, totals = line["_cols"], line["_totals"]
    cfg = line["_obs"].cell.config
    query = importlib.import_module(f"bench.queries.{cfg['query']}")
    low = totals.astype(ml_dtypes.bfloat16).astype(np.float64)
    key, value = query.answers(cfg, low, cols["end"], cols["key"])
    return query.compare(cfg, dict(cols, key=key, value=value), totals,
                         line["_due"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    cell = harness.load_cell(args.workload)
    t_start = T_PROC
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = harness.run(cell, seed, args.seconds, False, t_start)
        except harness.NoChip as e:
            print(f"bench: {e}", file=sys.stderr)
            return 1
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": line["correct"], "attempted": line["attempted"],
            "metrics": line["metrics"],
            "program": {k: c["value"] for k, c in line["checks"].items()},
            "control": control_numbers(line)}), flush=True)
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
