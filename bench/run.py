#!/usr/bin/env python3
"""Run one cell of the benchmark that ``BENCHMARK.json`` defines.

    python3 bench/run.py --workload q5-hop.paced --seed 7 --seconds 30 \\
        --trace 0

Runs from the root of a checkout that holds the program under ``src/``,
on a machine whose JAX sees the TPU chips the cell asks for; with no such
chip it exits non-zero and prints no result.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit, which also end standard error.
"""

import time

T_PROC = time.monotonic()        # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401  the system under test
        from bench import harness
        cell = harness.load_cell(args.workload)
    except (ImportError, KeyError, OSError) as e:
        print(f"bench: cannot set up {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # the step compiles in about a second: cache it however fast it was
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROC)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"bench: settled {line['_settle_s']:.3f} s past the warm-up",
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps({k: v for k, v in line.items()
                      if not k.startswith("_")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
