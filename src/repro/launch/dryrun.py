import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: ``.lower().compile()`` every (architecture x input
shape) cell on the production meshes and extract the roofline terms.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results (memory analysis, HLO flops/bytes, per-collective byte counts) are
appended to ``experiments/dryrun/<arch>__<shape>__<mesh>.json``.
"""

import argparse
import json
import pathlib
import re
import time
import traceback

import jax

from ..configs import SHAPES, applicable_cells
from .mesh import make_production_mesh
from .specs import build_cell, lower_cell

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# HLO collective ops whose operand bytes count against the ICI roofline
COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?(\.\d+)?\s*=?\s")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """bytes of one HLO shape literal like f32[128,256]."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str, scan_multipliers=None) -> dict:
    """Sum result-shape bytes of every collective op, by kind.

    Uses the *result* shape of each collective instruction (for an
    all-reduce the result size equals the contribution moved per chip up to
    ring-algorithm constant factors; this is the standard dry-run proxy).

    CPU-backend caveat (documented in EXPERIMENTS.md): instructions inside
    a ``while`` (scan) body are counted ONCE here; per-step traffic needs
    the statically-known trip counts applied downstream.  We also report
    the per-computation breakdown so that correction is possible.
    """
    per_kind = {}
    per_comp = {}
    # global multiline pass: tuple-result collectives (a multi-operand
    # all-to-all prints its tuple shape across several lines)
    pat = re.compile(
        r"%[\w\.\-]+\s*=\s*"
        r"(\([^()]*\)|[\w\[\],\s\{\}]+?)\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start|-done)?(?:\.\d+)?\(",
        re.DOTALL)
    for m in pat.finditer(hlo_text):
        shape_part, kind = m.groups()
        total = sum(_shape_bytes(s)
                    for s in re.findall(r"\w+\[[\d,]*\]", shape_part))
        per_kind[kind] = per_kind.get(kind, 0) + total
        # attribute to the nearest enclosing computation header above
        header = hlo_text.rfind("\n%", 0, m.start())
        comp = "entry"
        if header >= 0:
            hm = re.match(r"%([\w\.\-]+)", hlo_text[header + 1:header + 120])
            if hm and "=" not in hlo_text[header:header + 120].split("(")[0]:
                comp = hm.group(1)
        per_comp[comp] = per_comp.get(comp, 0) + total
    per_kind["total"] = sum(per_kind.values())
    per_kind["by_computation"] = per_comp
    return per_kind


def run_cell(arch: str, shape: str, multi_pod: bool,
             remat_policy: str = "nothing",
             tag: str = "", cache_int8: bool = False) -> dict:
    import jax.numpy as jnp
    from ..sharding import constraints
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, remat_policy=remat_policy,
                      cache_dtype=jnp.int8 if cache_int8 else jnp.bfloat16)
    constraints.set_mesh(mesh)
    try:
        with mesh:
            lowered = lower_cell(cell)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    finally:
        constraints.set_mesh(None)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    result = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "chips": n_chips,
        "kind": cell.kind, "remat": remat_policy, "tag": tag,
        "meta": cell.static_meta,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "flops": cost.get("flops", 0.0) if cost else 0.0,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else 0.0,
        "collective_bytes": coll,
        "hlo_bytes": len(hlo),
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--remat", default="nothing",
                    choices=["nothing", "dots", "none"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = applicable_cells(args.arch)
        if args.shape:
            cells = [(a, s) for a, s in cells if s == args.shape]
    else:
        if not (args.arch and args.shape):
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            out = OUT_DIR / (f"{arch}__{shape}__{mesh_name}"
                             f"{'' if args.tag == 'baseline' else '__' + args.tag}.json")
            if args.skip_existing and out.exists():
                print(f"[skip] {out.name}")
                continue
            print(f"[dryrun] {arch} x {shape} on {mesh_name} "
                  f"(remat={args.remat}, tag={args.tag})", flush=True)
            try:
                res = run_cell(arch, shape, mp, args.remat, args.tag)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, mesh_name, repr(e)))
                continue
            out.write_text(json.dumps(res, indent=1))
            print(f"  flops={res['flops']:.3e} "
                  f"bytes={res['bytes_accessed']:.3e} "
                  f"coll={res['collective_bytes']['total']:.3e} "
                  f"temp/dev={res['memory']['temp_bytes']/2**30:.2f}GiB "
                  f"compile={res['compile_s']}s", flush=True)
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
