import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the paper's OWN workload on the production mesh: the
device-tier Q5 step (keyed exchange via psum_scatter + pane accumulation +
window emission) and its ring-replication snapshot, lowered and compiled
for the 16x16 pod (and optionally 2x16x16).

    PYTHONPATH=src python -m repro.launch.dryrun_streaming [--multi-pod]
"""

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from ..streaming import StreamExecutor, StreamJobConfig, VectorWindowSpec
from .dryrun import OUT_DIR, collective_bytes
from .mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--keys", type=int, default=1 << 20)
    ap.add_argument("--batch", type=int, default=1 << 20)
    ap.add_argument("--exchange", default="reduce",
                    choices=["reduce", "route"])
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    n_chips = 512 if args.multi_pod else 256
    # paper-extreme Q5: 10 s window, 10 ms slide, 1M key buckets, 1M-event
    # global batches (≈ the paper's 1M events/second at one batch/second,
    # or 100x that at one batch per 10 ms slide)
    spec = VectorWindowSpec(size_ms=10_000, slide_ms=10,
                            n_key_buckets=args.keys,
                            max_windows_per_step=2, ring_margin=24)
    ex = StreamExecutor(StreamJobConfig(window=spec, batch_size=args.batch,
                                        exchange=args.exchange),
                        mesh=mesh)
    state_s = jax.eval_shape(ex.init_state)
    batch_s = {"ts": jax.ShapeDtypeStruct((args.batch,), jnp.int32),
               "key": jax.ShapeDtypeStruct((args.batch,), jnp.int32),
               "value": jax.ShapeDtypeStruct((args.batch,), jnp.float32),
               "valid": jax.ShapeDtypeStruct((args.batch,), bool),
               "wm": jax.ShapeDtypeStruct((), jnp.int32)}
    t0 = time.time()
    with mesh:
        lowered = jax.jit(ex._build_step(), donate_argnums=(0,)).lower(
            state_s, batch_s)
        compiled = lowered.compile()
        snap_lowered = jax.jit(ex._build_snapshot()).lower(state_s)
        snap_compiled = snap_lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    snap_coll = collective_bytes(snap_compiled.as_text())
    result = {
        "arch": f"jet-q5-stream-{args.exchange}",
        "shape": f"b{args.batch}-k{args.keys}",
        "mesh": mesh_name, "chips": n_chips, "kind": "stream_step",
        "remat": "-", "tag": "paper-technique",
        "meta": {"window_ms": spec.size_ms, "slide_ms": spec.slide_ms,
                 "key_buckets": args.keys, "batch": args.batch},
        "lower_s": 0.0, "compile_s": round(time.time() - t0, 1),
        "memory": {"argument_bytes": mem.argument_size_in_bytes,
                   "output_bytes": mem.output_size_in_bytes,
                   "temp_bytes": mem.temp_size_in_bytes,
                   "alias_bytes": mem.alias_size_in_bytes},
        "flops": cost.get("flops", 0.0) if cost else 0.0,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else 0.0,
        "collective_bytes": coll,
        "snapshot_collective_bytes": snap_coll["total"],
        "hlo_bytes": len(compiled.as_text()),
    }
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out = OUT_DIR / f"jet-q5-stream-{args.exchange}__{mesh_name}.json"
    out.write_text(json.dumps(result, indent=1))
    print(f"[stream dryrun {mesh_name} {args.exchange}] "
          f"compile={result['compile_s']}s "
          f"flops/chip={result['flops']:.3e} "
          f"coll={coll['total'] / 1e6:.2f}MB "
          f"snapshot_coll={snap_coll['total'] / 1e6:.2f}MB "
          f"temp/chip={mem.temp_size_in_bytes / 2**20:.1f}MiB")


if __name__ == "__main__":
    main()
