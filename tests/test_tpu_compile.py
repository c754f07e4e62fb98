"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode and the CPU backend accept: a
kernel block not aligned to the tiling, a program larger than the chip's
memory, a collective that cannot be partitioned.  These compile the device
path at its one-chip cell size and on a 2x2 mesh, plus both Pallas kernels
at real widths, in a few seconds each.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import route, window_agg
from repro.streaming import (StreamExecutor, StreamJobConfig,
                             VectorWindowSpec, window_state_init)

BATCH, BUCKETS = 65_536, 16_384
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec():
    # the paper-extreme Q5 window at the one-chip smoke cell's bucket count
    return VectorWindowSpec(size_ms=1000, slide_ms=10, n_key_buckets=BUCKETS,
                            max_windows_per_step=8, ring_margin=8,
                            frontier_from_data=False)


def _shapes(spec, state_sharding, batch_sharding):
    state = jax.eval_shape(lambda: window_state_init(spec))
    state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                     sharding=state_sharding(k))
             for k, v in state.items()}
    dtypes = {"ts": jnp.int32, "key": jnp.int32, "value": jnp.float32,
              "valid": jnp.bool_}
    batch = {k: jax.ShapeDtypeStruct((BATCH,), d, sharding=batch_sharding(k))
             for k, d in dtypes.items()}
    batch["wm"] = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=batch_sharding("wm"))
    return state, batch


def test_single_chip_step_compiles_and_fits(one_chip):
    ex = StreamExecutor(StreamJobConfig(window=_spec(), batch_size=BATCH))
    state, batch = _shapes(ex.cfg.window, lambda k: one_chip,
                           lambda k: one_chip)
    lowered = ex._step.lower(state, batch)
    # the emission matmul must not run at the TPU's bf16 DEFAULT precision
    # (window sums above 256 would round)
    dots = [line for line in lowered.as_text().splitlines()
            if "dot_general" in line]
    assert dots and all("HIGHEST" in line for line in dots), dots
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM


@pytest.mark.parametrize("exchange,collective",
                         [("reduce", "reduce-scatter"),
                          ("route", "all-to-all")])
def test_four_chip_step_compiles(topo, exchange, collective):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    ex = StreamExecutor(StreamJobConfig(window=_spec(), batch_size=BATCH,
                                        exchange=exchange), mesh=mesh)
    state_specs = {"panes": P(None, "data")}
    state, batch = _shapes(
        ex.cfg.window,
        lambda k: NamedSharding(mesh, state_specs.get(k, P())),
        lambda k: NamedSharding(mesh, P() if k == "wm" else P("data")))
    compiled = ex._step.lower(state, batch).compile()
    assert collective in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < V5E_HBM


def test_window_agg_kernel_compiles(one_chip):
    spec = _spec()
    ev = {d: jax.ShapeDtypeStruct((BATCH,), d, sharding=one_chip)
          for d in (jnp.int32, jnp.float32, jnp.bool_)}
    fn = jax.jit(lambda k, s, v, ok: window_agg.window_agg(
        k, s, v, ok, BUCKETS, spec.ring_len, interpret=False))
    args = (ev[jnp.int32], ev[jnp.int32], ev[jnp.float32], ev[jnp.bool_])
    # the kernel's matmul must not take event values as bfloat16 (values
    # above 256 would round); the Mosaic payload is opaque, the jaxpr not
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert "dot_general" in jaxpr
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in jaxpr
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_partitions", [128, 256])
def test_route_counts_kernel_compiles(one_chip, n_partitions):
    pids = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((BATCH,), jnp.bool_, sharding=one_chip)
    fn = jax.jit(lambda p, ok: route.route_counts(
        p, ok, n_partitions, interpret=False))
    compiled = fn.lower(pids, valid).compile()
    assert "tpu_custom_call" in compiled.as_text()
