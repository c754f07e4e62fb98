"""Production mesh definition.

A *function*, not a module-level constant, so importing this module never
touches JAX device state.  The single-pod mesh is 16x16 = 256 chips (one
TPU v5e pod); the multi-pod mesh adds a leading ``pod`` axis (2 pods = 512
chips) over which data parallelism (and checkpoint failure domains)
extend.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} exist; "
            "the dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax")
    if len(devices) == n:
        # Auto axes: the sharding rules place data with
        # with_sharding_constraint, which Explicit axes (make_mesh's
        # default since JAX 0.7) refuse
        return jax.make_mesh(shape, axes, axis_types=(
            jax.sharding.AxisType.Auto,) * len(axes))
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def make_smoke_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    n = int(np.prod(shape))
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)
