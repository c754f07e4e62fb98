"""Pallas TPU kernels (``window_agg``, ``route``, ``decode_attn``), their
jitted wrappers (``ops``) and pure-jnp oracles (``ref``)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel's ``interpret=None`` default: compile it for the TPU when
    JAX runs on one, interpret it anywhere else (the CPU test backend).
    Passing a bool overrides the platform."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
