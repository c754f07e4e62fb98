"""jit'd public wrappers over the Pallas kernels.

``interpret=None`` (the default) follows the platform: the kernels compile
for the TPU when JAX runs on one and run in the Pallas interpreter
anywhere else (see :func:`repro.kernels.resolve_interpret`).  A bool
forces either mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .decode_attn import decode_attention as _decode_attention
from .route import route_counts as _route_counts, route_offsets
from .window_agg import window_agg as _window_agg


@functools.partial(jax.jit,
                   static_argnames=("n_key_buckets", "ring_len",
                                    "interpret"))
def window_agg(keys, slots, values, valid, n_key_buckets: int,
               ring_len: int, interpret: Optional[bool] = None):
    return _window_agg(keys, slots, values, valid, n_key_buckets, ring_len,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_partitions", "interpret"))
def route_counts(pids, valid, n_partitions: int,
                 interpret: Optional[bool] = None):
    return _route_counts(pids, valid, n_partitions, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, pos, interpret: Optional[bool] = None):
    return _decode_attention(q, k, v, pos, interpret=interpret)
