"""Pallas TPU kernel: keyed pane aggregation as one-hot matmuls.

The Jet stage-1 accumulate (events -> per-(key-bucket, frame-slot) partial
aggregates) is a scatter-add on CPU/GPU.  TPUs have no fast scatter; the
TPU-native formulation builds two one-hot matrices per event tile and
contracts them on the MXU:

    out[k, r] = sum_n onehot_k[n, k] * onehot_r[n, r] * value[n]
              = (onehot_k)^T @ (onehot_r * value[:, None])

Grid: (K / BK) key tiles x (N / BN) event tiles; the event dimension is
minormost so each key tile accumulates across event tiles in its output
block (revisited blocks stay resident in VMEM).  BK is a multiple of the
128-lane MXU width; R (the frame ring, <= ~32) rides along as the second
matmul dim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

BK = 128     # key-bucket tile (MXU-aligned)
BN = 1024    # event tile


def _kernel(key_ref, slot_ref, val_ref, out_ref, *, R: int, BK: int):
    kt = pl.program_id(0)
    nt = pl.program_id(1)

    @pl.when(nt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = key_ref[...]                      # (BN,) int32
    slots = slot_ref[...]                    # (BN,) int32
    vals = val_ref[...]                      # (BN,) f32 (0 where invalid)

    k_base = kt * BK
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], BK), 1)
    onehot_k = jnp.where(keys[:, None] == k_base + k_iota, 1.0, 0.0
                         ).astype(jnp.float32)                # (BN, BK)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (keys.shape[0], R), 1)
    onehot_rv = jnp.where(slots[:, None] == r_iota, 1.0, 0.0
                          ).astype(jnp.float32) * vals[:, None]  # (BN, R)
    # HIGHEST: at the default precision the MXU takes f32 operands as
    # bfloat16, which rounds any event value above 256
    out_ref[...] += jax.lax.dot_general(
        onehot_k, onehot_rv, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                    # (BK, R)


def window_agg(keys, slots, values, valid, n_key_buckets: int, ring_len: int,
               block_k: int = BK, block_n: int = BN,
               interpret: Optional[bool] = None):
    """keys/slots: (N,) int32; values/valid: (N,). Returns (K, R) f32.

    Non-tile-multiple shapes are handled by padding: the event axis pads
    with ``valid=False`` rows (value forced to 0 below, so they contribute
    nothing) and the key axis pads to the next tile multiple with buckets
    no event points at; the padded key rows are sliced off the result.
    """
    N = keys.shape[0]
    K, R = n_key_buckets, ring_len
    if N == 0:
        return jnp.zeros((K, R), jnp.float32)
    bn = min(block_n, N)
    bk = min(block_k, K)
    n_pad = (-N) % bn
    if n_pad:
        keys = jnp.concatenate([keys, jnp.zeros((n_pad,), keys.dtype)])
        slots = jnp.concatenate([slots, jnp.zeros((n_pad,), slots.dtype)])
        values = jnp.concatenate(
            [values, jnp.zeros((n_pad,), values.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((n_pad,), bool)])
        N += n_pad
    k_pad = (-K) % bk
    K_padded = K + k_pad
    vals = jnp.where(valid, values, 0.0).astype(jnp.float32)
    # out-of-range guard: invalid events point at a bucket that exists but
    # carry value 0, so they contribute nothing
    keys = jnp.where(valid, keys, 0).astype(jnp.int32)
    slots = jnp.where(valid, slots, 0).astype(jnp.int32)
    grid = (K_padded // bk, N // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, R=R, BK=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn,), lambda kt, nt: (nt,)),
            pl.BlockSpec((bn,), lambda kt, nt: (nt,)),
            pl.BlockSpec((bn,), lambda kt, nt: (nt,)),
        ],
        out_specs=pl.BlockSpec((bk, R), lambda kt, nt: (kt, 0)),
        out_shape=jax.ShapeDtypeStruct((K_padded, R), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(keys, slots, vals)
    return out[:K] if k_pad else out
