"""Pallas TPU kernel: partition routing histogram.

Jet's exchange operator must know how many events go to each partition
before building the all-to-all (counting sort).  Histogramming is a
scatter-add on CPU; here it is the same one-hot reduction as window_agg
(matvec against ones) on the MXU:

    counts[p] = sum_n (pid[n] == p)

Grid: (P / BP) partition tiles x (N / BN) event tiles, event dim minormost
so each partition tile accumulates across event tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

BP = 128
BN = 2048


def _kernel(pid_ref, out_ref, *, BP: int):
    pt = pl.program_id(0)
    nt = pl.program_id(1)

    @pl.when(nt == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pids = pid_ref[...]                                       # (BN,)
    base = pt * BP
    iota = jax.lax.broadcasted_iota(jnp.int32, (pids.shape[0], BP), 1)
    onehot = jnp.where(pids[:, None] == base + iota, 1.0, 0.0
                       ).astype(jnp.float32)                  # (BN, BP)
    out_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).astype(jnp.int32)


def route_counts(pids, valid, n_partitions: int,
                 block_p: int = BP, block_n: int = BN,
                 interpret: Optional[bool] = None):
    """pids: (N,) int32 partition ids. Returns (P,) int32 counts."""
    N = pids.shape[0]
    P = n_partitions
    bn = min(block_n, N)
    bp = min(block_p, P)
    assert N % bn == 0 and P % bp == 0
    pids = jnp.where(valid, pids, -1).astype(jnp.int32)   # -1 matches nothing
    # the counts travel as one (1, P) row: a lane-aligned 2-D block, where
    # a 1-D (bp,) block of a longer vector mismatches XLA's tiling of it
    counts = pl.pallas_call(
        functools.partial(_kernel, BP=bp),
        grid=(P // bp, N // bn),
        in_specs=[pl.BlockSpec((bn,), lambda pt, nt: (nt,))],
        out_specs=pl.BlockSpec((1, bp), lambda pt, nt: (0, pt)),
        out_shape=jax.ShapeDtypeStruct((1, P), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(pids)
    return counts[0]


def route_offsets(pids, valid, n_partitions: int, **kw):
    """counts + exclusive-prefix offsets (the all-to-all send layout)."""
    counts = route_counts(pids, valid, n_partitions, **kw)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    return counts, offsets
