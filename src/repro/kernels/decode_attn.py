"""Pallas TPU kernel: flash-decode — single-token GQA attention against a
long KV cache with online softmax over KV chunks.

The serving hot loop (decode_32k / long_500k shapes): G query heads per kv
head attend to S cached keys.  The kernel is GQA-native — kv heads are a
grid dimension and the G grouped query rows ride together in one VMEM tile,
so the cache is never expanded (the jnp path's ``_expand_kv`` materializes
G copies; measured 2+ GiB/token at internlm scale before the sharding fix).
KV chunks are the minormost grid dim, carrying the running online-softmax
(max, denom, out) in VMEM scratch; scores of size S never materialize.

Layout: q (B, Hk, G, dh); k/v (B, Hk, S, dh) head-major so a chunk block
is a contiguous (CS, dh) VMEM tile; positions > pos are masked.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

NEG_INF = -1e30
CS = 512          # kv chunk


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, CS: int):
    ct = pl.program_id(2)
    n_chunks = pl.num_programs(2)

    @pl.when(ct == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)           # (CS, dh)
    v = v_ref[0, 0].astype(jnp.float32)           # (CS, dh)
    pos = pos_ref[0]
    base = ct * CS
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, CS)
    idx = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(idx <= pos, s, NEG_INF)
    m_prev = m_ref[...]                           # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                        # (G, CS)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ct == n_chunks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k, v, pos, *, chunk: int = CS,
                     interpret: Optional[bool] = None):
    """GQA flash-decode.

    q: (B, H, dh) with H = Hk * G;  k/v: (B, Hk, S, dh);  pos: scalar int32
    (attend to positions <= pos).  Returns (B, H, dh) f32.
    The dh**-0.5 scaling is applied here (on q, once)."""
    B, H, dh = q.shape
    Hk, S = k.shape[1], k.shape[2]
    assert H % Hk == 0, (H, Hk)
    G = H // Hk
    cs = min(chunk, S)
    assert S % cs == 0
    qg = (q * (dh ** -0.5)).reshape(B, Hk, G, dh).astype(q.dtype)
    pos_arr = jnp.full((1,), pos, jnp.int32)
    grid = (B, Hk, S // cs)
    out = pl.pallas_call(
        functools.partial(_kernel, CS=cs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, c: (0,)),
            pl.BlockSpec((1, 1, G, dh), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, cs, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, cs, dh), lambda b, h, c: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, h, c: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),      # running max
            pltpu.VMEM((G, 1), jnp.float32),      # running denom
            pltpu.VMEM((G, dh), jnp.float32),     # running out
        ],
        interpret=resolve_interpret(interpret),
    )(pos_arr, qg, k, v)
    return out.reshape(B, H, dh)
