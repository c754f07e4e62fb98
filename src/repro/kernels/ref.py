"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def window_agg_ref(keys, slots, values, valid, n_key_buckets: int,
                   ring_len: int):
    """One-hot matmul formulation, evaluated directly in jnp."""
    vals = jnp.where(valid, values, 0.0).astype(jnp.float32)
    onehot_k = jax.nn.one_hot(jnp.where(valid, keys, -1), n_key_buckets,
                              dtype=jnp.float32)
    onehot_r = jax.nn.one_hot(jnp.where(valid, slots, -1), ring_len,
                              dtype=jnp.float32)
    return jnp.einsum("nk,nr->kr", onehot_k, onehot_r * vals[:, None],
                      precision=jax.lax.Precision.HIGHEST)


def route_counts_ref(pids, valid, n_partitions: int):
    onehot = jax.nn.one_hot(jnp.where(valid, pids, -1), n_partitions,
                            dtype=jnp.int32)
    return jnp.sum(onehot, axis=0).astype(jnp.int32)


def decode_attention_ref(q, k, v, pos):
    """GQA decode: q (B,H,dh), k/v (B,Hk,S,dh), H = Hk*G; positions <= pos."""
    B, H, dh = q.shape
    Hk, S = k.shape[1], k.shape[2]
    G = H // Hk
    qg = q.reshape(B, Hk, G, dh)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    mask = jnp.arange(S)[None, None, None, :] <= pos
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, H, dh)
