"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic contract its kernel must match bit-for-bit
(integer outputs) or to float tolerance (accumulations). Tests sweep shapes
and dtypes asserting ``assert_allclose(kernel(interpret=True), ref)``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ------------------------------------------------------------- cache probe
def cache_probe_ref(key_hi, key_lo, write_ts, values, q_hi, q_lo, buckets,
                    now_ms, ttl_ms):
    """Set-associative TTL probe (same contract as core.cache.lookup, with
    bucket indices precomputed — the kernel's scalar-prefetch input).

    key_hi/key_lo/write_ts: (Nb, W); values: the (D, Nb * W) value plane
    (slot (bucket, way) is column bucket * W + way); q_hi/q_lo/buckets:
    (B,). Returns (hit (B,) bool, value (B, D),
    age (B,) int32 — -1 on miss, way (B,) int32 — hit way, -1 on miss).
    """
    k_hi = key_hi[buckets]                   # (B, W)
    k_lo = key_lo[buckets]
    ts = write_ts[buckets]
    match = (k_hi == q_hi[:, None]) & (k_lo == q_lo[:, None])
    # TS_EMPTY lanes wrap negative but never match; `match` masks them.
    fresh = (jnp.int32(now_ms) - ts) <= jnp.int32(ttl_ms)  # erlint: allow[ER004]
    valid = match & fresh
    hit = jnp.any(valid, axis=-1)
    way = jnp.argmax(valid, axis=-1)
    out = values[:, buckets * key_hi.shape[1] + way].T
    out = jnp.where(hit[:, None], out, 0.0)
    # erlint: allow[ER004] — miss lanes forced to -1 by the hit mask
    age = jnp.where(hit, jnp.int32(now_ms) - ts[jnp.arange(buckets.shape[0]),
                                                way], jnp.int32(-1))
    return hit, out, age, jnp.where(hit, way.astype(jnp.int32),
                                    jnp.int32(-1))


# ----------------------------------------------------------- embedding bag
def embedding_bag_ref(table, ids, mode: str = "sum"):
    """table (V, D); ids (B, nnz) int32, -1 = padding → (B, D)."""
    mask = ids >= 0
    rows = jnp.take(table, jnp.maximum(ids, 0), axis=0).astype(jnp.float32)
    rows = jnp.where(mask[..., None], rows, 0.0)
    out = rows.sum(axis=1)                     # fp32 accumulation contract
    if mode == "mean":
        out = out / jnp.maximum(mask.sum(axis=1, keepdims=True), 1)
    return out.astype(table.dtype)


# --------------------------------------------------------- flash attention
def flash_attention_ref(q, k, v, *, causal: bool = True, q_offset: int = 0):
    """q (B, Sq, Hq, hd); k, v (B, Sk, Hkv, hd); GQA by head repetition.
    fp32 softmax, output in q.dtype."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    n_rep = Hq // Hkv
    kr = jnp.repeat(k, n_rep, axis=2)
    vr = jnp.repeat(v, n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * (hd ** -0.5)
    if causal:
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where((ki <= qi)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)


# -------------------------------------------------------- decode attention
def decode_attention_ref(q, k, v, valid_len=None):
    """q (B, Hq, hd); k, v (B, S, Hkv, hd); valid_len (B,) int32 masks
    positions ≥ len. fp32 online-softmax-equivalent result."""
    B, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    n_rep = Hq // Hkv
    kr = jnp.repeat(k, n_rep, axis=2)
    vr = jnp.repeat(v, n_rep, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * (hd ** -0.5)
    if valid_len is not None:
        mask = jnp.arange(S)[None, None, :] < valid_len[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, vr.astype(jnp.float32))
    return o.astype(q.dtype)
