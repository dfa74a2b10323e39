"""Explicit-collective building blocks (shard_map) for the serving path.

The jit/GSPMD path covers most programs; the places where manual collectives
beat the partitioner are implemented here:

  * ``seq_sharded_decode_attention`` — flash-decode with the KV cache
    sequence-sharded across one or more mesh axes. Each shard computes a
    partial online-softmax (max, sum, weighted-acc) over its local KV slice;
    the combine is two cheap psums of (B, H) + (B, H, D) — bytes independent
    of S — instead of all-gathering the KV cache (bytes ∝ S·D). This is the
    long-context-decode enabler for ``decode_32k`` / ``long_500k``.

  * ``sharded_topk_scores`` — candidate-sharded retrieval scoring where each
    shard scores its local candidate rows; only (B, k) winners cross shards.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30

AxisNames = Union[str, Tuple[str, ...]]


def _as_tuple(axis: AxisNames) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _combined_axis_index(axes: Tuple[str, ...]) -> jnp.ndarray:
    """Row-major linear index over several mesh axes."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _local_decode_partials(q, k, v, *, kv_len_mask: Optional[jnp.ndarray]):
    """One-token attention partials over a local KV slice.

    q: (B, Hq, hd); k, v: (B, Sl, Hkv, hd). Returns (m, l, acc):
    m, l: (B, Hq) float32; acc: (B, Hq, hd) float32.
    """
    B, Sl, Hkv, hd = k.shape
    n_rep = q.shape[1] // Hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    qg = qf.reshape(B, Hkv, n_rep, hd)
    s = jnp.einsum("bknd,bskd->bkns", qg, kf).reshape(B, -1, Sl)
    if kv_len_mask is not None:
        s = jnp.where(kv_len_mask[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)                                    # (B, Hq)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    pg = p.reshape(B, Hkv, n_rep, Sl)
    acc = jnp.einsum("bkns,bskd->bknd", pg, vf).reshape(B, -1, hd)
    return m, l, acc


def decode_attention_local(q, k, v, kv_valid_len=None):
    """Single-device reference for the sharded decode (tests/smoke)."""
    if kv_valid_len is not None:
        mask = jnp.arange(k.shape[1])[None, :] < kv_valid_len[:, None]
    else:
        mask = None
    m, l, acc = _local_decode_partials(q, k, v, kv_len_mask=mask)
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def seq_sharded_decode_attention(q, k, v, mesh: Mesh,
                                 seq_axes: AxisNames = "model",
                                 batch_axes: Optional[AxisNames] = None,
                                 kv_valid_len: Optional[jnp.ndarray] = None):
    """Decode attention with KV sequence-sharded over ``seq_axes``.

    q: (B, Hq, hd) replicated along ``seq_axes``; k, v: (B, S, Hkv, hd) with
    S sharded. The merge is the standard online-softmax combine: pmax of the
    partial maxima, psum of the rescaled sums/accumulators. Collective bytes
    per step: (B·Hq) + (B·Hq·hd) floats — independent of S.
    """
    seq_axes = _as_tuple(seq_axes)
    if batch_axes is None:
        batch_axes = tuple(a for a in mesh.axis_names if a not in seq_axes)
    else:
        batch_axes = _as_tuple(batch_axes)
    # only shard batch over axes whose cumulative size divides it
    bsz = q.shape[0]
    keep, prod = [], 1
    for a in batch_axes:
        if bsz % (prod * mesh.shape[a]) == 0:
            keep.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(keep)

    def body(q_l, k_l, v_l, valid_l):
        Sl = k_l.shape[1]
        shard = _combined_axis_index(seq_axes)
        if valid_l is not None:
            pos = shard * Sl + jnp.arange(Sl)[None, :]
            mask = pos < valid_l[:, None]
        else:
            mask = None
        m, l, acc = _local_decode_partials(q_l, k_l, v_l, kv_len_mask=mask)
        m_g = jax.lax.pmax(m, seq_axes)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, seq_axes)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axes)
        out = acc_g / jnp.maximum(l_g[..., None], 1e-30)
        return out.astype(q_l.dtype)

    bspec = batch_axes if batch_axes else None
    qspec = P(bspec)
    kvspec = P(bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0])
    vspec = P(bspec)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, vspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v, kv_valid_len)


def sharded_topk_scores(query, candidates, k_top: int, mesh: Mesh,
                        cand_axes: AxisNames = ("data", "model")):
    """Retrieval scoring: (B, D) query vs (N, D) candidates row-sharded over
    ``cand_axes``. Local matmul + local top-k; only (B, k) winners per shard
    cross the interconnect (all-gather), then a final re-top-k."""
    cand_axes = _as_tuple(cand_axes)
    cand_axes = tuple(a for a in cand_axes if a in mesh.axis_names)

    def body(q_l, c_l):
        shard = _combined_axis_index(cand_axes)
        scores = jnp.einsum("bd,nd->bn", q_l.astype(jnp.float32),
                            c_l.astype(jnp.float32))
        vals, idx = jax.lax.top_k(scores, k_top)
        idx = idx + shard * c_l.shape[0]
        vals_g = vals
        idx_g = idx
        for a in cand_axes:
            vals_g = jax.lax.all_gather(vals_g, a, axis=-1, tiled=True)
            idx_g = jax.lax.all_gather(idx_g, a, axis=-1, tiled=True)
        v2, pos = jax.lax.top_k(vals_g, k_top)
        i2 = jnp.take_along_axis(idx_g, pos, axis=-1)
        return v2, i2

    spec = cand_axes if len(cand_axes) > 1 else cand_axes[0]
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(spec, None)),
        out_specs=(P(), P()),
        check_vma=False,
    )(query, candidates)


# ========================================================= sharded cache tier
# Bucket-axis sharding for the ERCache tables (DESIGN.md §11). A key's bucket
# is a pure function of the key, so under the contiguous bucket partition
# (cache.route_buckets) every query/record belongs to exactly ONE shard:
#
#   * lookup  — each shard probes its local slab at the routed local bucket;
#     per-query results are combined with a one-hot psum (at most one shard
#     contributes a non-zero row), O(B·D) bytes — never cache rows.
#   * insert/flush/touch — each shard masks the shared record stream down to
#     its owned rows and applies the NORMAL single-device plan locally. The
#     plan's dedupe / per-bucket ranks / collision resolution only couple
#     rows in the same bucket, and same-bucket rows are always co-sharded,
#     so the restricted plan is bit-identical to the global plan's
#     restriction — tests/test_shard_parity.py locks this.
#
# The wrappers below mirror their single-device counterparts in
# core/cache.py and core/writebuf.py and return REPLICATED results (global
# bucket coordinates), so servers and ring buffers upstream are unchanged.

from repro.core import cache as cache_lib
from repro.core import trace
from repro.core import writebuf as wb_lib

SHARD_AXIS = "shard"


def cache_pspec(state):
    """The bucket-axis PartitionSpecs of a (Multi)CacheState, one per
    field: bucket is axis 0 of a CacheState metadata plane, axis 1 behind
    the model axis of a MultiCacheState one, and the value plane splits
    along its entry axis (axis 1), where every shard's buckets — of every
    model, in a stacked tier — are contiguous."""
    meta = P(None, SHARD_AXIS) if isinstance(
        state, cache_lib.MultiCacheState) else P(SHARD_AXIS)
    return type(state)(key_hi=meta, key_lo=meta, write_ts=meta,
                       value_plane=P(None, SHARD_AXIS),
                       last_access_ts=meta)


def _shard_index():
    return jax.lax.axis_index(SHARD_AXIS)


def _combine_probe(res: cache_lib.LookupResult, owned, global_bucket
                   ) -> cache_lib.LookupResult:
    """One-hot reduce of per-shard probe results: at most one shard owns a
    query's bucket, so a masked psum reassembles the owner's row exactly
    (everyone else contributes zeros). Miss sentinels (-1 age/way, zero
    values) are re-imposed after the reduce; the reported bucket is the
    GLOBAL id, so downstream touch buffering stays shard-agnostic."""
    hitc = res.hit & owned
    hit = jax.lax.psum(hitc.astype(jnp.int32), SHARD_AXIS) > 0
    vals = jax.lax.psum(
        jnp.where(hitc[:, None], res.values, jnp.zeros_like(res.values)),
        SHARD_AXIS)
    age = jax.lax.psum(jnp.where(hitc, res.age_ms, 0), SHARD_AXIS)
    way = jax.lax.psum(jnp.where(hitc, res.way, 0), SHARD_AXIS)
    return cache_lib.LookupResult(
        hit=hit, values=vals,
        age_ms=jnp.where(hit, age, jnp.int32(-1)),
        bucket=global_bucket,
        way=jnp.where(hit, way, jnp.int32(-1)))


def sharded_lookup_dual(mesh: Mesh, direct, failover, keys, now_ms,
                        direct_ttl_ms, failover_ttl_ms, *,
                        backend: str = "jnp"):
    """``cache.lookup_dual`` across a bucket-sharded pair of tables.

    ONE shard_map: each shard issues the same dual probe the single-device
    path would (fused pallas launch or two jnp reference lookups) against
    its local slabs, then the per-cache one-hot combine runs inside the
    same mapped computation. Results are replicated and bit-identical to
    the unsharded oracle."""
    with jax.named_scope(trace.PROBE):
        n_shards = mesh.shape[SHARD_AXIS]
        nb_d, nb_f = direct.n_buckets, failover.n_buckets
        nbl_d = cache_lib.shard_local_buckets(nb_d, n_shards)
        nbl_f = cache_lib.shard_local_buckets(nb_f, n_shards)

        def body(d, f, k, now, ttl_d, ttl_f):
            shard = _shard_index()
            g_d = cache_lib.bucket_index(k, nb_d)
            g_f = cache_lib.bucket_index(k, nb_f)
            own_d, loc_d = cache_lib.route_buckets(g_d, shard, nb_d, nbl_d)
            own_f, loc_f = cache_lib.route_buckets(g_f, shard, nb_f, nbl_f)
            if backend == "pallas":
                from repro.kernels import cache_probe as probe_kernels

                ((hd, vd, ad, wd),
                 (hf, vf, af, wf)) = probe_kernels.cache_probe_dual(
                    d.key_hi, d.key_lo, d.write_ts, d.value_plane,
                    f.key_hi, f.key_lo, f.write_ts, f.value_plane,
                    k.hi, k.lo, loc_d, loc_f, now, ttl_d, ttl_f)
                rd = cache_lib.LookupResult(hd, vd, ad, loc_d, wd)
                rf = cache_lib.LookupResult(hf, vf, af, loc_f, wf)
            else:
                rd = cache_lib.lookup(d, k, now, ttl_d, backend=backend,
                                      buckets=loc_d)
                rf = cache_lib.lookup(f, k, now, ttl_f, backend=backend,
                                      buckets=loc_f)
            return (_combine_probe(rd, own_d, g_d),
                    _combine_probe(rf, own_f, g_f))

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(cache_pspec(direct), cache_pspec(failover), P(), P(),
                      P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )(direct, failover, keys, jnp.int32(now_ms),
          jnp.asarray(direct_ttl_ms, jnp.int32),
          jnp.asarray(failover_ttl_ms, jnp.int32))


def sharded_lookup_dual_multi(mesh: Mesh, direct, failover, policy, slots,
                              keys, now_ms, *, backend: str = "jnp"):
    """``cache.lookup_dual_multi`` across bucket-sharded stacked tiers.

    Pooled bucket ids are computed replicated (they are a pure function of
    slot/key/policy), routed per shard, and probed against each shard's
    local flat view; the combine is the same per-cache one-hot psum."""
    with jax.named_scope(trace.PROBE):
        n_shards = mesh.shape[SHARD_AXIS]
        nb_d, nb_f = direct.n_buckets, failover.n_buckets
        nbl_d = cache_lib.shard_local_buckets(nb_d, n_shards)
        nbl_f = cache_lib.shard_local_buckets(nb_f, n_shards)
        slots = jnp.asarray(slots, jnp.int32)
        b_d, b_f = cache_lib._pooled_bucket_pair(direct, failover, policy,
                                                 slots, keys)
        ttl_d = policy.ttl_ms[slots]
        ttl_f = policy.failover_ttl_ms[slots]

        def body(d, f, sl, k, g_d, g_f, td, tf, table, now):
            shard = _shard_index()
            own_d, loc_d = cache_lib.route_buckets(g_d, shard, nb_d, nbl_d)
            own_f, loc_f = cache_lib.route_buckets(g_f, shard, nb_f, nbl_f)
            fd, ff = d.flat(), f.flat()
            if backend == "pallas":
                from repro.kernels import cache_probe as probe_kernels

                ((hd, vd, ad, wd),
                 (hf, vf, af, wf)) = probe_kernels.cache_probe_dual_multi(
                    fd.key_hi, fd.key_lo, fd.write_ts, fd.value_plane,
                    ff.key_hi, ff.key_lo, ff.write_ts, ff.value_plane,
                    k.hi, k.lo, sl, loc_d, loc_f, table, now)
                rd = cache_lib.LookupResult(hd, vd, ad, loc_d, wd)
                rf = cache_lib.LookupResult(hf, vf, af, loc_f, wf)
            else:
                rd = cache_lib.lookup(fd, k, now, td, buckets=loc_d,
                                      value_buckets=d.value_buckets(loc_d))
                rf = cache_lib.lookup(ff, k, now, tf, buckets=loc_f,
                                      value_buckets=f.value_buckets(loc_f))
            return (_combine_probe(rd, own_d, g_d),
                    _combine_probe(rf, own_f, g_f))

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(cache_pspec(direct), cache_pspec(failover), P(), P(),
                      P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )(direct, failover, slots, keys, b_d, b_f, ttl_d, ttl_f,
          policy.table(), jnp.int32(now_ms))


def _touch_local(state, tb, bucket, way, nb_global, nb_local, shard):
    """One cache's deferred recency bumps, routed to this shard (global
    coordinates in the ring; -1 marks "no hit in that cache")."""
    with jax.named_scope(trace.TOUCH):
        own, loc = cache_lib.route_buckets(bucket, shard, nb_global, nb_local)
        live = wb_lib._touch_live(tb) & (bucket >= 0) & own
        return cache_lib.touch(state, loc, way, tb.ts_ms, live=live)


def sharded_flush(mesh: Mesh, buf, state, now_ms, ttl_ms, evict_lru=None,
                  touchbuf=None):
    """``writebuf.flush`` (direct tier only) across a bucket-sharded table."""
    n_shards = mesh.shape[SHARD_AXIS]
    nb = state.n_buckets
    nbl = cache_lib.shard_local_buckets(nb, n_shards)

    def body(st, b, tb, now):
        shard = _shard_index()
        if tb is not None:
            st = _touch_local(st, tb, tb.bucket_d, tb.way_d, nb, nbl, shard)
        keys, values, ts, live, _ = wb_lib._ring_order(b)
        own, loc = cache_lib.route_buckets(
            cache_lib.bucket_index(keys, nb), shard, nb, nbl)
        return cache_lib.insert(st, keys, values, now, ttl_ms,
                                write_mask=live & own, ts_ms=ts,
                                evict_lru=evict_lru, buckets=loc)

    sp = cache_pspec(state)
    new_state = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sp, P(), P(), P()),
        out_specs=sp,
        check_vma=False,
    )(state, buf, touchbuf, jnp.int32(now_ms))
    return (new_state, buf._replace(count=jnp.int32(0)),
            None if touchbuf is None
            else touchbuf._replace(count=jnp.int32(0)))


def sharded_flush_dual(mesh: Mesh, buf, direct, failover, now_ms,
                       direct_ttl_ms, failover_ttl_ms, evict_lru=None,
                       touchbuf=None):
    """``writebuf.flush_dual`` across a bucket-sharded pair of tables.

    The two tiers hash at different bucket counts, so a record's direct
    and failover rows generally live on DIFFERENT shards — each tier is
    routed and inserted independently inside one shard_map (insert_dual's
    shared plan assumes one write mask; per-tier restricted plans are
    bit-identical to it by the co-sharding argument above)."""
    n_shards = mesh.shape[SHARD_AXIS]
    nb_d, nb_f = direct.n_buckets, failover.n_buckets
    nbl_d = cache_lib.shard_local_buckets(nb_d, n_shards)
    nbl_f = cache_lib.shard_local_buckets(nb_f, n_shards)

    def body(d, f, b, tb, now):
        shard = _shard_index()
        if tb is not None:
            d = _touch_local(d, tb, tb.bucket_d, tb.way_d, nb_d, nbl_d,
                             shard)
            f = _touch_local(f, tb, tb.bucket_f, tb.way_f, nb_f, nbl_f,
                             shard)
        keys, values, ts, live, _ = wb_lib._ring_order(b)
        own_d, loc_d = cache_lib.route_buckets(
            cache_lib.bucket_index(keys, nb_d), shard, nb_d, nbl_d)
        own_f, loc_f = cache_lib.route_buckets(
            cache_lib.bucket_index(keys, nb_f), shard, nb_f, nbl_f)
        d = cache_lib.insert(d, keys, values, now, direct_ttl_ms,
                             write_mask=live & own_d, ts_ms=ts,
                             evict_lru=evict_lru, buckets=loc_d)
        f = cache_lib.insert(f, keys, values, now, failover_ttl_ms,
                             write_mask=live & own_f, ts_ms=ts,
                             evict_lru=evict_lru, buckets=loc_f)
        return d, f

    sp_d, sp_f = cache_pspec(direct), cache_pspec(failover)
    new_d, new_f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sp_d, sp_f, P(), P(), P()),
        out_specs=(sp_d, sp_f),
        check_vma=False,
    )(direct, failover, buf, touchbuf, jnp.int32(now_ms))
    return (new_d, new_f, buf._replace(count=jnp.int32(0)),
            None if touchbuf is None
            else touchbuf._replace(count=jnp.int32(0)))


def sharded_flush_dual_multi(mesh: Mesh, buf, direct, failover, policy,
                             now_ms, touchbuf=None):
    """``writebuf.flush_dual_multi`` across bucket-sharded stacked tiers.

    Ring records carry model slots; pooled bucket ids are recomputed
    replicated from the policy (exactly as the unsharded flush does via
    insert_dual_multi) and routed per shard. Per-record TTL/eviction
    gathers stay replicated — only the table writes are local."""
    n_shards = mesh.shape[SHARD_AXIS]
    nb_d, nb_f = direct.n_buckets, failover.n_buckets
    nbl_d = cache_lib.shard_local_buckets(nb_d, n_shards)
    nbl_f = cache_lib.shard_local_buckets(nb_f, n_shards)

    def body(d, f, b, tb, mask_d, mask_f, ttl_d, ttl_f, lru, now):
        shard = _shard_index()
        fd, ff = d.flat(), f.flat()
        if tb is not None:
            fd = _touch_local(fd, tb, tb.bucket_d, tb.way_d, nb_d, nbl_d,
                              shard)
            ff = _touch_local(ff, tb, tb.bucket_f, tb.way_f, nb_f, nbl_f,
                              shard)
        keys, values, ts, live, slots = wb_lib._ring_order(b)
        g_d = cache_lib.pooled_buckets(slots, keys, mask_d, nb_d)
        g_f = cache_lib.pooled_buckets(slots, keys, mask_f, nb_f)
        own_d, loc_d = cache_lib.route_buckets(g_d, shard, nb_d, nbl_d)
        own_f, loc_f = cache_lib.route_buckets(g_f, shard, nb_f, nbl_f)
        fd = cache_lib.insert(fd, keys, values, now, ttl_d[slots],
                              write_mask=live & own_d, ts_ms=ts,
                              evict_lru=lru[slots], buckets=loc_d,
                              dedupe_salt=slots,
                              value_buckets=d.value_buckets(loc_d))
        ff = cache_lib.insert(ff, keys, values, now, ttl_f[slots],
                              write_mask=live & own_f, ts_ms=ts,
                              evict_lru=lru[slots], buckets=loc_f,
                              dedupe_salt=slots,
                              value_buckets=f.value_buckets(loc_f))
        return d.with_flat(fd), f.with_flat(ff)

    sp_d, sp_f = cache_pspec(direct), cache_pspec(failover)
    new_d, new_f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sp_d, sp_f, P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(sp_d, sp_f),
        check_vma=False,
    )(direct, failover, buf, touchbuf, policy.bucket_mask_d,
      policy.bucket_mask_f, policy.ttl_ms, policy.failover_ttl_ms,
      policy.evict_lru, jnp.int32(now_ms))
    return (new_d, new_f, buf._replace(count=jnp.int32(0)),
            None if touchbuf is None
            else touchbuf._replace(count=jnp.int32(0)))
