"""Asynchronous write + touch buffers (paper §3.5).

Production ERCache sends one grouped write RPC per user *asynchronously* so
the write never sits on the serving critical path. The JAX analogue: the
serve step appends (key, value, ts) records to a fixed-size ring buffer
pytree — an O(B) scatter, no cache-table traffic — and a separate ``flush``
program (dispatched off the latency path, e.g. on the next step's bubble)
performs the actual cache inserts.

Entries carry their compute timestamp so deferred flushing never inflates
freshness (see cache.insert ``ts_ms``).

The :class:`TouchBuffer` is the same idea for cache READS: serve_step
appends each hit's (bucket, way) coordinates — another O(B) scatter — and
the flush scatter-MAXes the buffered access timestamps into the caches'
``last_access_ts`` recency plane before applying the inserts. Scatter-max
makes the bump order irrelevant, so deferring costs nothing semantically;
the LRU-timestamp eviction policy then ranks on true access recency
instead of write age. Coordinates stay valid between serve and flush
because serve_step never mutates the cache tables — only flush does, and
it drains both rings in the same program.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import trace
from repro.core.hashing import Key64


class WriteBuffer(NamedTuple):
    key_hi: jnp.ndarray   # (cap,) int32
    key_lo: jnp.ndarray   # (cap,) int32
    ts_ms: jnp.ndarray    # (cap,) int32
    values: jnp.ndarray   # (cap, dim)
    count: jnp.ndarray    # () int32 — total appended since last flush (may
                          # exceed cap; ring overwrites oldest)
    model_id: jnp.ndarray  # (cap,) int32 — model slot per record (all zero
                           # for single-model servers)

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]


def init_writebuf(capacity: int, dim: int, dtype=jnp.float32) -> WriteBuffer:
    return WriteBuffer(
        key_hi=jnp.zeros((capacity,), jnp.int32),
        key_lo=jnp.zeros((capacity,), jnp.int32),
        ts_ms=jnp.zeros((capacity,), jnp.int32),
        values=jnp.zeros((capacity, dim), dtype),
        count=jnp.int32(0),
        model_id=jnp.zeros((capacity,), jnp.int32),
    )


def append(buf: WriteBuffer, keys: Key64, values: jnp.ndarray,
           ts_ms, mask: jnp.ndarray,
           model_ids: Optional[jnp.ndarray] = None) -> WriteBuffer:
    """Append masked records at the ring head. O(B) scatter.

    ``model_ids`` (B,) tags each record with its model slot — the
    multi-model flush gathers per-record TTL/eviction policy from it."""
    B = values.shape[0]
    ts_vec = jnp.broadcast_to(jnp.asarray(ts_ms, jnp.int32), (B,))
    if model_ids is None:
        model_ids = jnp.zeros((B,), jnp.int32)
    # Compact live records to the front so ring slots aren't wasted on pads.
    order = jnp.argsort(~mask, stable=True)          # live first
    n_live = jnp.sum(mask.astype(jnp.int32))
    pos_in_batch = jnp.arange(B, dtype=jnp.int32)
    slot = (buf.count + pos_in_batch) % buf.capacity
    # Drop positions beyond n_live — AND, when one batch carries more live
    # records than the ring holds, the FIRST n_live - capacity of them:
    # two live positions a capacity apart would otherwise scatter to the
    # same slot and XLA picks an arbitrary winner. True last-writer-wins
    # keeps only the last `capacity` live records.
    keep = ((pos_in_batch < n_live)
            & (pos_in_batch >= n_live - buf.capacity))
    slot = jnp.where(keep, slot, jnp.int32(buf.capacity))
    src = order
    return WriteBuffer(
        key_hi=buf.key_hi.at[slot].set(keys.hi[src], mode="drop"),
        key_lo=buf.key_lo.at[slot].set(keys.lo[src], mode="drop"),
        ts_ms=buf.ts_ms.at[slot].set(ts_vec[src], mode="drop"),
        values=buf.values.at[slot].set(
            values[src].astype(buf.values.dtype), mode="drop"),
        count=buf.count + n_live,
        model_id=buf.model_id.at[slot].set(
            jnp.asarray(model_ids, jnp.int32)[src], mode="drop"),
    )


def _ring_order(buf: WriteBuffer):
    """Unroll the ring into append order. Returns (keys, values, ts, live,
    model slots)."""
    cap = buf.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    n_live = jnp.minimum(buf.count, cap)
    # Ring start: if count > cap the oldest surviving record is at count % cap.
    start = jnp.where(buf.count > cap, buf.count % cap, 0)
    ring = (start + idx) % cap
    live = idx < n_live
    keys = Key64(hi=buf.key_hi[ring], lo=buf.key_lo[ring])
    return keys, buf.values[ring], buf.ts_ms[ring], live, buf.model_id[ring]


# ============================================================= touch buffer
class TouchBuffer(NamedTuple):
    """Ring of hit coordinates awaiting deferred last-access bumps.

    Each record holds the (bucket, way) a request hit in the direct AND
    failover caches (−1 bucket marks "no hit in that cache") plus the
    access timestamp. The flush scatter-MAXes the timestamps into the
    caches' ``last_access_ts`` planes — order-independent, so ring
    overwrites under pressure only lose the oldest (weakest) bumps.
    """

    bucket_d: jnp.ndarray  # (cap,) int32 — direct-cache bucket, -1 = no hit
    way_d: jnp.ndarray     # (cap,) int32
    bucket_f: jnp.ndarray  # (cap,) int32 — failover bucket, -1 = no hit
    way_f: jnp.ndarray     # (cap,) int32
    ts_ms: jnp.ndarray     # (cap,) int32 — access timestamp
    count: jnp.ndarray     # () int32 — total appended since last flush

    @property
    def capacity(self) -> int:
        return self.bucket_d.shape[0]


def init_touchbuf(capacity: int) -> TouchBuffer:
    shape = (capacity,)
    return TouchBuffer(
        bucket_d=jnp.full(shape, -1, jnp.int32),
        way_d=jnp.zeros(shape, jnp.int32),
        bucket_f=jnp.full(shape, -1, jnp.int32),
        way_f=jnp.zeros(shape, jnp.int32),
        ts_ms=jnp.zeros(shape, jnp.int32),
        count=jnp.int32(0),
    )


def touch_append(buf: TouchBuffer, direct: cache_lib.LookupResult,
                 failover: cache_lib.LookupResult, ts_ms,
                 mask: Optional[jnp.ndarray] = None) -> TouchBuffer:
    """Append one serve batch's hit coordinates at the ring head. O(B).

    ``direct``/``failover`` are the probe results carrying (bucket, way)
    hit coordinates; rows that hit NEITHER cache carry no information and
    are compacted away. ``mask`` (B,) additionally gates rows — the
    multi-model tier passes each query's per-model ``touch`` policy.
    Same ring discipline as :func:`append`, including last-`capacity`-wins
    when a batch carries more touches than the ring holds.
    """
    B = direct.hit.shape[0]
    ts_vec = jnp.broadcast_to(jnp.asarray(ts_ms, jnp.int32), (B,))
    live = direct.hit | failover.hit
    if mask is not None:
        live = live & mask
    bkt_d = jnp.where(direct.hit & live, direct.bucket, jnp.int32(-1))
    bkt_f = jnp.where(failover.hit & live, failover.bucket, jnp.int32(-1))
    order = jnp.argsort(~live, stable=True)          # live first
    n_live = jnp.sum(live.astype(jnp.int32))
    pos = jnp.arange(B, dtype=jnp.int32)
    slot = (buf.count + pos) % buf.capacity
    keep = (pos < n_live) & (pos >= n_live - buf.capacity)
    slot = jnp.where(keep, slot, jnp.int32(buf.capacity))
    return TouchBuffer(
        bucket_d=buf.bucket_d.at[slot].set(bkt_d[order], mode="drop"),
        way_d=buf.way_d.at[slot].set(direct.way[order], mode="drop"),
        bucket_f=buf.bucket_f.at[slot].set(bkt_f[order], mode="drop"),
        way_f=buf.way_f.at[slot].set(failover.way[order], mode="drop"),
        ts_ms=buf.ts_ms.at[slot].set(ts_vec[order], mode="drop"),
        count=buf.count + n_live,
    )


def _touch_live(buf: TouchBuffer) -> jnp.ndarray:
    """(cap,) bool — physical slots holding un-flushed records. Scatter-max
    is order-independent, so no ring unroll is needed."""
    idx = jnp.arange(buf.capacity, dtype=jnp.int32)
    return idx < jnp.minimum(buf.count, buf.capacity)


def _apply_touches(buf: TouchBuffer, state: cache_lib.CacheState,
                   bucket: jnp.ndarray, way: jnp.ndarray
                   ) -> cache_lib.CacheState:
    """Scatter-max one cache's buffered bumps (records with ``bucket`` −1
    never hit that cache and are skipped)."""
    with jax.named_scope(trace.TOUCH):
        return cache_lib.touch(state, bucket, way, buf.ts_ms,
                               live=_touch_live(buf) & (bucket >= 0))


def _apply_touches_dual(buf: Optional[TouchBuffer],
                        direct: cache_lib.CacheState,
                        failover: cache_lib.CacheState):
    """Scatter-max the buffered bumps into both recency planes (no-op when
    no touch buffer rides along)."""
    if buf is None:
        return direct, failover, None
    direct = _apply_touches(buf, direct, buf.bucket_d, buf.way_d)
    failover = _apply_touches(buf, failover, buf.bucket_f, buf.way_f)
    return direct, failover, buf._replace(count=jnp.int32(0))


def flush(buf: WriteBuffer, state: cache_lib.CacheState, now_ms, ttl_ms,
          evict_lru=None, touchbuf: Optional[TouchBuffer] = None,
          mesh=None) -> Tuple[cache_lib.CacheState, WriteBuffer,
                              Optional[TouchBuffer]]:
    """Apply all buffered records to the cache; reset the buffer(s).

    Records are applied in append order (ring order), so last-writer-wins
    matches the true write stream. Slots beyond ``count`` are masked out.
    ``evict_lru`` selects the victim order (paper §3.3 policy switch) —
    it must reach the insert plan, or a server configured
    ``eviction="lru"`` silently runs TTL-priority. ``touchbuf`` carries
    deferred last-access bumps; its DIRECT-cache coordinates are applied
    (scatter-max) BEFORE the inserts so the LRU plan ranks on bumped
    recency and overwritten slots reset cleanly. ``mesh`` routes the
    inserts/touches to a bucket-sharded table (DESIGN.md §11) — the rings
    stay replicated; results are bit-identical either way.
    """
    with jax.named_scope(trace.FLUSH):
        if mesh is not None:
            from repro.distributed import collectives as coll

            return coll.sharded_flush(mesh, buf, state, now_ms, ttl_ms,
                                      evict_lru=evict_lru, touchbuf=touchbuf)
        if touchbuf is not None:
            state = _apply_touches(touchbuf, state, touchbuf.bucket_d,
                                   touchbuf.way_d)
            touchbuf = touchbuf._replace(count=jnp.int32(0))
        keys, values, ts, live, _ = _ring_order(buf)
        new_state = cache_lib.insert(state, keys, values, now_ms, ttl_ms,
                                     write_mask=live, ts_ms=ts,
                                     evict_lru=evict_lru)
        return new_state, buf._replace(count=jnp.int32(0)), touchbuf


def flush_dual(buf: WriteBuffer, direct: cache_lib.CacheState,
               failover: cache_lib.CacheState, now_ms,
               direct_ttl_ms, failover_ttl_ms, evict_lru=None,
               touchbuf: Optional[TouchBuffer] = None, mesh=None
               ) -> Tuple[cache_lib.CacheState, cache_lib.CacheState,
                          WriteBuffer, Optional[TouchBuffer]]:
    """Flush the buffer into BOTH caches with ONE shared insert plan.

    The ring unroll and the plan's dedupe/rank sort run once instead of
    twice (cache_lib.insert_dual); semantics per cache are identical to two
    independent :func:`flush` calls with the respective TTLs.
    ``evict_lru`` selects the victim order (paper §3.3 policy switch);
    ``touchbuf``'s deferred last-access bumps are scatter-maxed into both
    recency planes BEFORE the inserts (see :func:`flush`). ``mesh`` routes
    everything to bucket-sharded tables (DESIGN.md §11), bit-identically.
    """
    with jax.named_scope(trace.FLUSH):
        if mesh is not None:
            from repro.distributed import collectives as coll

            return coll.sharded_flush_dual(mesh, buf, direct, failover, now_ms,
                                           direct_ttl_ms, failover_ttl_ms,
                                           evict_lru=evict_lru,
                                           touchbuf=touchbuf)
        direct, failover, touchbuf = _apply_touches_dual(touchbuf, direct,
                                                         failover)
        keys, values, ts, live, _ = _ring_order(buf)
        new_direct, new_failover = cache_lib.insert_dual(
            direct, failover, keys, values, now_ms, direct_ttl_ms,
            failover_ttl_ms, write_mask=live, ts_ms=ts, evict_lru=evict_lru)
        return (new_direct, new_failover, buf._replace(count=jnp.int32(0)),
                touchbuf)


def flush_dual_multi(buf: WriteBuffer, direct: cache_lib.MultiCacheState,
                     failover: cache_lib.MultiCacheState,
                     policy: cache_lib.ModelPolicy, now_ms,
                     touchbuf: Optional[TouchBuffer] = None, mesh=None
                     ) -> Tuple[cache_lib.MultiCacheState,
                                cache_lib.MultiCacheState, WriteBuffer,
                                Optional[TouchBuffer]]:
    """Flush a mixed-model buffer into BOTH stacked tiers with ONE shared
    insert plan.

    Each record's TTLs and eviction policy come from its model's row of
    the policy table (``cache_lib.insert_dual_multi``); the plan's dedupe
    is model-salted so the same user buffered for two models writes to
    both slabs. Semantics per model are identical to flushing that
    model's records alone with its own settings. ``touchbuf`` coordinates
    are POOLED (M·Nb) indices, so the bumps land on the flat views of the
    stacked planes — same scatter-max as the single-model flush. ``mesh``
    routes everything to bucket-sharded stacked tiers (DESIGN.md §11),
    bit-identically.
    """
    with jax.named_scope(trace.FLUSH):
        if mesh is not None:
            from repro.distributed import collectives as coll

            return coll.sharded_flush_dual_multi(mesh, buf, direct, failover,
                                                 policy, now_ms,
                                                 touchbuf=touchbuf)
        if touchbuf is not None:
            flat_d, flat_f, touchbuf = _apply_touches_dual(
                touchbuf, direct.flat(), failover.flat())
            direct = direct.with_flat(flat_d)
            failover = failover.with_flat(flat_f)
        keys, values, ts, live, slots = _ring_order(buf)
        new_direct, new_failover = cache_lib.insert_dual_multi(
            direct, failover, policy, slots, keys, values, now_ms,
            write_mask=live, ts_ms=ts)
        return (new_direct, new_failover, buf._replace(count=jnp.int32(0)),
                touchbuf)
