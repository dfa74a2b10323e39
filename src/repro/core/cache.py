"""ERCache core: a functional, set-associative, TTL-validated embedding cache.

This is the paper's central data structure re-thought for a JAX/TPU serving
fleet (DESIGN.md §2): instead of an out-of-mesh memcache tier, the cache lives
in device HBM as a pytree of arrays and every operation is a pure function
suitable for jit / pjit:

  * ``n_buckets`` buckets × ``ways`` slots (memcache-slab-like set-associative
    layout: a lookup reads one bucket's ``ways`` metadata slots, which the
    Pallas ``cache_probe`` kernel lands with one DMA per plane).
  * The values are one flat plane of entry columns, ``(dim, n_buckets *
    ways)``: slot ``(bucket, way)`` is column ``bucket * ways + way``
    (DESIGN.md §2). That is the order the compiler's scatter writes in, so
    an insert updates the plane in place and no step relayouts it.
  * TTL-based validity and TTL-based eviction (paper §3.3): a hit requires the
    key to match AND ``now - write_ts <= ttl``; inserts pick, within the
    bucket:  key-match > empty > expired > oldest.
  * No read-refresh: per the paper (§3.2, "Cache update"), entries are only
    written when fresh embeddings come back from model inference. Reads DO
    feed a separate ``last_access_ts`` recency plane (bumped off the
    critical path via the touch buffer, :func:`touch`) that the
    LRU-timestamp eviction policy ranks on — validity stays write-ts-based.

Timestamps are int32 milliseconds from the simulation epoch. Keys are 64-bit
(hi, lo) int32 pairs (see hashing.py).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import trace
from repro.core.hashing import EMPTY_HI, EMPTY_LO, Key64, bucket_index, \
    hash_u32

INT32_MIN = -0x80000000
INT32_MAX = 0x7FFFFFFF
# Timestamp value for never-written slots (also the minimum, so "oldest wins"
# eviction prefers empty slots automatically on the ts tie-break).
TS_EMPTY = jnp.int32(INT32_MIN)


class CacheState(NamedTuple):
    """All arrays of one cache namespace. Shardable along the bucket axis:
    axis 0 of the metadata planes, the entry axis of the value plane."""

    key_hi: jnp.ndarray    # (n_buckets, ways) int32
    key_lo: jnp.ndarray    # (n_buckets, ways) int32
    write_ts: jnp.ndarray  # (n_buckets, ways) int32, ms
    # (dim, n_buckets * ways): slot (bucket, way) is column bucket*ways+way.
    # Inside the serve program only this flat plane is used; ``values`` is
    # the (n_buckets, ways, dim) view for code outside it.
    value_plane: jnp.ndarray
    # Last-access recency plane: max(read timestamps) per slot, bumped off
    # the critical path via the touch buffer (writebuf.TouchBuffer). Writes
    # reset it to the write ts; TS_EMPTY until then. Only the LRU-timestamp
    # eviction policy reads it (recency = max(write_ts, last_access_ts)).
    last_access_ts: jnp.ndarray  # (n_buckets, ways) int32, ms

    @property
    def n_buckets(self) -> int:
        return self.key_hi.shape[0]

    @property
    def ways(self) -> int:
        return self.key_hi.shape[1]

    @property
    def dim(self) -> int:
        return self.value_plane.shape[0]

    @property
    def values(self) -> jnp.ndarray:
        """(n_buckets, ways, dim) view of the value plane: a transposing
        copy, for code outside the serve program (tests, snapshots,
        tools)."""
        return self.value_plane.reshape(
            self.dim, self.n_buckets, self.ways).transpose(1, 2, 0)

    @property
    def capacity(self) -> int:
        return self.n_buckets * self.ways

    def occupancy(self) -> jnp.ndarray:
        """Fraction of slots holding an entry (any age)."""
        occupied = ~((self.key_hi == EMPTY_HI) & (self.key_lo == EMPTY_LO))
        return jnp.mean(occupied.astype(jnp.float32))


class LookupResult(NamedTuple):
    hit: jnp.ndarray     # (B,) bool — key present AND within TTL
    values: jnp.ndarray  # (B, dim) — cached value where hit, zeros otherwise
    age_ms: jnp.ndarray  # (B,) int32 — now - write_ts where hit, -1 otherwise
    # Hit coordinates: the probed bucket and the hit way (-1 on miss).
    # serve_step scatters these into the touch buffer so the flush can bump
    # last_access_ts off the critical path. Optional (None) only for legacy
    # producers that never feed an LRU plane (e.g. the grouped combiner).
    bucket: Optional[jnp.ndarray] = None  # (B,) int32 — probed bucket
    way: Optional[jnp.ndarray] = None     # (B,) int32 — hit way, -1 on miss


def init_cache(n_buckets: int, ways: int, dim: int,
               dtype=jnp.float32) -> CacheState:
    """Create an empty cache. ``n_buckets`` must be a power of two."""
    assert n_buckets & (n_buckets - 1) == 0, "n_buckets must be a power of 2"
    shape = (n_buckets, ways)
    return CacheState(
        key_hi=jnp.full(shape, EMPTY_HI, dtype=jnp.int32),
        key_lo=jnp.full(shape, EMPTY_LO, dtype=jnp.int32),
        write_ts=jnp.full(shape, TS_EMPTY, dtype=jnp.int32),
        value_plane=jnp.zeros((dim, n_buckets * ways), dtype=dtype),
        last_access_ts=jnp.full(shape, TS_EMPTY, dtype=jnp.int32),
    )


class TableImage(NamedTuple):
    """A table (or a stacked tier) with its values in the (buckets, ways,
    dim) view — ``(M, buckets, ways, dim)`` for a stacked tier. What
    snapshots store (ft/snapshot.py; the leaf names and shapes of the
    on-disk format) and what code outside the serve program builds tables
    from; :func:`table_image` and :func:`from_image` convert."""

    key_hi: jnp.ndarray
    key_lo: jnp.ndarray
    write_ts: jnp.ndarray
    values: jnp.ndarray
    last_access_ts: jnp.ndarray


def table_image(state) -> TableImage:
    """A :class:`CacheState` or :class:`MultiCacheState` as its image."""
    return TableImage(key_hi=state.key_hi, key_lo=state.key_lo,
                      write_ts=state.write_ts, values=state.values,
                      last_access_ts=state.last_access_ts)


def from_image(image: TableImage):
    """Inverse of :func:`table_image`: a :class:`CacheState` from a
    single-table image, a :class:`MultiCacheState` from a stacked one."""
    values = jnp.asarray(image.values)
    if values.ndim == 4:
        plane = values.transpose(3, 1, 0, 2).reshape(values.shape[-1], -1)
        cls = MultiCacheState
    else:
        plane = values.reshape(-1, values.shape[-1]).T
        cls = CacheState
    return cls(key_hi=image.key_hi, key_lo=image.key_lo,
               write_ts=image.write_ts, value_plane=plane,
               last_access_ts=image.last_access_ts)


def flat_entries(state: CacheState):
    """Every slot of a table as flat per-entry vectors (bucket-major,
    way-minor), plus the occupancy mask.

    The restore-side elastic rehash (ft/elastic.py) consumes this: it
    filters live entries, re-buckets them under a new geometry, and
    replays them through the normal insert plan. Returns
    ``(keys, values, write_ts, last_access_ts, live)`` with shapes
    ``(Nb*W,)`` / ``(Nb*W, dim)``; ``live`` is True where the slot holds
    a key (any age — TTL filtering is the caller's policy decision).
    """
    n = state.n_buckets * state.ways
    keys = Key64(hi=state.key_hi.reshape(n), lo=state.key_lo.reshape(n))
    live = ~((keys.hi == EMPTY_HI) & (keys.lo == EMPTY_LO))
    return (keys, state.value_plane.T,
            state.write_ts.reshape(n), state.last_access_ts.reshape(n),
            live)


# ============================================================ bucket sharding
# The scale-out story (DESIGN.md §11): a cache's bucket axis is partitioned
# CONTIGUOUSLY across a 1-D device mesh — shard s owns global buckets
# [s*nb_local, (s+1)*nb_local). A key's bucket is a pure function of the key,
# so the bucket id alone decides the owning shard and every probe/insert/touch
# localizes exactly; the only cross-device traffic is the O(B) one-hot
# combine of probe RESULTS (distributed/collectives.py), never cache rows.
# The arithmetic lives here because it is cache geometry, not communication.


def shard_local_buckets(n_buckets: int, n_shards: int) -> int:
    """Per-shard bucket count under the contiguous partition. Bucket counts
    and shard counts are both powers of two, so divisibility is the only
    constraint worth enforcing."""
    if n_buckets % n_shards:
        raise ValueError(f"n_buckets={n_buckets} not divisible by "
                         f"n_shards={n_shards}")
    return n_buckets // n_shards


def route_buckets(bucket, shard, nb_global: int, nb_local: int):
    """GLOBAL bucket ids → (owned (B,) bool, local (B,) int32) on ``shard``.

    Handles plain and POOLED (``slot*Nb + within``) bucket ids uniformly:
    the slab slot is recovered by divmod and re-applied at the local bucket
    count, so a stacked tier sharded along its bucket axis keeps its pooled
    flat-view addressing per shard. Negative ids (touch-buffer "no hit"
    sentinels) are owned by nobody; non-owned rows get an in-range dummy
    index (callers mask with ``owned``, the dummy read/write never lands).
    """
    ok = bucket >= 0
    b = jnp.maximum(bucket, 0)
    slot = b // nb_global
    within = b - slot * nb_global
    local_w = within - shard * nb_local
    owned = ok & (local_w >= 0) & (local_w < nb_local)
    local = slot * nb_local + jnp.clip(local_w, 0, nb_local - 1)
    return owned, local.astype(jnp.int32)


def _ttl_cols(ttl_ms) -> jnp.ndarray:
    """Scalar TTL or per-query (B,) TTLs → broadcastable against (B, W).

    Per-query TTLs are how the multi-model tier threads each model's policy
    through one shared probe/insert (DESIGN.md §5)."""
    ttl = jnp.asarray(ttl_ms, jnp.int32)
    return ttl[:, None] if ttl.ndim == 1 else ttl


def _probe(state: CacheState, keys: Key64, bucket=None):
    """Shared probe: bucket index + per-way match/empty/ts gathers.

    ``bucket`` overrides the hash-derived index — the multi-model tier passes
    pooled (slot-offset) buckets computed with per-model capacity masks.
    Returns (bucket (B,), match (B,W) bool, empty (B,W) bool, ts (B,W) int32).
    """
    if bucket is None:
        bucket = bucket_index(keys, state.n_buckets)
    k_hi = state.key_hi[bucket]          # (B, W)
    k_lo = state.key_lo[bucket]
    ts = state.write_ts[bucket]
    match = (k_hi == keys.hi[:, None]) & (k_lo == keys.lo[:, None])
    empty = (k_hi == EMPTY_HI) & (k_lo == EMPTY_LO)
    return bucket, match, empty, ts


def _entry_values(plane, cols) -> jnp.ndarray:
    """(B, dim) values of entry columns ``cols`` of a value plane."""
    return jnp.take(plane, cols, axis=1, mode="clip").T


def lookup(state: CacheState, keys: Key64, now_ms, ttl_ms,
           backend: str = "jnp", buckets=None,
           value_buckets=None) -> LookupResult:
    """Batched TTL-validated lookup.

    ``backend="jnp"`` is the pure-jnp reference path (the bit-exact oracle);
    ``backend="pallas"`` dispatches the tiled ``cache_probe`` kernel
    (kernels/cache_probe.py) — tests assert the two agree bit-exactly.
    ``ttl_ms`` may be a scalar or a per-query (B,) vector (multi-model
    policies); ``buckets`` optionally overrides the hash-derived index and
    ``value_buckets`` the bucket a query's values sit at in the value plane
    (a stacked tier's pooled view, :meth:`MultiCacheState.value_buckets`).
    """
    if backend == "pallas":
        from repro.kernels import cache_probe as probe_kernels

        if jnp.asarray(ttl_ms).ndim or value_buckets is not None:
            raise ValueError("per-query ttl_ms or a stacked tier needs the "
                             "multi-model kernel: use lookup_dual_multi")
        if buckets is None:
            buckets = bucket_index(keys, state.n_buckets)
        hit, vals, age, way = probe_kernels.cache_probe_tiled(
            state.key_hi, state.key_lo, state.write_ts, state.value_plane,
            keys.hi, keys.lo, buckets, now_ms, ttl_ms)
        return LookupResult(hit=hit, values=vals, age_ms=age,
                            bucket=buckets, way=way)
    if backend != "jnp":
        raise ValueError(f"unknown cache backend: {backend!r}")
    now_ms = jnp.int32(now_ms)
    ttl_b = _ttl_cols(ttl_ms)
    bucket, match, _, ts = _probe(state, keys, bucket=buckets)
    fresh = (now_ms - ts) <= ttl_b  # erlint: allow[ER004] — garbage for
    valid = match & fresh           # empty slots, but match is False there.
    hit = jnp.any(valid, axis=-1)
    # At most one way can match a given key (insert overwrites matches), so
    # argmax of the bool picks the unique valid way when hit.
    way = jnp.argmax(valid, axis=-1).astype(jnp.int32)
    vb = bucket if value_buckets is None else value_buckets
    vals = _entry_values(state.value_plane, vb * state.ways + way)
    vals = jnp.where(hit[:, None], vals, jnp.zeros_like(vals))
    # erlint: allow[ER004] — miss lanes (incl. TS_EMPTY wrap) forced to -1
    age = jnp.where(hit, now_ms - ts[jnp.arange(keys.hi.shape[0]), way],
                    jnp.int32(-1))
    return LookupResult(hit=hit, values=vals, age_ms=age, bucket=bucket,
                        way=jnp.where(hit, way, jnp.int32(-1)))


def lookup_dual(direct: CacheState, failover: CacheState, keys: Key64,
                now_ms, direct_ttl_ms, failover_ttl_ms,
                backend: str = "jnp"):
    """Probe the direct AND failover caches for the same keys.

    Returns (LookupResult_direct, LookupResult_failover). On the pallas
    backend this is a SINGLE fused kernel launch (``cache_probe_dual``);
    on jnp it is two reference lookups — same results either way.
    """
    with jax.named_scope(trace.PROBE):
        if backend == "pallas":
            from repro.kernels import cache_probe as probe_kernels

            b_d = bucket_index(keys, direct.n_buckets)
            b_f = bucket_index(keys, failover.n_buckets)
            ((hd, vd, ad, wd),
             (hf, vf, af, wf)) = probe_kernels.cache_probe_dual(
                direct.key_hi, direct.key_lo, direct.write_ts,
                direct.value_plane, failover.key_hi, failover.key_lo,
                failover.write_ts, failover.value_plane, keys.hi, keys.lo,
                b_d, b_f,
                now_ms, direct_ttl_ms, failover_ttl_ms)
            return (LookupResult(hit=hd, values=vd, age_ms=ad, bucket=b_d,
                                 way=wd),
                    LookupResult(hit=hf, values=vf, age_ms=af, bucket=b_f,
                                 way=wf))
        return (lookup(direct, keys, now_ms, direct_ttl_ms,
                       backend=backend),
                lookup(failover, keys, now_ms, failover_ttl_ms,
                       backend=backend))


def _dedupe(keys: Key64, live: jnp.ndarray, salt=None) -> jnp.ndarray:
    """ONE lexsort: last-writer-wins batch dedupe, cache-independent.

    Returns winner (B,) bool — the LAST live occurrence of each distinct
    key. Depends only on the keys (a key maps to the same bucket however
    the cache is sized), so a dual insert shares this across both caches.

    ``salt`` (optional (B,) int32) widens key identity to (salt, key): the
    multi-model tier passes model slots so the SAME user appearing for two
    models stays two records (they target different slabs of the stacked
    table and must both be written).
    """
    B = keys.hi.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    dead = (~live).astype(jnp.int32)
    cols = [idx, keys.lo, keys.hi]
    if salt is not None:
        salt = jnp.asarray(salt, jnp.int32)
        cols.append(salt)
    cols.append(dead)
    order = jnp.lexsort(tuple(cols))
    s_d = dead[order]
    s_hi = keys.hi[order]
    s_lo = keys.lo[order]
    nxt = lambda a, fill: jnp.concatenate([a[1:], jnp.full((1,), fill,
                                                           a.dtype)])
    same_as_next = ((s_d == nxt(s_d, -1)) & (s_hi == nxt(s_hi, 0))
                    & (s_lo == nxt(s_lo, 0)))
    if salt is not None:
        s_s = salt[order]
        same_as_next = same_as_next & (s_s == nxt(s_s, -1))
    winner_sorted = (~same_as_next) & (s_d == 0)
    return jnp.zeros((B,), bool).at[order].set(winner_sorted)


def dedupe_first_groups(keys: Key64, live: jnp.ndarray, salt=None):
    """ONE lexsort: first-occurrence dedupe + duplicate-group broadcast map.

    The serve path's in-batch inference coalescing (DESIGN.md §9): among
    the ``live`` rows (cache misses), pick the FIRST occurrence of each
    distinct key as the group's *representative* — the row whose tower
    inference every duplicate reuses — and return the broadcast map.

    First (not last, as :func:`_dedupe`'s last-writer-wins) because
    admission control grants inferences in batch arrival order: a user's
    place in the queue is where they FIRST appeared.

    ``salt`` widens key identity exactly as in :func:`_dedupe` (the
    multi-model tier passes model slots: the same user queried for two
    models is two inferences, not one).

    Returns ``(rep, src_row)``:

    * ``rep`` (B,) bool — True on each group's representative row;
    * ``src_row`` (B,) int32 — for every live row, the batch index of its
      representative (its own index on rep rows); -1 on dead rows.
    """
    B = keys.hi.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    dead = (~live).astype(jnp.int32)
    # Reversed index column: the sort's within-group "last" is then the
    # smallest original index — the first occurrence.
    cols = [B - 1 - idx, keys.lo, keys.hi]
    if salt is not None:
        salt = jnp.asarray(salt, jnp.int32)
        cols.append(salt)
    cols.append(dead)
    order = jnp.lexsort(tuple(cols))
    s_d = dead[order]
    s_hi = keys.hi[order]
    s_lo = keys.lo[order]
    nxt = lambda a, fill: jnp.concatenate([a[1:], jnp.full((1,), fill,
                                                           a.dtype)])
    same_as_next = ((s_d == nxt(s_d, -1)) & (s_hi == nxt(s_hi, 0))
                    & (s_lo == nxt(s_lo, 0)))
    if salt is not None:
        s_s = salt[order]
        same_as_next = same_as_next & (s_s == nxt(s_s, -1))
    rep_sorted = (~same_as_next) & (s_d == 0)
    rep = jnp.zeros((B,), bool).at[order].set(rep_sorted)
    # Broadcast map: groups are contiguous in sorted order; scatter each
    # group's (unique) representative index by dense group id, gather
    # back. A row starts a group iff its predecessor didn't match it —
    # the one-position shift of same_as_next.
    is_start = jnp.concatenate([jnp.ones((1,), bool), ~same_as_next[:-1]])
    gid = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    s_idx = idx[order]
    rep_of_g = (jnp.full((B,), -1, jnp.int32)
                .at[gid].max(jnp.where(rep_sorted, s_idx, -1)))
    src_row = jnp.zeros((B,), jnp.int32).at[order].set(rep_of_g[gid])
    return rep, jnp.where(live, src_row, jnp.int32(-1))


def _bucket_rank(bucket: jnp.ndarray, winner: jnp.ndarray,
                 n_buckets: int) -> jnp.ndarray:
    """Per-bucket rank of the winners (batch order within each bucket), via
    ONE stable single-key argsort — the only per-cache sort of the plan."""
    B = bucket.shape[0]
    bkt_w = jnp.where(winner, bucket, jnp.int32(n_buckets))
    order = jnp.argsort(bkt_w, stable=True)
    s_b = bkt_w[order]
    win_i = winner[order].astype(jnp.int32)
    cum = jnp.cumsum(win_i)
    prev_b = jnp.concatenate([jnp.full((1,), -1, s_b.dtype), s_b[:-1]])
    is_start = s_b != prev_b
    seg_base = jax.lax.cummax(jnp.where(is_start, cum - win_i, -1))
    rank_sorted = cum - 1 - seg_base
    return jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted)


def _choose_way(match, empty, expired, ts, rank, lru=None,
                recency=None) -> jnp.ndarray:
    """(B, W) probe results + (B,) rank → (B,) way. Sort-free.

    Eviction order is lexicographic (priority, ts, way). Two policies
    (paper §3.3, selectable per model in the multi-model tier):

    * **TTL-priority** (default): empty(0) > expired(1) > live(2) — an
      expired slot is always sacrificed before a live one, however old.
      Ranks on the WRITE timestamp (expiry is write-age).
    * **LRU-timestamp** (``lru`` True): empty(0) > everything-else(2) —
      the least-recently-USED slot goes first regardless of TTL state.
      Ranks on ``recency`` = max(write_ts, last_access_ts) when given
      (the access-bumped plane), else falls back to the write timestamp.

    ``lru`` may be a scalar bool or a per-query (B,) vector (mixed-model
    batches carry each model's policy) — rows rank on their own policy's
    timestamp. Instead of argsorting each bucket row twice, compute each
    way's position in the eviction order with O(W²) vectorized comparisons
    (W is 4–8: 16–64 lanes), then one-hot select the way whose position
    equals the insert rank.
    """
    W = ts.shape[-1]
    prio_ttl = jnp.where(empty, 0, jnp.where(expired, 1, 2))
    if lru is None:
        priority = prio_ttl.astype(jnp.int32)
    else:
        lru = jnp.asarray(lru, bool)
        lru_b = lru[:, None] if lru.ndim == 1 else lru
        prio_lru = jnp.where(empty, 0, 2)
        priority = jnp.where(lru_b, prio_lru, prio_ttl).astype(jnp.int32)
        if recency is not None:
            # LRU rows rank on access-bumped recency; TTL rows keep write_ts
            ts = jnp.where(lru_b, recency, ts)
    w_idx = jnp.arange(W, dtype=jnp.int32)
    # rank_ts[b, w] = #{w' : (ts[b, w'], w') < (ts[b, w], w)} — the rank of
    # each way's timestamp within its row, way index as tie-break.
    ts_w = ts[:, :, None]                   # (B, W, 1): w on axis 1
    ts_wp = ts[:, None, :]                  # (B, 1, W): w' on axis 2
    lt = (ts_wp < ts_w) | ((ts_wp == ts_w)
                           & (w_idx[None, None, :] < w_idx[None, :, None]))
    rank_ts = jnp.sum(lt, axis=2).astype(jnp.int32)          # (B, W)
    # priority*W + rank_ts is distinct within a row and orders ways exactly
    # by (priority, ts, way); pos[b, w] = position of way w in evict order.
    composite = priority * W + rank_ts
    pos = jnp.sum(composite[:, None, :] < composite[:, :, None], axis=2)
    r = jnp.clip(rank, 0, W - 1)
    way_evict = jnp.sum(w_idx[None, :] * (pos == r[:, None]),
                        axis=1).astype(jnp.int32)
    has_match = jnp.any(match, axis=-1)
    way_match = jnp.argmax(match, axis=-1).astype(jnp.int32)
    return jnp.where(has_match, way_match, way_evict)


def _resolve_collisions(winner, bucket, way, n_buckets: int,
                        ways: int) -> jnp.ndarray:
    """Last-writer-wins on residual slot collisions (clipped ranks /
    match-vs-evict overlap), without a sort: scatter-max each winner's batch
    index into its target slot, keep only the index that won."""
    B = bucket.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    slot = bucket * ways + way
    slot_w = jnp.where(winner, slot, jnp.int32(n_buckets * ways))
    best = jnp.full((n_buckets * ways,), -1, jnp.int32)
    best = best.at[slot_w].max(idx, mode="drop")
    return winner & (best[slot] == idx)


def plan_insert(state: CacheState, keys: Key64, now_ms, ttl_ms,
                write_mask: Optional[jnp.ndarray] = None,
                evict_lru=None, buckets=None, dedupe_salt=None):
    """Slot assignment for a batched insert, emulating sequential writes.

    ONE lexsort (``_dedupe``) + one single-key argsort (``_bucket_rank``)
    drive the whole plan; way selection and collision resolution are
    sort-free (DESIGN.md §3). Returns (winner (B,) bool, bucket (B,),
    way (B,)). Semantics:

    * identical keys within the batch: LAST occurrence wins (sequential
      last-writer-wins), earlier ones are dropped;
    * a key already in its bucket overwrites its own way (match priority);
    * distinct new keys that hash to the same bucket get DISTINCT ways,
      assigned in evictability order (empty > expired > oldest) by their
      per-bucket rank — the fix for batched writes racing on one slot;
    * > W distinct new keys in one bucket in one batch: ranks clip to the
      last (worst) way and collide there (bounded, last-writer-wins) —
      a cache may drop writes under pressure.

    Multi-model extensions (DESIGN.md §5): ``ttl_ms`` may be per-query,
    ``evict_lru`` switches the victim order per query (see
    :func:`_choose_way`), ``buckets`` injects pooled slot-offset indices,
    and ``dedupe_salt`` widens key identity (see :func:`_dedupe`).

    The returned ``winner`` already has residual slot collisions resolved;
    ``(winner, bucket, way)`` target slots are distinct.
    """
    with jax.named_scope(trace.PLAN):
        B = keys.hi.shape[0]
        now_ms = jnp.int32(now_ms)
        bucket, match, empty, ts = _probe(state, keys, bucket=buckets)
        expired = (~empty) & ((now_ms - ts) > _ttl_cols(ttl_ms))  # erlint: allow[ER004] — ~empty masks the wrap
        live = (write_mask if write_mask is not None
                else jnp.ones((B,), bool))
        winner = _dedupe(keys, live, salt=dedupe_salt)
        rank = _bucket_rank(bucket, winner, state.n_buckets)
        recency = jnp.maximum(ts, state.last_access_ts[bucket])
        way = _choose_way(match, empty, expired, ts, rank, lru=evict_lru,
                          recency=recency)
        winner = _resolve_collisions(winner, bucket, way, state.n_buckets,
                                     state.ways)
        return winner, bucket, way


def _scatter_insert(state: CacheState, keys: Key64, values, ts_vec,
                    winner, bucket, way, value_buckets=None) -> CacheState:
    """Apply a resolved insert plan. mode='drop': losers get an
    out-of-range bucket (entry column, for the value plane). The value
    plane takes ONE scatter of the winners' columns at ``bucket * ways +
    way`` (``value_buckets`` in place of ``bucket`` on a stacked tier's
    pooled view): the plane is stored in the scatter's own order, so it is
    written in place. A write resets the slot's last_access_ts to the
    write timestamp — stale touch coordinates from a previous occupant
    must never boost the new entry's recency."""
    with jax.named_scope(trace.SCATTER):
        b_w = jnp.where(winner, bucket, jnp.int32(state.n_buckets))
        vb = bucket if value_buckets is None else value_buckets
        plane = state.value_plane
        cols = jnp.where(winner, vb * state.ways + way,
                         jnp.int32(plane.shape[1]))
        return CacheState(
            key_hi=state.key_hi.at[b_w, way].set(keys.hi, mode="drop"),
            key_lo=state.key_lo.at[b_w, way].set(keys.lo, mode="drop"),
            write_ts=state.write_ts.at[b_w, way].set(ts_vec, mode="drop"),
            value_plane=plane.at[:, cols].set(values.astype(plane.dtype).T,
                                              mode="drop"),
            last_access_ts=state.last_access_ts.at[b_w, way].set(ts_vec,
                                                                 mode="drop"),
        )


def _ts_vector(values, now_ms, ts_ms) -> jnp.ndarray:
    B = values.shape[0]
    if ts_ms is None:
        return jnp.broadcast_to(jnp.int32(now_ms), (B,))
    return jnp.asarray(ts_ms, jnp.int32)


def insert(state: CacheState, keys: Key64, values: jnp.ndarray,
           now_ms, ttl_ms,
           write_mask: Optional[jnp.ndarray] = None,
           ts_ms: Optional[jnp.ndarray] = None,
           evict_lru=None, buckets=None,
           dedupe_salt=None, value_buckets=None) -> CacheState:
    """Batched insert/overwrite with sequential-write emulation (see
    ``plan_insert``).

    * ``write_mask`` disables individual writes (padding in the async write
      buffer).
    * ``ts_ms`` optionally carries per-entry compute timestamps: an embedding
      computed at t but flushed at t+δ ages from t, not t+δ — async writes
      (paper §3.5) move work off the critical path without faking freshness.
    * ``evict_lru`` / ``buckets`` / ``dedupe_salt``: multi-model plan knobs,
      forwarded to :func:`plan_insert`; ``value_buckets`` as in
      :func:`lookup`.
    """
    winner, bucket, way = plan_insert(state, keys, now_ms, ttl_ms,
                                      write_mask, evict_lru=evict_lru,
                                      buckets=buckets,
                                      dedupe_salt=dedupe_salt)
    return _scatter_insert(state, keys, values,
                           _ts_vector(values, now_ms, ts_ms),
                           winner, bucket, way, value_buckets)


def touch(state: CacheState, bucket, way, ts_ms,
          live: Optional[jnp.ndarray] = None) -> CacheState:
    """Bump ``last_access_ts`` at hit coordinates — ONE scatter-max.

    ``bucket``/``way`` are (B,) hit coordinates from :class:`LookupResult`
    (``way`` < 0 marks a miss and is skipped, as are ``live=False`` rows
    and bucket sentinels ≥ n_buckets via mode='drop'). ``ts_ms`` is a
    scalar or (B,) access-timestamp vector.

    Scatter-MAX (not set) makes the bump order irrelevant: however touches
    are batched, buffered, or reordered before the flush applies them, a
    slot ends up with the latest access time it ever served. Values, keys,
    and write_ts are untouched — there is no read-refresh (paper §3.2);
    only the recency plane moves.
    """
    B = bucket.shape[0]
    ts_vec = jnp.broadcast_to(jnp.asarray(ts_ms, jnp.int32), (B,))
    ok = way >= 0
    if live is not None:
        ok = ok & live
    b_ok = jnp.where(ok, bucket, jnp.int32(state.n_buckets))
    w_ok = jnp.maximum(way, 0)        # never a wrapped negative index
    return state._replace(
        last_access_ts=state.last_access_ts.at[b_ok, w_ok].max(
            ts_vec, mode="drop"))


def insert_dual(direct: CacheState, failover: CacheState, keys: Key64,
                values: jnp.ndarray, now_ms, direct_ttl_ms, failover_ttl_ms,
                write_mask: Optional[jnp.ndarray] = None,
                ts_ms: Optional[jnp.ndarray] = None,
                evict_lru=None, buckets_d=None, buckets_f=None,
                dedupe_salt=None, value_buckets_d=None,
                value_buckets_f=None):
    """Insert the same records into BOTH caches with ONE shared plan.

    The batch dedupe (the plan's lexsort) depends only on the keys (plus
    ``dedupe_salt``), so it runs ONCE and is shared. When both caches use
    the same bucket mapping — same ``n_buckets`` (hash-derived path) or the
    same explicit ``buckets`` array — the per-bucket ranks are reused
    outright; otherwise one cheap single-key regroup pass re-ranks under
    the failover's mapping. Way choice and collision resolution are
    per-cache (they depend on each cache's contents) but sort-free.
    Results are bit-identical to two independent :func:`insert` calls.

    TTLs may be per-query vectors and ``evict_lru`` switches the eviction
    policy per query — the multi-model flush path (DESIGN.md §5), which
    also passes each tier's ``value_buckets`` (see :func:`lookup`).

    Returns (new_direct, new_failover).
    """
    B = keys.hi.shape[0]
    now_ms = jnp.int32(now_ms)
    live = (write_mask if write_mask is not None
            else jnp.ones((B,), bool))
    ts_vec = _ts_vector(values, now_ms, ts_ms)

    with jax.named_scope(trace.PLAN):
        winner = _dedupe(keys, live, salt=dedupe_salt)

        b_d, match_d, empty_d, ts_d = _probe(direct, keys, bucket=buckets_d)
        rank_d = _bucket_rank(b_d, winner, direct.n_buckets)
        expired_d = (~empty_d) & ((now_ms - ts_d) > _ttl_cols(direct_ttl_ms))  # erlint: allow[ER004] — ~empty_d masks the wrap
        way_d = _choose_way(match_d, empty_d, expired_d, ts_d, rank_d,
                            lru=evict_lru,
                            recency=jnp.maximum(ts_d,
                                                direct.last_access_ts[b_d]))
        win_d = _resolve_collisions(winner, b_d, way_d, direct.n_buckets,
                                    direct.ways)
    new_direct = _scatter_insert(direct, keys, values, ts_vec,
                                 win_d, b_d, way_d, value_buckets_d)

    with jax.named_scope(trace.PLAN):
        # Probe results must come from the failover's own contents; only the
        # bucket mapping (and therefore the ranks) can be shared across caches.
        b_f, match_f, empty_f, ts_f = _probe(failover, keys, bucket=buckets_f)
        same_mapping = ((buckets_d is None and buckets_f is None
                         and failover.n_buckets == direct.n_buckets)
                        or (buckets_d is not None and buckets_d is buckets_f))
        if same_mapping:
            rank_f = rank_d                       # identical bucket mapping
        else:
            rank_f = _bucket_rank(b_f, winner, failover.n_buckets)
        expired_f = (~empty_f) & ((now_ms - ts_f) > _ttl_cols(failover_ttl_ms))  # erlint: allow[ER004] — ~empty_f masks the wrap
        way_f = _choose_way(match_f, empty_f, expired_f, ts_f, rank_f,
                            lru=evict_lru,
                            recency=jnp.maximum(ts_f,
                                                failover.last_access_ts[b_f]))
        win_f = _resolve_collisions(winner, b_f, way_f, failover.n_buckets,
                                    failover.ways)
    new_failover = _scatter_insert(failover, keys, values, ts_vec,
                                   win_f, b_f, way_f, value_buckets_f)
    return new_direct, new_failover


# =========================================================== multi-model tier
# One serving tier fronting the WHOLE model registry (paper: "more than 30
# ranking models", each with customized cache settings). Per-model direct +
# failover tables are stacked along a leading model axis; a mixed-model
# request batch ((model_slot, user_key) pairs) is served by ONE dual-probe
# dispatch, with each query's TTL / eviction policy gathered from a small
# per-model policy table (DESIGN.md §5).


class ModelPolicy(NamedTuple):
    """Per-model policy table of the multi-model tier.

    Device arrays indexed by model *slot* (the model's position in the
    tier, not its registry ``model_id``). TTLs feed the probe's freshness
    check — the pallas path scalar-prefetches the (M, 2) :meth:`table`
    into SMEM and gathers per query in-kernel. ``evict_lru`` switches the
    insert plan's victim order (paper §3.3 TTL-priority vs LRU-timestamp)
    and the bucket masks give each model its own capacity inside the
    stacked table: local bucket = hash & mask, mask = model n_buckets - 1.
    """

    ttl_ms: jnp.ndarray            # (M,) int32 — direct-cache TTL
    failover_ttl_ms: jnp.ndarray   # (M,) int32
    evict_lru: jnp.ndarray         # (M,) bool — True: LRU-timestamp policy
    bucket_mask_d: jnp.ndarray     # (M,) int32 — direct n_buckets[m] - 1
    bucket_mask_f: jnp.ndarray     # (M,) int32 — failover n_buckets[m] - 1
    touch: jnp.ndarray             # (M,) bool — record last-access bumps
    # SLA admission control (DESIGN.md §8): per-model tower-inference
    # budget (tokens/step; 0 where unlimited — see budget_limited) and the
    # relaxed TTL the failover serves at on the degradation path (equals
    # failover_ttl_ms for models without admission control).
    infer_budget: jnp.ndarray      # (M,) float32 — tokens per serve step
    budget_limited: jnp.ndarray    # (M,) bool — admission control on
    failover_relax_ttl_ms: jnp.ndarray  # (M,) int32
    # In-batch inference coalescing (DESIGN.md §9): dedupe this model's
    # admitted misses within a batch, one tower run per distinct user.
    coalesce: jnp.ndarray          # (M,) bool

    @property
    def n_models(self) -> int:
        return self.ttl_ms.shape[0]

    def table(self) -> jnp.ndarray:
        """(M, 2) int32 [direct_ttl, failover_ttl] — the scalar-prefetched
        view consumed by ``cache_probe_dual_multi``."""
        return jnp.stack([self.ttl_ms, self.failover_ttl_ms], axis=1)


def policy_from_configs(cfgs) -> ModelPolicy:
    """Build the device-side policy table from an ordered CacheConfig list
    (slot i ↔ cfgs[i]).

    When every model's failover capacity equals its direct capacity the
    two mask fields alias ONE array — object identity is the static
    marker ``insert_dual_multi`` uses to share the insert plan's rank
    sort across both tiers (it survives jit tracing, unlike a value
    comparison on traced arrays)."""
    from repro.core.ratelimit import budget_table

    rates, _, limited = budget_table(cfgs)
    masks_d = [c.n_buckets - 1 for c in cfgs]
    masks_f = [c.resolved_failover_n_buckets() - 1 for c in cfgs]
    mask_d = jnp.asarray(masks_d, jnp.int32)
    mask_f = mask_d if masks_f == masks_d else jnp.asarray(masks_f,
                                                           jnp.int32)
    return ModelPolicy(
        ttl_ms=jnp.asarray([c.cache_ttl_ms for c in cfgs], jnp.int32),
        failover_ttl_ms=jnp.asarray([c.failover_ttl_ms for c in cfgs],
                                    jnp.int32),
        evict_lru=jnp.asarray([c.eviction == "lru" for c in cfgs], bool),
        bucket_mask_d=mask_d,
        bucket_mask_f=mask_f,
        touch=jnp.asarray([c.resolved_touch() for c in cfgs], bool),
        infer_budget=rates,
        budget_limited=limited,
        failover_relax_ttl_ms=jnp.asarray(
            [c.resolved_failover_relax_ttl_ms() for c in cfgs], jnp.int32),
        coalesce=jnp.asarray([c.coalesce_misses for c in cfgs], bool),
    )


class MultiCacheState(NamedTuple):
    """Per-model cache tables stacked along a leading model axis.

    The stack allocates ``max(n_buckets)`` buckets per model; a model with
    a smaller configured capacity only ever addresses the first
    ``n_buckets[m]`` rows of its slab (its bucket mask is narrower) — the
    tail rows simply stay empty. Ways and dim are uniform across the tier
    (heterogeneous ``ways`` are normalized up to the tier maximum).

    The value plane is one flat ``(dim, n_buckets * M * ways)`` plane with
    the models interleaved within each bucket: slot ``(model, bucket,
    way)`` is column ``(bucket * M + model) * ways + way``. A contiguous
    split of its entry axis is then a split of every model's bucket range,
    the same one bucket sharding makes of the metadata planes (DESIGN.md
    §11).
    """

    key_hi: jnp.ndarray    # (M, n_buckets, ways) int32
    key_lo: jnp.ndarray    # (M, n_buckets, ways) int32
    write_ts: jnp.ndarray  # (M, n_buckets, ways) int32, ms
    value_plane: jnp.ndarray  # (dim, n_buckets * M * ways)
    last_access_ts: jnp.ndarray  # (M, n_buckets, ways) int32, ms

    @property
    def n_models(self) -> int:
        return self.key_hi.shape[0]

    @property
    def n_buckets(self) -> int:
        """Stacked (maximum) buckets per model slab."""
        return self.key_hi.shape[1]

    @property
    def ways(self) -> int:
        return self.key_hi.shape[2]

    @property
    def dim(self) -> int:
        return self.value_plane.shape[0]

    @property
    def values(self) -> jnp.ndarray:
        """(M, n_buckets, ways, dim) view of the value plane (a copy, for
        code outside the serve program)."""
        return self.value_plane.reshape(
            self.dim, self.n_buckets, self.n_models, self.ways
        ).transpose(2, 1, 3, 0)

    def value_buckets(self, pooled) -> jnp.ndarray:
        """Pooled bucket ids (``slot * Nb + bucket``, the metadata's flat
        view) → the bucket of the value plane their entries sit in,
        ``bucket * M + slot`` — what :func:`lookup` and :func:`insert` take
        as ``value_buckets`` on the :meth:`flat` view."""
        return pooled_value_buckets(pooled, self.n_models, self.n_buckets)

    def flat(self) -> CacheState:
        """The (M*Nb, W) pooled view the shared probe/insert math runs on.
        A reshape of contiguous arrays — no copy under XLA. Its value
        plane is the stacked one: address it with :meth:`value_buckets`."""
        M, Nb, W = self.key_hi.shape
        return CacheState(
            key_hi=self.key_hi.reshape(M * Nb, W),
            key_lo=self.key_lo.reshape(M * Nb, W),
            write_ts=self.write_ts.reshape(M * Nb, W),
            value_plane=self.value_plane,
            last_access_ts=self.last_access_ts.reshape(M * Nb, W),
        )

    def with_flat(self, flat: CacheState) -> "MultiCacheState":
        """Re-stack a pooled view produced by :meth:`flat`."""
        M, Nb, W = self.key_hi.shape
        return MultiCacheState(
            key_hi=flat.key_hi.reshape(M, Nb, W),
            key_lo=flat.key_lo.reshape(M, Nb, W),
            write_ts=flat.write_ts.reshape(M, Nb, W),
            value_plane=flat.value_plane,
            last_access_ts=flat.last_access_ts.reshape(M, Nb, W),
        )

    def model_view(self, slot: int, n_buckets: Optional[int] = None
                   ) -> CacheState:
        """Model ``slot``'s slab as a standalone CacheState (the per-model
        jnp oracle's operand; its value plane is a copy). ``n_buckets``
        trims to the model's own configured capacity so ``bucket_index``
        reproduces the pooled mapping."""
        nb = self.n_buckets if n_buckets is None else n_buckets
        plane = self.value_plane.reshape(self.dim, self.n_buckets,
                                         self.n_models, self.ways)
        return CacheState(
            key_hi=self.key_hi[slot, :nb],
            key_lo=self.key_lo[slot, :nb],
            write_ts=self.write_ts[slot, :nb],
            value_plane=plane[:, :nb, slot].reshape(self.dim, nb * self.ways),
            last_access_ts=self.last_access_ts[slot, :nb],
        )


def init_multi_cache(n_buckets: Sequence[int], ways: int, dim: int,
                     dtype=jnp.float32) -> MultiCacheState:
    """Allocate an empty stacked tier: one slab per model, each a power-of-2
    bucket count; the stack is sized by the largest."""
    for nb in n_buckets:
        assert nb & (nb - 1) == 0, "per-model n_buckets must be powers of 2"
    M = len(n_buckets)
    nb_max = max(n_buckets)
    shape = (M, nb_max, ways)
    return MultiCacheState(
        key_hi=jnp.full(shape, EMPTY_HI, dtype=jnp.int32),
        key_lo=jnp.full(shape, EMPTY_LO, dtype=jnp.int32),
        write_ts=jnp.full(shape, TS_EMPTY, dtype=jnp.int32),
        value_plane=jnp.zeros((dim, M * nb_max * ways), dtype=dtype),
        last_access_ts=jnp.full(shape, TS_EMPTY, dtype=jnp.int32),
    )


def pooled_value_buckets(pooled, n_models: int, nb_stack: int
                         ) -> jnp.ndarray:
    """See :meth:`MultiCacheState.value_buckets`; ``nb_stack`` is the
    stack's buckets per model (a shard's local count inside a
    bucket-sharded computation)."""
    slot = pooled // nb_stack
    return (pooled - slot * nb_stack) * n_models + slot


def pooled_buckets(slots, keys: Key64, bucket_mask, nb_stack: int
                   ) -> jnp.ndarray:
    """Flat bucket index into a stacked tier's pooled (M*Nb, W) view:
    ``slot * Nb + (hash & mask[slot])``. The per-model mask realizes
    per-model capacity; the slot offset selects the slab."""
    h = hash_u32(keys)
    local = (h & bucket_mask[slots].astype(jnp.uint32)).astype(jnp.int32)
    return slots.astype(jnp.int32) * nb_stack + local


def _pooled_bucket_pair(direct: "MultiCacheState",
                        failover: "MultiCacheState",
                        policy: "ModelPolicy", slots, keys: Key64):
    """(direct, failover) pooled buckets for one mixed-model batch — THE
    mapping both lookup_dual_multi and insert_dual_multi must agree on.

    Identical stack size + aliased masks (see policy_from_configs) ⇒
    identical mapping: the SAME array object is returned for both, which
    downstream code (insert_dual's ``buckets_d is buckets_f`` test) uses
    to reuse the insert plan's per-bucket ranks instead of re-sorting.
    """
    b_d = pooled_buckets(slots, keys, policy.bucket_mask_d,
                         direct.n_buckets)
    if (failover.n_buckets == direct.n_buckets
            and policy.bucket_mask_f is policy.bucket_mask_d):
        return b_d, b_d
    return b_d, pooled_buckets(slots, keys, policy.bucket_mask_f,
                               failover.n_buckets)


def lookup_dual_multi(direct: MultiCacheState, failover: MultiCacheState,
                      policy: ModelPolicy, slots, keys: Key64, now_ms,
                      backend: str = "jnp"):
    """Probe BOTH stacked tiers for a mixed-model batch in one dispatch.

    ``slots`` (B,) int32 assigns each query its model; each query is
    validated against its model's direct/failover TTL. On the pallas
    backend this is a SINGLE fused kernel launch (``cache_probe_dual_multi``
    — per-model TTLs gathered in-kernel from the scalar-prefetched policy
    table); on jnp it is two per-query-TTL reference lookups on the pooled
    views — bit-identical either way, and bit-identical to looping the
    single-model oracle over each model's slab.

    Returns (LookupResult_direct, LookupResult_failover).
    """
    with jax.named_scope(trace.PROBE):
        slots = jnp.asarray(slots, jnp.int32)
        b_d, b_f = _pooled_bucket_pair(direct, failover, policy, slots, keys)
        if backend == "pallas":
            from repro.kernels import cache_probe as probe_kernels

            fd, ff = direct.flat(), failover.flat()
            ((hd, vd, ad, wd),
             (hf, vf, af, wf)) = probe_kernels.cache_probe_dual_multi(
                fd.key_hi, fd.key_lo, fd.write_ts, fd.value_plane,
                ff.key_hi, ff.key_lo, ff.write_ts, ff.value_plane,
                keys.hi, keys.lo, slots, b_d, b_f, policy.table(), now_ms)
            return (LookupResult(hit=hd, values=vd, age_ms=ad, bucket=b_d,
                                 way=wd),
                    LookupResult(hit=hf, values=vf, age_ms=af, bucket=b_f,
                                 way=wf))
        if backend != "jnp":
            raise ValueError(f"unknown cache backend: {backend!r}")
        return (lookup(direct.flat(), keys, now_ms, policy.ttl_ms[slots],
                       buckets=b_d, value_buckets=direct.value_buckets(b_d)),
                lookup(failover.flat(), keys, now_ms,
                       policy.failover_ttl_ms[slots], buckets=b_f,
                       value_buckets=failover.value_buckets(b_f)))


def insert_dual_multi(direct: MultiCacheState, failover: MultiCacheState,
                      policy: ModelPolicy, slots, keys: Key64,
                      values: jnp.ndarray, now_ms,
                      write_mask: Optional[jnp.ndarray] = None,
                      ts_ms: Optional[jnp.ndarray] = None):
    """Insert a mixed-model record batch into BOTH stacked tiers with ONE
    shared plan.

    Per-record TTLs and eviction policies are gathered from the policy
    table; the plan's dedupe is salted with the model slot so the same
    user appearing for two models stays two records. Bit-identical to
    looping the single-model :func:`insert` over each model's slab with
    that model's settings.

    Returns (new_direct, new_failover).
    """
    slots = jnp.asarray(slots, jnp.int32)
    b_d, b_f = _pooled_bucket_pair(direct, failover, policy, slots, keys)
    new_d, new_f = insert_dual(
        direct.flat(), failover.flat(), keys, values, now_ms,
        policy.ttl_ms[slots], policy.failover_ttl_ms[slots],
        write_mask=write_mask, ts_ms=ts_ms,
        evict_lru=policy.evict_lru[slots],
        buckets_d=b_d, buckets_f=b_f, dedupe_salt=slots,
        value_buckets_d=direct.value_buckets(b_d),
        value_buckets_f=failover.value_buckets(b_f))
    return direct.with_flat(new_d), failover.with_flat(new_f)
