"""Plain reference of the ERCache serve semantics, in numpy, independent of
the system under test (which it does not import).

It replays what a cell sent: the preload inserts, then every serve step,
and says for each request where its embedding must come from, and what the
tables must hold afterwards. Values are not carried: each slot records the
*origin* of its value (a preload record, or the window request whose tower
output was written), so the caller can check every served value exactly.

The semantics, per serve step of B requests at logical time ``now``:

1. Probe both tiers of each request's model: a hit is a slot holding the key
   whose write time is within the tier's TTL (``now - ts <= ttl``).
2. Misses are compacted to the front in batch order; the first
   ``miss_budget`` rows run the tower. A miss that ran and did not fail is
   served its tower output (age 0). The rest fall back to the failover hit
   (age from its write time), else to the default embedding (age -1).
3. The step's tower outputs are written to both tiers at ``now`` (one flush
   per step). First the hits of models with LRU eviction bump the slot's
   last-access time to ``now``. Then the insert plan: identical (model, key)
   records keep the last; a record whose key is in its bucket overwrites
   that way; otherwise the i-th new record of a bucket (batch order, counting
   every record aimed at the bucket) takes the i-th way (clipped to the last)
   in eviction order: empty, then expired (TTL policy only), then the rest,
   ordered by write time (TTL) or by max(write, last access) time (LRU), way
   index breaking ties. Records that still meet in one slot: the later wins.
   A write sets the slot's key, write time and last-access time.

Buckets: a 64-bit key's xxhash32-style hash (seed 0) masked to the model's
bucket count, offset by the model's slab in the stacked tier.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

EMPTY_KEY = np.int64(-(2 ** 63))     # the system's (INT32_MIN, 0) key pair
TS_EMPTY = -(2 ** 31)
DIRECT, COMPUTED, FAILOVER, FALLBACK = 0, 1, 2, 3

_P2, _P3, _P4, _P5 = (np.uint32(0x85EBCA77), np.uint32(0xC2B2AE3D),
                      np.uint32(0x27D4EB2F), np.uint32(0x165667B1))


def hash32(keys: np.ndarray) -> np.ndarray:
    """xxhash32-style avalanche over the key's two 32-bit words."""
    k = keys.astype(np.int64)
    hi = (k >> 32).astype(np.uint32)
    lo = (k & 0xFFFFFFFF).astype(np.uint32)

    def rotl17(x):
        return (x << np.uint32(17)) | (x >> np.uint32(15))

    with np.errstate(over="ignore"):
        h = np.full(k.shape, _P5 + np.uint32(8), np.uint32)
        h = rotl17(h + lo * _P3) * _P4
        h = rotl17(h + hi * _P3) * _P4
        h ^= h >> np.uint32(15)
        h *= _P2
        h ^= h >> np.uint32(13)
        h *= _P3
        h ^= h >> np.uint32(16)
    return h


class Tier:
    """One tier (direct or failover) of every model, stacked: model m owns
    rows [m * rows_per_model, m * rows_per_model + n_buckets[m])."""

    def __init__(self, n_buckets, ways: int, ttl_ms):
        self.nb = np.asarray(n_buckets, np.int64)
        self.rows_per_model = int(self.nb.max())
        self.ways = ways
        self.ttl = np.asarray(ttl_ms, np.int64)
        # one row per bucket holds every field of its ways, so that a probe
        # or an insert reads each bucket once
        self.meta = np.empty((len(self.nb) * self.rows_per_model, ways, 4),
                             np.int64)
        self.meta[...] = (EMPTY_KEY, TS_EMPTY, TS_EMPTY, -1)
        self.key, self.ts, self.last, self.origin = np.moveaxis(self.meta,
                                                                -1, 0)

    def bucket(self, keys, slots):
        h = hash32(keys).astype(np.int64)
        return slots * self.rows_per_model + (h & (self.nb[slots] - 1))

    def probe(self, keys, slots, now):
        """(bucket, hit, way, age, origin) of each query."""
        b = self.bucket(keys, slots)
        key_r, ts_r, _, origin_r = np.moveaxis(self.meta[b], -1, 0)
        valid = ((key_r == keys[:, None])
                 & (now - ts_r <= self.ttl[slots][:, None]))
        hit = valid.any(axis=1)
        way = np.argmax(valid, axis=1)
        rows = np.arange(b.size)
        age = np.where(hit, now - ts_r[rows, way], -1)
        origin = np.where(hit, origin_r[rows, way], -1)
        return b, hit, np.where(hit, way, -1), age, origin

    def put(self, keys, slots, ts, origin, now, winner, lru):
        """One batched insert; ``winner`` marks the last record of each
        (model, key), ``lru`` (per record) the model's eviction policy."""
        n = keys.size
        idx = np.arange(n)
        b = self.bucket(keys, slots)
        key_r, ts_r, last_r, _ = np.moveaxis(self.meta[b], -1, 0)
        match = key_r == keys[:, None]
        empty = key_r == EMPTY_KEY
        expired = ~empty & (now - ts_r > self.ttl[slots][:, None])
        # rank of each winner among the winners aimed at its bucket
        bw = np.where(winner, b, -1)
        order = np.lexsort((idx, bw))
        sb = bw[order]
        start = np.r_[True, sb[1:] != sb[:-1]]
        first = np.maximum.accumulate(np.where(start, np.arange(n), 0))
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n) - first
        # eviction order: (priority, time, way)
        lru_c = lru[:, None]
        prio = np.where(empty, 0, np.where(expired & ~lru_c, 1, 2))
        t = np.where(lru_c, np.maximum(ts_r, last_r), ts_r)
        # erlint: allow[ER004] — t is an int64 numpy array: no wrap
        comp = (prio * 2 ** 34 + (t - TS_EMPTY)) * 16 + np.arange(self.ways)
        evict_order = np.argsort(comp, axis=1)
        r = np.clip(rank, 0, self.ways - 1)
        way = np.where(match.any(axis=1), np.argmax(match, axis=1),
                       evict_order[idx, r])
        # residual collisions: the later record wins its slot
        slot_id = np.where(winner, b * self.ways + way, -1)
        order = np.lexsort((idx, slot_id))
        so = slot_id[order]
        last_of = np.r_[so[1:] != so[:-1], True] & (so >= 0)
        w = order[last_of]
        self.meta[b[w], way[w]] = np.stack([keys[w], ts[w], ts[w],
                                            origin[w]], axis=1)

    def touch(self, b, way, now):
        ok = way >= 0
        np.maximum.at(self.last, (b[ok], way[ok]), now)


def last_of_each(keys, slots) -> np.ndarray:
    """(N,) bool: the last record of each distinct (model, key)."""
    n = keys.size
    idx = np.arange(n)
    order = np.lexsort((idx, keys, slots))
    sk, ss = keys[order], slots[order]
    last = np.r_[(sk[1:] != sk[:-1]) | (ss[1:] != ss[:-1]), True]
    out = np.zeros(n, bool)
    out[order] = last
    return out


class Reference:
    """The cache tier of a configuration, driven like the system."""

    def __init__(self, models, miss_budget: int):
        ways = {m["ways"] for m in models}
        if len(ways) != 1:
            raise ValueError("one way count per tier")
        ways = ways.pop()
        self.direct = Tier([m["direct_buckets"] for m in models], ways,
                           [m["direct_ttl_ms"] for m in models])
        self.failover = Tier([m["failover_buckets"] for m in models], ways,
                             [m["failover_ttl_ms"] for m in models])
        self.lru = np.array([m["eviction"] == "lru" for m in models])
        self.miss_budget = miss_budget
        # the tiers are independent: numpy releases the GIL in their work
        self._pool = ThreadPoolExecutor(2)

    def put(self, keys, slots, ts, origin, now):
        winner = last_of_each(keys, slots)
        lru = self.lru[slots]
        list(self._pool.map(lambda tier: tier.put(
            keys, slots, ts, origin, now, winner, lru),
            (self.direct, self.failover)))

    def serve_batch(self, keys, slots, fails, now, first_request: int):
        """One serve step. Returns per request (source, age, origin) and the
        step's counters."""
        B = keys.size
        bd, hd, wd, ad, od = self.direct.probe(keys, slots, now)
        bf, hf, wf, af, of = self.failover.probe(keys, slots, now)
        miss = ~hd
        order = np.argsort(~miss, kind="stable")
        sel = order[:self.miss_budget]
        ran = sel[miss[sel]]
        ok = ran[~fails[ran]]
        computed = np.zeros(B, bool)
        computed[ok] = True
        use_fo = miss & ~computed & hf
        fallback = miss & ~computed & ~hf
        source = np.select([hd, computed, use_fo], [DIRECT, COMPUTED, FAILOVER],
                           FALLBACK)
        age = np.select([hd, computed, use_fo], [ad, 0, af], -1)
        req = first_request + np.arange(B)
        origin = np.select([hd, computed, use_fo], [od, req, of], -1)
        touch = (hd | hf) & self.lru[slots]
        self.direct.touch(bd[touch & hd], wd[touch & hd], now)
        self.failover.touch(bf[touch & hf], wf[touch & hf], now)
        self.put(keys[ok], slots[ok], np.full(ok.size, now, np.int64),
                    req[ok], now)
        counters = {
            "requests": B, "direct_hits": int(hd.sum()),
            "tower_inferences": int(ran.size),
            "tower_failures": int(ran.size - ok.size),
            "overflow": int(miss.sum() - ran.size),
            "admitted": int(miss.sum()), "deferred": 0,
            "failover_hits": int(use_fo.sum()),
            "failover_serves": int(use_fo.sum()),
            "fallbacks": int(fallback.sum()),
            "served_age_count": int((age >= 0).sum()),
        }
        if len(self.lru) > 1:
            def per_model(flag):
                return np.bincount(slots[flag], minlength=len(self.lru))
            counters.update({
                "per_model_requests": per_model(np.ones(B, bool)),
                "per_model_direct_hits": per_model(hd),
                "per_model_failover_hits": per_model(use_fo),
                "per_model_failover_serves": per_model(use_fo),
                "per_model_fallbacks": per_model(fallback),
                "per_model_admitted": per_model(miss),
            })
        return source, age, origin, counters
