"""Elastic re-sharding: keep training/serving when the device count changes.

On a real fleet, a pod losing a rack shrinks the usable mesh; the framework
must (a) pick the best new mesh factorization, (b) re-shard the global batch
and cache shards, and (c) restart from the latest checkpoint with identical
global state. Checkpoints store GLOBAL arrays (ft/checkpoint.py), so (c) is
mesh-independent by construction; this module provides (a)/(b): a
deterministic plan from (n_devices, constraints) → mesh shape + per-axis
re-partitioning of the standing state.

It also provides the CACHE side of elasticity (DESIGN.md §10): a snapshot
taken under one table geometry can be restored into a differently shaped
table (:func:`rehash_cache` / :func:`rehash_multi_cache`). Capacity is a
deploy knob — a restart may grow the table to chase hit rate or shrink it
to fit a smaller mesh — and a geometry change must not force a cold start.
Live, unexpired entries are re-bucketed through the normal hash + insert
plan with their ORIGINAL write timestamps (age is preserved, nothing gets
artificially refreshed), oldest-first so that when a shrunk table's bucket
overflows, the newest entries win the contested ways. A second pass
re-applies ``last_access_ts`` through the touch scatter-max so the LRU
recency plane survives too.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import cache as C
from repro.core.hashing import EMPTY_HI, EMPTY_LO, Key64


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    per_device_batch: int
    notes: str = ""

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _factor_pairs(n: int) -> List[Tuple[int, int]]:
    out = []
    d = 1
    while d * d <= n:
        if n % d == 0:
            out.append((d, n // d))
            out.append((n // d, d))
        d += 1
    return sorted(set(out))


def plan_mesh(n_devices: int, global_batch: int,
              model_parallel_min: int = 1,
              prefer_model: int = 16) -> MeshPlan:
    """Choose (data, model) maximizing data-parallel width subject to:
    model ≥ model_parallel_min (HBM fit) and data | global_batch.

    Among feasible factorizations prefer model size closest to
    ``prefer_model`` (the TP width the kernels are blocked for), breaking
    ties toward larger data.
    """
    candidates = []
    for data, model in _factor_pairs(n_devices):
        if model < model_parallel_min:
            continue
        if global_batch % data != 0:
            continue
        candidates.append((abs(model - prefer_model), -data, data, model))
    if not candidates:
        # degenerate: all devices on model axis
        return MeshPlan(shape=(1, n_devices), axes=("data", "model"),
                        per_device_batch=global_batch,
                        notes="no data-parallel factorization fits")
    _, _, data, model = sorted(candidates)[0]
    return MeshPlan(shape=(data, model), axes=("data", "model"),
                    per_device_batch=global_batch // data)


def elastic_transition(old: MeshPlan, n_devices_now: int,
                       global_batch: int,
                       model_parallel_min: int = 1) -> Dict[str, object]:
    """The coordinator's failover recipe when the device count changes.

    Returns the new plan plus the re-partition summary: which state is
    re-split (optimizer/cache shards move between devices; checkpointed
    global arrays simply re-load under the new sharding).
    """
    new = plan_mesh(n_devices_now, global_batch,
                    model_parallel_min=model_parallel_min,
                    prefer_model=old.shape[-1])
    old_data, old_model = old.shape[-2], old.shape[-1]
    new_data, new_model = new.shape[-2], new.shape[-1]
    return {
        "new_plan": new,
        "batch_resplit": old_data != new_data,
        "weight_reshard": old_model != new_model,
        "cache_resplit": old_data != new_data,   # cache slots follow data
        "restart_from_checkpoint": True,
        "per_device_batch": new.per_device_batch,
    }


# ======================================================= cache elastic rehash

def rehash_cache(old: C.CacheState, new: C.CacheState, now_ms: int,
                 ttl_ms: int, evict_lru: Optional[bool] = None,
                 chunk: int = 4096) -> Tuple[C.CacheState, int]:
    """Re-bucket ``old``'s live, unexpired entries into ``new``'s geometry.

    ``new`` is a (typically empty) table with a different ``n_buckets`` /
    ``ways``; entries flow through the normal ``core.cache`` insert plan so
    every batching/eviction invariant holds. Three properties matter:

    * **Age preservation** — inserts carry ``ts_ms = original write_ts``:
      an entry written at t still expires at t + ttl after the restore.
      (Entries already expired at ``now_ms`` are dropped up front; they
      could never serve a hit again under write-ts validity.)
    * **Newest wins on shrink** — candidates are inserted oldest-first, so
      when more survivors hash to a bucket than it has ways, the plan's
      oldest-timestamp eviction sacrifices the old ones.
    * **Recency survives** — a second pass re-applies ``last_access_ts``
      via the touch scatter-max (the insert reset it to the write ts), so
      LRU-policy tables rank exactly as before the restart.

    Returns ``(state, n_candidates)`` — the count of live unexpired
    entries that were replayed (survivors of a shrink may be fewer).
    """
    keys, vals, wts, lats, live = C.flat_entries(old)
    hi = np.asarray(keys.hi)
    lo = np.asarray(keys.lo)
    vals = np.asarray(vals)
    wts = np.asarray(wts)
    lats = np.asarray(lats)
    # int64 age math: live=False slots hold TS_EMPTY = int32 min, and
    # now - int32min overflows int32.
    age = np.int64(now_ms) - wts.astype(np.int64)
    keep = np.asarray(live) & (age <= int(ttl_ms))
    idx = np.nonzero(keep)[0]
    # Stable oldest-first: ties (same write_ts) keep table order.
    idx = idx[np.argsort(wts[idx], kind="stable")]
    n = int(idx.size)

    state = new
    for base in range(0, n, chunk):
        sel = idx[base:base + chunk]
        b = sel.size
        pad = chunk - b
        k = Key64(
            hi=jnp.asarray(np.pad(hi[sel], (0, pad),
                                  constant_values=EMPTY_HI)),
            lo=jnp.asarray(np.pad(lo[sel], (0, pad),
                                  constant_values=EMPTY_LO)))
        v = jnp.asarray(np.pad(vals[sel], ((0, pad), (0, 0))))
        mask = jnp.asarray(np.arange(chunk) < b)
        state = C.insert(state, k, v, now_ms, ttl_ms, write_mask=mask,
                         ts_ms=jnp.asarray(np.pad(wts[sel], (0, pad))),
                         evict_lru=evict_lru)
    # Recency pass AFTER all inserts: entries evicted by a later chunk
    # simply miss the lookup (way = -1) and are skipped by touch.
    for base in range(0, n, chunk):
        sel = idx[base:base + chunk]
        b = sel.size
        pad = chunk - b
        k = Key64(
            hi=jnp.asarray(np.pad(hi[sel], (0, pad),
                                  constant_values=EMPTY_HI)),
            lo=jnp.asarray(np.pad(lo[sel], (0, pad),
                                  constant_values=EMPTY_LO)))
        mask = jnp.asarray(np.arange(chunk) < b)
        res = C.lookup(state, k, now_ms, ttl_ms)
        state = C.touch(state, res.bucket, res.way,
                        jnp.asarray(np.pad(lats[sel], (0, pad))),
                        live=mask)
    return state, n


def rehash_multi_cache(old: C.MultiCacheState,
                       old_n_buckets: Sequence[int],
                       new: C.MultiCacheState,
                       new_n_buckets: Sequence[int],
                       now_ms: int, ttl_ms: Sequence[int],
                       evict_lru: Optional[Sequence[bool]] = None,
                       chunk: int = 4096
                       ) -> Tuple[C.MultiCacheState, List[int]]:
    """Per-model elastic rehash of a stacked tier.

    Each model's slab is a standalone set-associative table over its own
    first ``n_buckets[m]`` rows, and ``bucket_index`` over a power-of-2
    ``nb`` equals the pooled ``hash & (nb - 1)`` local mapping — so the
    rehash is exactly M single-table rehashes, one per slot, written back
    into the new stack. Returns ``(state, per-model candidate counts)``.
    """
    assert old.n_models == new.n_models, (old.n_models, new.n_models)
    counts: List[int] = []
    image = C.table_image(new)
    for m in range(new.n_models):
        old_v = old.model_view(m, int(old_n_buckets[m]))
        nb = int(new_n_buckets[m])
        out, cnt = rehash_cache(
            old_v, new.model_view(m, nb), now_ms, int(ttl_ms[m]),
            evict_lru=None if evict_lru is None else bool(evict_lru[m]),
            chunk=chunk)
        image = C.TableImage(*(
            whole.at[m, :nb].set(part) for whole, part in
            zip(image, C.table_image(out))))
        counts.append(cnt)
    return C.from_image(image), counts
