"""Update combination (paper §3.4, Fig. 5).

Production ERCache consolidates the embeddings a user produced across *all*
ranking models × ranking stages into ONE cache-write request, cutting write
QPS by ≥ 30× for 30 models. The TPU-native analogue: all member models share
one grouped cache entry per user — a single bucket slot whose value row is the
concatenation of every member's embedding, plus a per-slot ``present`` bitmap
(bit per member) so per-model validity survives partial failures.

One grouped insert == one scatter == "one write request"; per-member lookups
slice the group row and apply the member's own TTL against the shared
write timestamp.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core.cache import CacheState, LookupResult
from repro.core.hashing import Key64


@dataclasses.dataclass(frozen=True)
class GroupMember:
    name: str           # e.g. "ctr_first"
    dim: int
    ttl_ms: int


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    members: Tuple[GroupMember, ...]

    def __post_init__(self):
        assert len(self.members) <= 32, "present bitmap is one int32"

    @property
    def total_dim(self) -> int:
        return sum(m.dim for m in self.members)

    def offset(self, name: str) -> Tuple[int, int, int]:
        """(member index, start, end) of a member's slice in the group row."""
        off = 0
        for i, m in enumerate(self.members):
            if m.name == name:
                return i, off, off + m.dim
            off += m.dim
        raise KeyError(name)


class GroupedCacheState(NamedTuple):
    base: CacheState
    present: jnp.ndarray  # (n_buckets, ways) int32 bitmap — bit i: member i valid


def init_grouped(spec: GroupSpec, n_buckets: int, ways: int,
                 dtype=jnp.float32) -> GroupedCacheState:
    base = cache_lib.init_cache(n_buckets, ways, spec.total_dim, dtype)
    return GroupedCacheState(
        base=base, present=jnp.zeros((n_buckets, ways), jnp.int32))


def insert_group(spec: GroupSpec, state: GroupedCacheState, keys: Key64,
                 member_values: Dict[str, jnp.ndarray], now_ms,
                 member_mask: Optional[Dict[str, jnp.ndarray]] = None,
                 write_mask: Optional[jnp.ndarray] = None,
                 ts_ms: Optional[jnp.ndarray] = None) -> GroupedCacheState:
    """ONE combined write for all members (the Fig. 5 consolidation).

    ``member_values[name]`` is (B, dim_name); ``member_mask[name]`` (B,) marks
    which users actually produced that member this round (failed inferences
    contribute nothing — their bit stays 0).
    """
    B = keys.hi.shape[0]
    rows, bits = [], jnp.zeros((B,), jnp.int32)
    for i, m in enumerate(spec.members):
        v = member_values.get(m.name)
        if v is None:
            rows.append(jnp.zeros((B, m.dim), state.base.value_plane.dtype))
            continue
        ok = (member_mask or {}).get(m.name)
        if ok is None:
            ok = jnp.ones((B,), bool)
        rows.append(jnp.where(ok[:, None], v, 0).astype(state.base.value_plane.dtype))
        bits = bits | jnp.where(ok, jnp.int32(1 << i), jnp.int32(0))
    group_row = jnp.concatenate(rows, axis=-1)

    # Reuse the base-insert slot plan, then stamp the bitmap on the SAME
    # slots (plan_insert is deterministic on the pre-insert state).
    eviction_ttl = jnp.int32(max(m.ttl_ms for m in spec.members))
    winner, bucket, way = cache_lib.plan_insert(
        state.base, keys, now_ms, eviction_ttl, write_mask)
    new_base = cache_lib.insert(state.base, keys, group_row, now_ms,
                                eviction_ttl, write_mask, ts_ms)
    b_w = jnp.where(winner, bucket, jnp.int32(state.base.n_buckets))
    new_present = state.present.at[b_w, way].set(bits, mode="drop")
    return GroupedCacheState(base=new_base, present=new_present)


def lookup_member(spec: GroupSpec, state: GroupedCacheState, name: str,
                  keys: Key64, now_ms) -> LookupResult:
    """Per-model read: slice the group row, member's own TTL + present bit."""
    idx, lo, hi = spec.offset(name)
    member = spec.members[idx]
    res = cache_lib.lookup(state.base, keys, now_ms, member.ttl_ms)
    bucket, match, _, ts = cache_lib._probe(state.base, keys)
    fresh = (jnp.int32(now_ms) - ts) <= jnp.int32(member.ttl_ms)  # erlint: allow[ER004] — match masks the wrap
    valid = match & fresh
    way = jnp.argmax(valid, axis=-1)
    bit = (state.present[bucket, way] >> idx) & 1
    hit = res.hit & (bit == 1)
    vals = res.values[:, lo:hi]
    vals = jnp.where(hit[:, None], vals, jnp.zeros_like(vals))
    return LookupResult(hit=hit, values=vals,
                        age_ms=jnp.where(hit, res.age_ms, jnp.int32(-1)))


def write_amplification(n_models: int, n_stages: int) -> float:
    """Writes-per-user without combining / with combining (paper: ≥ 30×)."""
    return float(n_models * n_stages) / 1.0
