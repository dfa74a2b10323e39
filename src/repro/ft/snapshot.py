"""Warm restarts for the serving tier: snapshot/restore of the cache state.

The cache's value is its contents — a deploy or crash that cold-starts the
table burns exactly the tower FLOPs the framework exists to save (paper
§3.6–3.7: the reliability story is that the cache keeps serving through
failures). This module glues the serving tier to the durable layer:

* :func:`snapshot_server` drains the async write/touch rings into the
  tables (``server.flush``) so the image is a PURE cache state, then
  writes ``{direct, failover, budget}`` through ft/checkpoint's atomic
  save with a self-describing metadata record (schema, geometry,
  counters, clock). Torn saves are invisible to restore by construction.
* :func:`restore_server` rebuilds a server state from the latest
  committed snapshot. Three outcomes, in order of preference:

  - **bitexact** — the snapshot geometry matches the target server's:
    the arrays load straight in; serving resumes as if never killed.
  - **rehash** — the geometry differs (grown/shrunk ``n_buckets`` or
    ``ways``, single↔M=1-multi): live unexpired entries are re-bucketed
    through the elastic rehash (ft/elastic.py) with write timestamps and
    recency preserved — capacity is a deploy knob, not a cold start.
  - **cold** — anything else (no/corrupt/incompatible checkpoint): LOG
    and fall back to a cold table. Restore is fail-open and never
    raises into the serve path; an empty cache serves correctly, just
    slower, which always beats not serving.

* Counters provenance: the snapshot carries the accumulated
  :class:`ServingCounters`; the restore hands them back so the serving
  tier RESUMES the ledger additively and rates (hit/fallback/SLA) stay
  correct across the kill/restore boundary.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional

import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import regional as regional_lib
from repro.core import server as server_lib
from repro.core.metrics import ServingCounters
from repro.core.ratelimit import InferBudget
from repro.ft import checkpoint as ckpt
from repro.ft import elastic

log = logging.getLogger(__name__)

SCHEMA = "ercache-snapshot/1"


def _shape_meta(server, state) -> Dict[str, Any]:
    """The snapshot's geometry fingerprint. Restore compares the stored
    fingerprint against the target's: equality ⇒ bit-exact load, anything
    else ⇒ elastic rehash. Per-model bucket counts come from the CONFIGS
    (the capacity masks), not the stack allocation — two stacks of equal
    shape but different per-model capacity still need a rehash."""
    if isinstance(state, regional_lib.RegionalState):
        # the inner stacked tier's fingerprint plus the regional axes; a
        # changed region count (or home-table size) is a different world
        # and restores fail-open to cold, never a silent remap.
        shapes = _shape_meta(server.inner, state.inner)
        shapes["n_regions"] = int(server.n_regions)
        shapes["n_users"] = int(state.home.shape[0])
        return shapes
    if isinstance(state, server_lib.MultiServerState):
        cfgs = list(server.cfgs)
        return {
            "n_models": len(cfgs),
            "direct_nb": [c.n_buckets for c in cfgs],
            "direct_ways": int(state.direct.ways),
            "failover_nb": [c.resolved_failover_n_buckets() for c in cfgs],
            "failover_ways": int(state.failover.ways),
        }
    cfg = server.cfg
    return {
        "direct_nb": int(cfg.n_buckets),
        "direct_ways": int(state.direct.ways),
        "failover_nb": int(cfg.resolved_failover_n_buckets()),
        "failover_ways": int(state.failover.ways),
    }


def snapshot_server(directory: str, step: int, server, state, now_ms: int,
                    counters: Optional[ServingCounters] = None,
                    retain_last_k: Optional[int] = None):
    """Drain the rings and write one atomic snapshot; returns the DRAINED
    state — the caller must continue serving from it (the pre-snapshot
    state still holds buffered writes the tables now also have).

    Uses the server's plain (non-jit) ``flush``: the jitted flush donates
    its input, and a snapshot must never consume the serving state.
    """
    state = server.flush(state, now_ms)
    if isinstance(state, regional_lib.RegionalState):
        kind, image, tier = ("regional", regional_lib.cache_image(state),
                             state.inner)
    elif isinstance(state, server_lib.MultiServerState):
        kind, image, tier = "multi", server_lib.cache_image(state), state
    else:
        kind, image, tier = "single", server_lib.cache_image(state), state
    meta = {
        "schema": SCHEMA,
        "kind": kind,
        "now_ms": int(now_ms),
        "value_dim": int(tier.direct.dim),
        "dtype": str(tier.direct.value_plane.dtype),
        "shapes": _shape_meta(server, state),
        "counters": None if counters is None else counters.as_dict(),
    }
    ckpt.save(directory, step, image, meta=meta,
              retain_last_k=retain_last_k)
    return state


@dataclasses.dataclass
class RestoreResult:
    """What :func:`restore_server` hands the serving tier."""

    state: Any                    # ServerState | MultiServerState
    counters: ServingCounters     # resumed ledger (fresh on cold)
    mode: str                     # "bitexact" | "rehash" | "cold"
    step: Optional[int]           # snapshot step restored from (None: cold)
    detail: str = ""


def _as_stack(single: cache_lib.CacheState) -> cache_lib.MultiCacheState:
    """A single table viewed as an M=1 stacked tier (single↔multi
    conversion on restore)."""
    return cache_lib.MultiCacheState(
        key_hi=single.key_hi[None], key_lo=single.key_lo[None],
        write_ts=single.write_ts[None], value_plane=single.value_plane,
        last_access_ts=single.last_access_ts[None])


def restore_server(directory: str, server, now_ms: int,
                   dtype=jnp.float32, writebuf_capacity: int = 4096,
                   touchbuf_capacity: Optional[int] = None,
                   step: Optional[int] = None) -> RestoreResult:
    """Rebuild a server state from the latest committed snapshot in
    ``directory`` (or ``step``), targeting ``server``'s CURRENT geometry.
    Fail-open: every failure path logs and returns a cold state — restore
    never aborts serving. ``now_ms`` is the stream clock used to drop
    already-expired entries during a rehash.
    """
    regional = isinstance(server, regional_lib.RegionalServer)
    multi = isinstance(server, server_lib.MultiModelServer)
    if regional:
        cold = server.init_state(dtype, writebuf_capacity,
                                 touchbuf_capacity)
    elif multi:
        cold = server_lib.init_multi_server_state(
            server.cfgs, dtype, writebuf_capacity, touchbuf_capacity)
    else:
        cold = server_lib.init_server_state(
            server.cfg, dtype, writebuf_capacity, touchbuf_capacity)
    cold_tier = cold.inner if regional else cold

    # Restore targets the server's PLACEMENT as well as its geometry: a
    # bucket-sharded server (server.mesh set) gets its restored tables
    # device_put across the mesh — so a snapshot taken on N shards restores
    # onto M shards (or onto one device) through the same code path; the
    # shard count is a deploy knob exactly like capacity.
    mesh = getattr(server, "mesh", None)

    def place(st):
        if mesh is None:
            return st
        from repro.distributed import sharding as shard_lib

        return shard_lib.place_server_state(st, mesh)

    def cold_result(detail: str, at: Optional[int] = None) -> RestoreResult:
        log.warning("cache restore fell back to cold init: %s", detail)
        return RestoreResult(state=place(cold), counters=ServingCounters(),
                             mode="cold", step=at, detail=detail)

    try:
        if step is None:
            step = ckpt.latest_step(directory)
        if step is None:
            return cold_result(f"no committed checkpoint in {directory!r}")
        meta = ckpt.read_meta(directory, step)
        if not meta or meta.get("schema") != SCHEMA:
            return cold_result(
                f"step {step}: not an ercache snapshot "
                f"(schema={None if not meta else meta.get('schema')!r})",
                step)
        if int(meta.get("value_dim", -1)) != int(cold_tier.direct.dim):
            return cold_result(
                f"step {step}: value_dim {meta.get('value_dim')} != "
                f"target {cold_tier.direct.dim}", step)
        kind = meta.get("kind")
        shapes = meta["shapes"]

        # Regional snapshots restore BIT-EXACT or not at all: the home
        # plane has no meaningful rehash across a changed region count
        # (a region that no longer exists is not a geometry change, it is
        # a different routing world), so any fingerprint drift — region
        # count, user-table size, inner tier geometry — fails open to a
        # cold start. Kind mismatches in either direction land here too.
        if regional or kind == "regional":
            if not regional:
                return cold_result(
                    f"step {step}: regional snapshot into a "
                    "non-regional server", step)
            if kind != "regional":
                return cold_result(
                    f"step {step}: {kind!r} snapshot into a regional "
                    "server", step)
            if shapes != _shape_meta(server, cold):
                return cold_result(
                    f"step {step}: regional geometry changed (snapshot "
                    f"{shapes.get('n_regions')} regions x "
                    f"{shapes.get('n_models')} slots, "
                    f"{shapes.get('n_users')} users; target "
                    f"{server.n_regions} regions x "
                    f"{server.inner.n_models} slots, {server.n_users} "
                    "users) — regional restore is bit-exact only", step)
            dim = int(meta["value_dim"])
            old_d = cache_lib.init_multi_cache(
                shapes["direct_nb"], shapes["direct_ways"], dim, dtype)
            old_f = cache_lib.init_multi_cache(
                shapes["failover_nb"], shapes["failover_ways"], dim, dtype)
            image = ckpt.restore(directory, step, {
                "direct": cache_lib.table_image(old_d),
                "failover": cache_lib.table_image(old_f),
                "budget": InferBudget(tokens=jnp.zeros(
                    (int(shapes["n_models"]),), jnp.float32)),
                "home": jnp.zeros((int(shapes["n_users"]),), jnp.int32)})
            counters = (ServingCounters.from_dict(meta["counters"])
                        if meta.get("counters") else ServingCounters())
            state = regional_lib.with_cache_image(cold, image)
            return RestoreResult(state=state, counters=counters,
                                 mode="bitexact", step=step,
                                 detail=f"loaded step {step} in place")

        # Rebuild the image at its ORIGINAL geometry (restore() is
        # shape-checked against this, so a manifest/meta mismatch lands
        # in the except-path and degrades to cold).
        dim = int(meta["value_dim"])
        if kind == "multi":
            old_d = cache_lib.init_multi_cache(
                shapes["direct_nb"], shapes["direct_ways"], dim, dtype)
            old_f = cache_lib.init_multi_cache(
                shapes["failover_nb"], shapes["failover_ways"], dim, dtype)
            n_old = int(shapes["n_models"])
        elif kind == "single":
            old_d = cache_lib.init_cache(
                shapes["direct_nb"], shapes["direct_ways"], dim, dtype)
            old_f = cache_lib.init_cache(
                shapes["failover_nb"], shapes["failover_ways"], dim, dtype)
            n_old = 1
        else:
            return cold_result(f"step {step}: unknown kind {kind!r}", step)
        image = ckpt.restore(directory, step, {
            "direct": cache_lib.table_image(old_d),
            "failover": cache_lib.table_image(old_f),
            "budget": InferBudget(tokens=jnp.zeros((n_old,), jnp.float32))})
        old_d = cache_lib.from_image(image["direct"])
        old_f = cache_lib.from_image(image["failover"])
        counters = (ServingCounters.from_dict(meta["counters"])
                    if meta.get("counters") else ServingCounters())

        # Carry the admission tokens whenever the registry width agrees;
        # the first refill clamps any excess to the burst, so restored
        # tokens self-correct against a changed budget config.
        budget = cold.budget
        if image["budget"].tokens.shape == cold.budget.tokens.shape:
            budget = image["budget"]

        same_kind = (kind == "multi") == multi
        if same_kind and shapes == _shape_meta(server, cold):
            state = cold._replace(direct=old_d, failover=old_f,
                                  budget=budget)
            return RestoreResult(state=place(state), counters=counters,
                                 mode="bitexact", step=step,
                                 detail=f"loaded step {step} in place")

        # Geometry changed: elastic rehash of live unexpired entries.
        if multi:
            if kind == "single":
                if server.n_models != 1:
                    return cold_result(
                        f"step {step}: single-model snapshot into a "
                        f"{server.n_models}-model tier", step)
                old_dm, old_fm = _as_stack(old_d), _as_stack(old_f)
                nb_d, nb_f = [shapes["direct_nb"]], [shapes["failover_nb"]]
            else:
                if n_old != server.n_models:
                    return cold_result(
                        f"step {step}: snapshot has {n_old} models, "
                        f"target has {server.n_models}", step)
                old_dm, old_fm = old_d, old_f
                nb_d, nb_f = shapes["direct_nb"], shapes["failover_nb"]
            cfgs = list(server.cfgs)
            lru = [c.eviction == "lru" for c in cfgs]
            new_d, cnt_d = elastic.rehash_multi_cache(
                old_dm, nb_d, cold.direct, [c.n_buckets for c in cfgs],
                now_ms, [c.cache_ttl_ms for c in cfgs], evict_lru=lru)
            new_f, cnt_f = elastic.rehash_multi_cache(
                old_fm, nb_f, cold.failover,
                [c.resolved_failover_n_buckets() for c in cfgs], now_ms,
                [c.resolved_failover_relax_ttl_ms() for c in cfgs],
                evict_lru=lru)
            n_dir, n_fo = sum(cnt_d), sum(cnt_f)
        else:
            if kind == "multi":
                if n_old != 1:
                    return cold_result(
                        f"step {step}: {n_old}-model snapshot into a "
                        "single-model server", step)
                old_d1 = old_d.model_view(0, int(shapes["direct_nb"][0]))
                old_f1 = old_f.model_view(0, int(shapes["failover_nb"][0]))
            else:
                old_d1, old_f1 = old_d, old_f
            cfg = server.cfg
            lru1 = cfg.eviction == "lru"
            new_d, n_dir = elastic.rehash_cache(
                old_d1, cold.direct, now_ms, cfg.cache_ttl_ms,
                evict_lru=lru1)
            new_f, n_fo = elastic.rehash_cache(
                old_f1, cold.failover, now_ms,
                cfg.resolved_failover_relax_ttl_ms(), evict_lru=lru1)
        state = cold._replace(direct=new_d, failover=new_f, budget=budget)
        detail = (f"rehashed step {step}: {n_dir} direct + {n_fo} "
                  "failover live entries into new geometry")
        log.info("cache restore: %s", detail)
        return RestoreResult(state=place(state), counters=counters,
                             mode="rehash", step=step, detail=detail)
    except Exception as e:                       # noqa: BLE001 — fail-open
        return cold_result(f"step {step}: {type(e).__name__}: {e}", step)
