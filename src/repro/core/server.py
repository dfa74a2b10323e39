"""CachedEmbeddingServer — the paper's Fig. 3 sequence diagram as one
static-shape JAX program (DESIGN.md §2, "miss-budget compaction").

Per serve batch:

  1. **Direct cache check** — TTL-validated probe for every request. The
     failover probe (step 3) is issued in the SAME dispatch: on the pallas
     backend both tables are probed by one fused kernel launch
     (``cache_probe_dual``, DESIGN.md §4).
  2. **Compaction** — misses are compacted to the front (stable argsort on the
     hit flag) and the user tower runs on the first ``miss_budget`` of them
     only. ``miss_budget`` is the provisioned-compute knob: the paper's
     "constrained computational resources" as a literal static shape.
  3. **Failover cache assistance** — inference *failures* (injected or real)
     and miss-budget *overflow* consult the long-TTL failover cache; what it
     cannot recover becomes a **model fallback** (default embedding), the
     paper's fallback-rate metric.
  4. **Cache update** — computed embeddings are appended to the async write
     buffer (one combined record per user; flushed off the critical path).
     Hits append their (bucket, way) coordinates to the TOUCH buffer the
     same way; the flush scatter-maxes them into the last_access_ts
     recency plane that LRU eviction ranks on (DESIGN.md §3.1).

Every request's provenance is reported (DIRECT/COMPUTED/FAILOVER/FALLBACK) so
the serving tier can account Tables 2–3 mechanically.

**SLA-aware admission control** (DESIGN.md §8): when
``CacheConfig.infer_budget_per_step`` is set, a jit-resident per-model
token bucket (``ratelimit.InferBudget``, part of the donated server
state) gates which misses are ADMITTED to model inference each step.
Misses over budget are *deferred* and fall through the degradation
chain — direct hit → failover hit at the RELAXED TTL
(``failover_ttl_relax``; None = any staleness) → default embedding —
with distinct ``admitted`` / ``deferred`` / ``failover_serves`` /
``failover_stale_ms`` counters so SLA compliance and staleness cost are
both observable. Admitted inferences still write back to BOTH tiers on
flush, which is what keeps the failover slab warm enough to catch the
deferred traffic.

**Streaming serve** (DESIGN.md §9): ``serve_many`` runs S serve steps in
ONE dispatch — a ``lax.scan`` over a pre-staged (S, B) stream with the
async flush folded in every F steps and a device-resident additive
counter pytree threaded through the carry, fetched ONCE per dispatch
instead of per step. **In-batch coalescing**
(``CacheConfig.coalesce_misses``) dedupes the admitted-miss keys inside
each step (the ``cache._dedupe`` lexsort machinery, first occurrence
wins), runs the tower once per distinct user, broadcasts the embedding
to the duplicates, and charges the inference token budget per UNIQUE
inference — tower FLOPs drop with traffic skew while the
``admitted``/``sla_served_rate`` ledger keeps its per-request meaning.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import cache as cache_lib
from repro.core import ratelimit as rl_lib
from repro.core import trace
from repro.core import writebuf as wb_lib
from repro.core.cache import CacheState
from repro.core.config import CacheConfig
from repro.core.hashing import Key64
from repro.core.writebuf import TouchBuffer, WriteBuffer

# Provenance codes (per request)
SRC_DIRECT = 0
SRC_COMPUTED = 1
SRC_FAILOVER = 2
SRC_FALLBACK = 3


class ServerState(NamedTuple):
    direct: CacheState
    failover: CacheState
    writebuf: WriteBuffer
    touchbuf: TouchBuffer
    # Per-model inference token bucket ((1,) on the single-model server).
    # Allocated unconditionally so the pytree structure doesn't depend on
    # whether admission control is configured; untouched when it is off.
    budget: rl_lib.InferBudget


class ServeResult(NamedTuple):
    embeddings: jnp.ndarray   # (B, D)
    source: jnp.ndarray       # (B,) int32 — SRC_* provenance
    age_ms: jnp.ndarray       # (B,) int32 — staleness of the served embedding
    state: ServerState        # updated (write buffer appended)
    stats: dict               # scalar counters


def init_server_state(cfg: CacheConfig, dtype=jnp.float32,
                      writebuf_capacity: int = 4096,
                      touchbuf_capacity: Optional[int] = None,
                      mesh=None) -> ServerState:
    """Allocate both caches + the write and touch buffers. The failover
    cache is sized from its OWN config knobs (paper §4.4 gives it different
    capacity/TTL than the direct tier); unset knobs fall back to the direct
    sizing. The touch buffer (hit coordinates awaiting last-access bumps)
    defaults to the write buffer's capacity. ``mesh`` places the cache
    tables bucket-sharded across the mesh's ``shard`` axis and replicates
    the rings/budget (DESIGN.md §11); both tiers' bucket counts must
    divide the shard count."""
    if touchbuf_capacity is None:
        touchbuf_capacity = writebuf_capacity

    def build():
        return ServerState(
            direct=cache_lib.init_cache(cfg.n_buckets, cfg.ways,
                                        cfg.value_dim, dtype),
            failover=cache_lib.init_cache(cfg.resolved_failover_n_buckets(),
                                          cfg.resolved_failover_ways(),
                                          cfg.value_dim, dtype),
            writebuf=wb_lib.init_writebuf(writebuf_capacity, cfg.value_dim,
                                          dtype),
            touchbuf=wb_lib.init_touchbuf(touchbuf_capacity),
            budget=rl_lib.init_infer_budget([cfg]),
        )

    if mesh is None:
        return build()
    from repro.distributed import sharding as shard_lib

    return shard_lib.init_placed_server_state(build, mesh)


def _per_model_miss_rank(slots, miss, n_models: int) -> jnp.ndarray:
    """(B,) batch-order rank of each miss among ITS model's misses — the
    per-model admission cutoff index (reuses the insert plan's segmented
    rank sort). Garbage where ``miss`` is False; callers gate on it."""
    return cache_lib._bucket_rank(slots, miss, n_models)


# ------------------------------------------------- serve_many accumulators
# The additive subset of serve_step's stats dict: what the scan driver's
# device-resident counter pytree carries across steps (DESIGN.md §9).
# Means are NOT additive, so the *_sum_ms / *_count raw keys ride instead
# and ServingCounters / the launchers derive means after the single
# per-dispatch fetch.
_ACC_I32 = ("requests", "direct_hits", "tower_inferences", "tower_failures",
            "overflow", "admitted", "deferred", "failover_hits",
            "failover_serves", "fallbacks", "served_age_count")
_ACC_F32 = ("failover_stale_sum_ms", "served_age_sum_ms")
_ACC_PM_I32 = ("per_model_requests", "per_model_direct_hits",
               "per_model_failover_hits", "per_model_fallbacks",
               "per_model_admitted", "per_model_deferred",
               "per_model_failover_serves")
_ACC_PM_F32 = ("per_model_failover_stale_sum_ms",)
# Chaos-only additive keys (DESIGN.md §14): the degradation ledger's
# retry/drop accounting. Only materialized when a fault schedule rides
# the scan, so the chaos-off accumulator (and trace) is unchanged.
_ACC_CHAOS_STEP = ("computed_serves", "retries", "retry_successes",
                   "blackout_write_drops")
_ACC_CHAOS_SCAN = ("write_ring_drops", "touch_ring_drops")


def _zero_acc(n_models: Optional[int] = None, chaos: bool = False) -> dict:
    """The scan carry's zeroed counter pytree. ``steps`` counts scan
    iterations (one grouped async write per step — the combined_writes
    analogue). ``chaos`` adds the degradation-ledger keys a fault
    schedule feeds."""
    acc = {k: jnp.int32(0) for k in _ACC_I32}
    acc.update({k: jnp.float32(0) for k in _ACC_F32})
    acc["steps"] = jnp.int32(0)
    if n_models is not None:
        acc.update({k: jnp.zeros((n_models,), jnp.int32)
                    for k in _ACC_PM_I32})
        acc.update({k: jnp.zeros((n_models,), jnp.float32)
                    for k in _ACC_PM_F32})
    if chaos:
        acc.update({k: jnp.int32(0)
                    for k in _ACC_CHAOS_STEP + _ACC_CHAOS_SCAN})
    return acc


def _acc_add(acc: dict, stats: dict) -> dict:
    """One scan step's counter contribution — device adds, no host sync.
    Keys the step's stats don't carry (the scan-level ring-drop counters)
    pass through untouched; the scan body owns them."""
    out = {k: (acc[k] + stats[k] if k in stats else acc[k])
           for k in acc if k != "steps"}
    out["steps"] = acc["steps"] + jnp.int32(1)
    return out


def _serve_many_scan(step_fn, flush_fn, state, payload, now_ms,
                     failure_mask, acc0, *, flush_every: int, collect: bool,
                     chaos=None):
    """The scan driver shared by both servers' ``serve_many``: scan
    ``step_fn(state, payload_row, now, fail) -> ServeResult`` over the
    staged stream, accumulating counters in the carry, folding the flush
    in every ``flush_every`` steps (statically inlined at 1, ``lax.cond``
    otherwise, 0 = tail only) and always tail-flushing.

    ``chaos`` (a compiled ``ft.chaos.ChaosSchedule`` with (S, ...)
    leading axes) rides the scan as an extra input: each step consumes
    its own row, ``FlushStall`` windows gate the folded flush off
    (``lax.cond`` — the tail flush still runs, so recovery always
    drains), and the ring-overflow drops the stall causes are accounted
    on device (``write_ring_drops`` / ``touch_ring_drops``: the records
    each ring's last-capacity-wins contract discarded). With
    ``chaos=None`` the scan's structure — and trace — is EXACTLY the
    pre-chaos one."""
    S = now_ms.shape[0]
    flush_every = int(flush_every)

    def flush_pred(i, ch):
        on = jnp.asarray(True) if flush_every == 1 else (
            (i + 1) % flush_every == 0)
        return on if ch is None else on & ~ch.flush_off

    def body(carry, x):
        st, acc = carry
        if chaos is None:
            i, pay, now, fail = x
            ch = None
            res = step_fn(st, pay, now, fail)
        else:
            i, pay, now, fail, ch = x
            wb0 = jnp.maximum(st.writebuf.count - st.writebuf.capacity, 0)
            tb0 = jnp.maximum(st.touchbuf.count - st.touchbuf.capacity, 0)
            res = step_fn(st, pay, now, fail, ch)
        acc = _acc_add(acc, res.stats)
        st = res.state
        if chaos is not None:
            # ring-drop deltas BEFORE the (possibly stalled) flush: how
            # far past capacity this step's appends pushed each ring
            wb1 = jnp.maximum(st.writebuf.count - st.writebuf.capacity, 0)
            tb1 = jnp.maximum(st.touchbuf.count - st.touchbuf.capacity, 0)
            acc["write_ring_drops"] = acc["write_ring_drops"] + (wb1 - wb0)
            acc["touch_ring_drops"] = acc["touch_ring_drops"] + (tb1 - tb0)
        if flush_every == 1 and chaos is None:
            st = flush_fn(st, now)
        elif flush_every >= 1:
            st = jax.lax.cond(flush_pred(i, ch),
                              lambda s: flush_fn(s, now), lambda s: s, st)
        ys = ((res.embeddings, res.source, res.age_ms) if collect
              else None)
        return (st, acc), ys

    xs = (jnp.arange(S, dtype=jnp.int32), payload, now_ms, failure_mask)
    if chaos is not None:
        xs = xs + (chaos,)
    (state, acc), ys = jax.lax.scan(body, (state, acc0), xs)
    with jax.named_scope(trace.TAIL_FLUSH):
        state = flush_fn(state, now_ms[-1])
    return state, acc, ys


def _serve_tail(tower_fn: Callable, miss_budget: int, fallback_value: float,
                params, features, keys: Key64, now_ms, failure_mask,
                direct, fo, writebuf: WriteBuffer,
                model_slots=None, n_models: Optional[int] = None,
                admit: Optional[jnp.ndarray] = None,
                fo_strict_hit: Optional[jnp.ndarray] = None,
                infer: Optional[jnp.ndarray] = None,
                src_row: Optional[jnp.ndarray] = None,
                write_drop: Optional[jnp.ndarray] = None):
    """Steps (2)–(4) of the Fig. 3 serve sequence, shared by the single-
    and multi-model servers (step (1), the dual probe, differs):

    miss-budget compaction + tower, failover assistance / model fallback,
    provenance + counters, write-buffer append. ``model_slots``/
    ``n_models`` (multi-model tier) tag buffered records and add per-model
    (M,) stat breakdowns.

    ``admit`` (B,) bool marks the misses ADMITTED to model inference by
    the per-model token budget (None → every miss, the pre-admission
    behavior); deferred misses (miss & ~admit) skip the tower and fall
    through the degradation chain. ``fo`` is then the RELAXED-TTL failover
    probe and ``fo_strict_hit`` (B,) its strict-TTL subset (None → same as
    ``fo.hit``), so ``failover_hits`` keeps its strict meaning while
    ``failover_serves`` counts every failover-tier serve on the chain.

    In-batch coalescing (DESIGN.md §9) splits "runs the tower" from "is
    served by the tower": ``infer`` (B,) bool marks the rows that RUN a
    tower inference (the duplicate-group representatives; None → same as
    ``admit``) and ``src_row`` (B,) int32 maps every admitted row to the
    batch row whose tower output serves it (None → the identity, the
    uncoalesced bit-exact legacy path). ``admit`` then covers every
    duplicate of an admitted representative while the tower and the token
    budget pay once per distinct user.

    ``write_drop`` (B,) bool (chaos ``BucketBlackout``, DESIGN.md §14)
    marks rows whose cache INSERT would land in a blacked-out bucket
    range: their computed embeddings still SERVE this batch but never
    enter the write buffer, and the drops are counted
    (``blackout_write_drops``).

    Returns (embeddings, source, age, new_writebuf, stats).
    """
    B = keys.hi.shape[0]
    miss = ~direct.hit
    if admit is None:
        admit = miss
    if infer is None:
        infer = admit
    if fo_strict_hit is None:
        fo_strict_hit = fo.hit

    # (2) compaction: rows that RUN the tower first, stable ---------------
    with jax.named_scope(trace.ADMIT):
        order = jnp.argsort(~infer, stable=True)        # inference rows first
        sel = order[:miss_budget]                       # batch indices
        sel_is_inf = infer[sel]                         # tail may be hits
        sel_features = jax.tree_util.tree_map(lambda x: x[sel], features)
    with jax.named_scope(trace.TOWER):
        towered = tower_fn(params, sel_features)        # (miss_budget, D)
        towered = towered.astype(direct.values.dtype)

    with jax.named_scope(trace.SERVE):
        sel_failed = failure_mask[sel]
        sel_ok = sel_is_inf & ~sel_failed               # produced embedding

        # (3) scatter computed rows back (broadcast to duplicates when
        # coalescing); the degradation chain for the rest — deferred (over
        # budget) ∪ overflow (over miss_budget) ∪ failed all consult the
        # failover probe, then the default embedding.
        if src_row is None:
            computed = jnp.zeros((B,), bool).at[sel].set(sel_ok)
            emb = direct.values
            emb = emb.at[sel].set(
                jnp.where(sel_ok[:, None], towered, emb[sel]))
        else:
            src = jnp.maximum(src_row, 0)     # -1 (no group) rows gated below
            ok_row = jnp.zeros((B,), bool).at[sel].set(sel_ok)
            computed = admit & ok_row[src]
            tower_rows = jnp.zeros_like(direct.values).at[sel].set(
                jnp.where(sel_is_inf[:, None], towered, 0))
            emb = jnp.where(computed[:, None], tower_rows[src], direct.values)
        unresolved = miss & ~computed
        use_fo = unresolved & fo.hit
        emb = jnp.where(use_fo[:, None], fo.values.astype(emb.dtype), emb)
        fallback = unresolved & ~fo.hit
        emb = jnp.where(fallback[:, None],
                        jnp.full_like(emb, fallback_value), emb)

        source = jnp.where(
            direct.hit, SRC_DIRECT,
            jnp.where(computed, SRC_COMPUTED,
                      jnp.where(use_fo, SRC_FAILOVER, SRC_FALLBACK))
        ).astype(jnp.int32)
        age = jnp.where(direct.hit, direct.age_ms,
                        jnp.where(computed, 0,
                                  jnp.where(use_fo, fo.age_ms, -1)))

        # (4) async cache update: append computed rows to the write buffer ----
        sel_keys = Key64(hi=keys.hi[sel], lo=keys.lo[sel])
        wb_mask = sel_ok if write_drop is None else sel_ok & ~write_drop[sel]
        new_wb = wb_lib.append(
            writebuf, sel_keys, towered, now_ms, mask=wb_mask,
            model_ids=None if model_slots is None else model_slots[sel])

        def count(flag):
            return jnp.sum(flag.astype(jnp.int32))

        # Staleness accounting of the failover serves (float32: int32 would
        # wrap on a batch of hour-scale ages) — the SLA trade's cost side.
        fo_age_sum = jnp.sum(jnp.where(use_fo, fo.age_ms, 0)
                             .astype(jnp.float32))
        # age >= 0: a hit written and read in the same millisecond is a
        # legitimate age-0 serve and must count in both numerator and
        # denominator (misses carry age -1 and stay excluded).
        age_sum = jnp.sum(jnp.where(age >= 0, age, 0).astype(jnp.float32))
        age_served = jnp.sum((age >= 0).astype(jnp.int32))
        stats = {
            "requests": jnp.int32(B),
            "direct_hits": count(direct.hit),
            # actual tower forward passes: one per UNIQUE admitted user when
            # coalescing, one per admitted miss row otherwise
            "tower_inferences": count(sel_is_inf),
            "tower_failures": count(sel_is_inf & sel_failed),
            # wanted inferences beyond the miss-budget window (never attempted)
            "overflow": count(infer) - count(sel_is_inf),
            # admission-control ledger: admitted counts every COVERED request
            # row (duplicates of an admitted user included, so deferred keeps
            # its per-request meaning); deferred = misses the budget gated off
            "admitted": count(admit),
            "deferred": count(miss) - count(admit),
            # strict-TTL failover recoveries (the pre-admission meaning) vs
            # ALL failover-tier serves on the degradation chain
            "failover_hits": count(use_fo & fo_strict_hit),
            "failover_serves": count(use_fo),
            "fallbacks": count(fallback),
            "failover_stale_ms": fo_age_sum /
                jnp.maximum(count(use_fo), 1).astype(jnp.float32),
            "mean_age_ms": age_sum /
                jnp.maximum(age_served, 1).astype(jnp.float32),
            # Additive twins of the mean keys above: means cannot be summed
            # across steps, so serve_many's device-resident accumulator
            # (DESIGN.md §9) carries the raw sums and derives means on the
            # host after the single per-dispatch fetch.
            "failover_stale_sum_ms": fo_age_sum,
            "served_age_sum_ms": age_sum,
            "served_age_count": age_served,
            # tower-served request rows (duplicates included): with
            # direct_hits / failover_serves / fallbacks this partitions the
            # batch — the degradation ledger's conservation identity
            "computed_serves": count(computed),
        }
        if write_drop is not None:
            stats["blackout_write_drops"] = count(sel_ok & write_drop[sel])
        if model_slots is not None:
            # per-model (M,) breakdowns for Table-1-style accounting
            def per_model(flag, dtype=jnp.int32):
                return (jnp.zeros((n_models,), dtype)
                        .at[model_slots].add(flag.astype(dtype)))

            stats["per_model_requests"] = per_model(jnp.ones((B,), bool))
            stats["per_model_direct_hits"] = per_model(direct.hit)
            stats["per_model_failover_hits"] = per_model(
                use_fo & fo_strict_hit)
            stats["per_model_fallbacks"] = per_model(fallback)
            stats["per_model_admitted"] = per_model(admit)
            stats["per_model_deferred"] = per_model(miss) - per_model(admit)
            stats["per_model_failover_serves"] = per_model(use_fo)
            pm_stale_sum = per_model(jnp.where(use_fo, fo.age_ms, 0),
                                     jnp.float32)
            stats["per_model_failover_stale_ms"] = (
                pm_stale_sum
                / jnp.maximum(per_model(use_fo), 1).astype(jnp.float32))
            stats["per_model_failover_stale_sum_ms"] = pm_stale_sum
        return emb, source, age.astype(jnp.int32), new_wb, stats


# ------------------------------------------------------- chaos serve hooks
# The serve-step side of the chaos engine (DESIGN.md §14). The schedule
# row is DUCK-TYPED — any pytree with fields ``fail`` (B,) bool,
# ``retry_fail`` (R, B) bool, ``outage`` (M,) bool, ``blackout_lo``/
# ``blackout_hi`` () int32 works (ft/chaos.py compiles one) — so core
# never imports ft. ``flush_off``/``skew_ms`` are consumed by the scan
# driver / the launcher's clock staging, not here.

def _chaos_blackout(direct, ch):
    """Mask a bucket-range blackout onto the direct probe: hits whose
    bucket lands in ``[blackout_lo, blackout_hi)`` become COLD misses
    (values zeroed, age/way -1 — indistinguishable from a real miss, so
    touch/coalesce/admission all see a cold row) and the returned (B,)
    drop mask marks every row whose INSERT would land in the range
    (``_serve_tail`` drops those appends — the blacked-out shard's write
    path is down for both tiers, since the shared ring feeds both).
    Probes hash to the same bucket they insert to, so one mask covers
    both directions. The failover READ path stays up: it is what absorbs
    the blacked-out range. An empty range (lo == hi, the benign row)
    masks nothing — bit-identical values to the unmasked probe."""
    bl = (direct.bucket >= ch.blackout_lo) & (direct.bucket < ch.blackout_hi)
    masked = direct._replace(
        hit=direct.hit & ~bl,
        values=jnp.where(bl[:, None], 0, direct.values),
        age_ms=jnp.where(bl, jnp.int32(-1), direct.age_ms),
        way=jnp.where(bl, jnp.int32(-1), direct.way),
    )
    return masked, bl


def _chaos_retries(ch, infer, failure_mask, budget, limited,
                   slots=None, n_models: Optional[int] = None):
    """Bounded retry-with-backoff for this step's FAILED tower attempts,
    inside the admission budget (DESIGN.md §14): attempt r is granted
    from the tokens LEFT after the initial grant (retries charge tokens,
    so a saturated bucket starves its own retries), and succeeds iff the
    schedule's ``retry_fail[r]`` row clears it — that row was sampled at
    the backoff-shifted time with outage windows OR'd in, so a retry
    landing in an outage re-fails deterministically. Recovered rows get
    their failure bit CLEARED: the tower output for the row is already
    materialized in the execution window, so clearing the bit is exactly
    "the retry produced the embedding" (the sim's tower is
    deterministic). Unlimited models grant retries freely, matching the
    initial-grant passthrough.

    Returns (effective failure mask, spent budget, retries, successes);
    the loop is a static unroll over the policy's max_retries."""
    still = infer & failure_mask
    n_att = jnp.int32(0)
    n_succ = jnp.int32(0)
    for r in range(ch.retry_fail.shape[0]):
        if slots is None:
            s_i = still.astype(jnp.int32)
            rank = jnp.cumsum(s_i) - s_i                     # exclusive
            demand = jnp.sum(s_i)[None]
            grant = rl_lib.grant_from(budget, limited, demand)
            att = still & (rank < grant[0])
            spent = jnp.sum(att.astype(jnp.int32))[None]
        else:
            rank = _per_model_miss_rank(slots, still, n_models)
            demand = (jnp.zeros((n_models,), jnp.int32)
                      .at[slots].add(still.astype(jnp.int32)))
            grant = rl_lib.grant_from(budget, limited, demand)
            att = still & (rank < grant[slots])
            spent = (jnp.zeros((n_models,), jnp.int32)
                     .at[slots].add(att.astype(jnp.int32)))
        budget = rl_lib.spend(budget, limited, spent)
        succ = att & ~ch.retry_fail[r]
        n_att = n_att + jnp.sum(att.astype(jnp.int32))
        n_succ = n_succ + jnp.sum(succ.astype(jnp.int32))
        still = still & ~succ
    recovered = (infer & failure_mask) & ~still
    return failure_mask & ~recovered, budget, n_att, n_succ


@dataclasses.dataclass(frozen=True)
class CachedEmbeddingServer:
    """Binds a user-tower fn to ERCache semantics.

    ``tower_fn(params, features) -> (B, D)`` must be shape-polymorphic in B
    (it is called with ``miss_budget`` rows).
    """

    cfg: CacheConfig
    tower_fn: Callable
    miss_budget: int
    fallback_value: float = 0.0   # default embedding on total fallback
    # Bucket-sharded cache tier (DESIGN.md §11): when set, the dual probe
    # and the flush run under shard_map on this 1-D ("shard",) mesh with
    # each device owning a contiguous bucket range — bit-identical to the
    # single-device path. The server state must be placed accordingly
    # (init_server_state(mesh=...) / sharding.place_server_state).
    mesh: Optional[jax.sharding.Mesh] = None

    def __post_init__(self) -> None:
        # Admission-control tables, materialized EAGERLY (same rationale as
        # MultiModelServer's policy table: never build constants inside a
        # jit trace). (1,)-shaped: the single-model tier is the M=1 case
        # of the vectorized bucket.
        object.__setattr__(self, "_admission",
                           self.cfg.infer_budget_per_step is not None)
        rates, bursts, limited = rl_lib.budget_table([self.cfg])
        object.__setattr__(self, "_budget_rates", rates)
        object.__setattr__(self, "_budget_bursts", bursts)
        object.__setattr__(self, "_budget_limited", limited)

    # ----------------------------------------------------------------- serve
    def serve_step(self, params, state: ServerState, keys: Key64,
                   features, now_ms, failure_mask: Optional[jnp.ndarray] = None,
                   chaos=None) -> ServeResult:
        """``chaos`` (None = today's serve path, bit-exact) is one step's
        fault-schedule row — a duck-typed pytree with ``fail`` (B,),
        ``retry_fail`` (R, B), ``outage`` ((1,) here), ``blackout_lo``/
        ``blackout_hi`` scalars (``ft.chaos.slice_schedule`` /
        ``_serve_many_scan`` produce rows). Fault schedules require
        admission control: outage and retry accounting live in the token
        bucket."""
        B = keys.hi.shape[0]
        cfg = self.cfg
        now_ms = jnp.int32(now_ms)
        if failure_mask is None:
            failure_mask = jnp.zeros((B,), bool)
        if chaos is not None:
            if not self._admission:
                raise ValueError(
                    "chaos fault schedules require admission control: set "
                    "CacheConfig.infer_budget_per_step")
            failure_mask = failure_mask | chaos.fail

        # (1) direct + failover cache check — ONE dispatch ----------------
        # Both probes read the pre-step state, so they fuse into a single
        # kernel launch on the pallas backend (cache_probe_dual); the
        # failover result is only consulted in step (3). With admission
        # control on, the failover validates at the RELAXED TTL (the
        # degradation chain may serve past the strict TTL) and the strict
        # hit set is recovered from the probe's age below.
        fo_ttl = cfg.resolved_failover_relax_ttl_ms()
        if self.mesh is not None:
            from repro.distributed import collectives as coll

            direct, fo = coll.sharded_lookup_dual(
                self.mesh, state.direct, state.failover, keys, now_ms,
                cfg.cache_ttl_ms, fo_ttl, backend=cfg.backend)
        else:
            direct, fo = cache_lib.lookup_dual(
                state.direct, state.failover, keys, now_ms, cfg.cache_ttl_ms,
                fo_ttl, backend=cfg.backend)

        with jax.named_scope(trace.ADMIT):
            # (1a') bucket-range blackout: mask BEFORE touch/coalesce/admission
            # so a blacked-out row is a cold miss to every downstream stage.
            write_drop = None
            if chaos is not None:
                direct, write_drop = _chaos_blackout(direct, chaos)

            # (1b) record hit coordinates for the deferred last-access bump —
            # an O(B) ring scatter, never a cache-table write on this path.
            # Statically skipped when the config doesn't track recency.
            new_tb = state.touchbuf
            if cfg.resolved_touch():
                new_tb = wb_lib.touch_append(new_tb, direct, fo, now_ms)

            # (1c) in-batch coalescing (DESIGN.md §9): dedupe the missed keys
            # so admission and the tower operate on UNIQUE users — the first
            # occurrence of each distinct key is the group's representative,
            # duplicates reuse its embedding. Statically skipped (src_row
            # None, the bit-exact legacy path) when the config doesn't opt in.
            miss = ~direct.hit
            infer = src_row = None
            if cfg.coalesce_misses:
                rep, src_row = cache_lib.dedupe_first_groups(keys, miss)
                unit = rep                       # unit of inference demand
            else:
                unit = miss

            # (1d) admission control: refill the token bucket, grant this
            # step's tower inferences, defer the rest (statically skipped —
            # admit=None — when no budget is configured). The grant is capped
            # by the miss-budget compaction window too, and tokens are only
            # charged for inferences that actually RUN (failed attempts
            # included) — never for grants the window clips. With coalescing
            # on, demand / grants / charges are all per UNIQUE user; an
            # admitted user's duplicates ride along token-free.
            admit = fo_strict = None
            new_budget = state.budget
            if self._admission:
                fo_strict = fo.hit & (
                    fo.age_ms <= jnp.int32(cfg.failover_ttl_ms))
                demand = jnp.sum(unit.astype(jnp.int32))[None]       # (1,)
                refilled = rl_lib.refill(state.budget, self._budget_rates,
                                         self._budget_bursts)
                grant = rl_lib.grant_from(
                    refilled, self._budget_limited, demand,
                    blocked=None if chaos is None else chaos.outage)
                # batch-order rank of each inference unit: first grant[0] are
                # admitted, clipped to the tower's execution window
                u_i = unit.astype(jnp.int32)
                rank = jnp.cumsum(u_i) - u_i                     # exclusive
                infer = unit & (rank < jnp.minimum(
                    grant[0], jnp.int32(self.miss_budget)))
                spent = jnp.sum(infer.astype(jnp.int32))[None]
                new_budget = rl_lib.spend(refilled, self._budget_limited,
                                          spent)
                if cfg.coalesce_misses:
                    # covered rows: every duplicate of an admitted user
                    admit = miss & infer[jnp.maximum(src_row, 0)]
                else:
                    admit = infer
            elif cfg.coalesce_misses:
                infer = rep          # window clipping happens in the tail

            # (1e) bounded retry/backoff: re-attempt this step's failed
            # inferences from the remaining tokens; recovered rows serve
            # their computed embedding (failure bit cleared before the tail).
            n_retries = n_retry_succ = None
            if chaos is not None and chaos.retry_fail.shape[0] > 0:
                failure_mask, new_budget, n_retries, n_retry_succ = \
                    _chaos_retries(chaos, infer, failure_mask, new_budget,
                                   self._budget_limited)

        # (2)–(4): shared serve tail
        emb, source, age, new_wb, stats = _serve_tail(
            self.tower_fn, self.miss_budget, self.fallback_value, params,
            features, keys, now_ms, failure_mask, direct, fo,
            state.writebuf, admit=admit, fo_strict_hit=fo_strict,
            infer=infer, src_row=src_row, write_drop=write_drop)
        if chaos is not None:
            stats["retries"] = (jnp.int32(0) if n_retries is None
                                else n_retries)
            stats["retry_successes"] = (jnp.int32(0) if n_retry_succ is None
                                        else n_retry_succ)
        return ServeResult(
            embeddings=emb, source=source, age_ms=age,
            state=ServerState(direct=state.direct, failover=state.failover,
                              writebuf=new_wb, touchbuf=new_tb,
                              budget=new_budget),
            stats=stats)

    # ------------------------------------------------------------ serve_many
    def serve_many(self, params, state: ServerState, keys: Key64,
                   features, now_ms, failure_mask: Optional[jnp.ndarray] = None,
                   chaos=None, *, flush_every: int = 1, collect: bool = True):
        """Device-resident streaming driver (DESIGN.md §9): run S serve
        steps in ONE dispatch via ``lax.scan`` over a pre-staged (S, B)
        stream, flush folded in, counters accumulated on device.

        ``keys`` is an (S, B) Key64, ``features`` a pytree with leading
        (S, B) axes, ``now_ms`` (S,) the per-step clock, ``failure_mask``
        (S, B) bool (None → no failures). ``flush_every=F`` folds the
        async flush into the scan every F steps (``lax.cond``); 0 defers
        every write to the tail — deferred records beyond the write
        buffer's capacity drop oldest-first (the ring contract), so size
        the buffer for F (or S) steps of misses. A tail flush ALWAYS
        runs (a no-op on a drained buffer), so the returned state's
        buffers are empty; with ``flush_every=1`` (the launcher default)
        a stream split across serve_many dispatches is bit-identical to
        the unsplit run — at other cadences the tail flush lands where
        the dispatch boundary falls, exactly as a Python loop flushing
        at chunk ends would.

        Returns ``(state, counters, outputs)``: ``counters`` is the
        additive device-resident accumulator (fetch with ONE
        ``jax.device_get``; feed :meth:`ServingCounters.from_stats`) and
        ``outputs`` is ``(embeddings (S, B, D), source, age_ms)`` or None
        with ``collect=False`` (throughput drivers that never read the
        embeddings back skip materializing them).

        ``chaos`` is a compiled ``ft.chaos.ChaosSchedule`` with S-row
        fault streams (None = the pre-chaos scan, trace-identical); the
        accumulator then carries the degradation-ledger keys too.
        """
        now_ms = jnp.asarray(now_ms, jnp.int32)
        if failure_mask is None:
            failure_mask = jnp.zeros(keys.hi.shape, bool)

        def step(st, pay, now, fail, ch=None):
            k, f = pay
            return self.serve_step(params, st, k, f, now, fail, ch)

        return _serve_many_scan(
            step, self.flush, state, (keys, features), now_ms,
            failure_mask, _zero_acc(chaos=chaos is not None),
            flush_every=flush_every, collect=collect, chaos=chaos)

    # ----------------------------------------------------------------- flush
    def flush(self, state: ServerState, now_ms) -> ServerState:
        """Apply the async write buffer to the cache tier(s), bumping the
        recency planes from the touch buffer first. Runs off the serving
        critical path.

        ``CacheConfig.failover_write`` makes the tier choice EXPLICIT:
        ``"dual"`` (default) flushes BOTH caches with ONE shared insert
        plan (wb_lib.flush_dual — same embeddings, the failover simply
        keeps them valid longer, paper §4.4); ``"off"`` flushes the direct
        cache only (wb_lib.flush) and deliberately leaves the failover
        slab cold — a combination CacheConfig rejects when admission
        control needs the failover warm."""
        tb = state.touchbuf if self.cfg.resolved_touch() else None
        if self.cfg.failover_write == "off":
            direct, wb1, tb1 = wb_lib.flush(
                state.writebuf, state.direct, now_ms, self.cfg.cache_ttl_ms,
                evict_lru=self.cfg.eviction == "lru", touchbuf=tb,
                mesh=self.mesh)
            failover = state.failover
        else:
            direct, failover, wb1, tb1 = wb_lib.flush_dual(
                state.writebuf, state.direct, state.failover, now_ms,
                self.cfg.cache_ttl_ms, self.cfg.failover_ttl_ms,
                evict_lru=self.cfg.eviction == "lru", touchbuf=tb,
                mesh=self.mesh)
        return ServerState(direct=direct, failover=failover, writebuf=wb1,
                           touchbuf=state.touchbuf if tb1 is None else tb1,
                           budget=state.budget)

    # ------------------------------------------------------------------ jit
    # ServerState is DONATED: the caches pass through serve_step unchanged
    # and flush rewrites them in place, so donation lets XLA alias the
    # (potentially multi-GB) cache tables instead of copying them every
    # step. Callers must follow the move pattern ``state = res.state`` /
    # ``state = srv.jit_flush(state, now)`` and never touch the old value.
    @functools.cached_property
    def jit_serve_step(self):
        return jax.jit(self.serve_step, donate_argnums=(1,))

    @functools.cached_property
    def jit_serve_many(self):
        return jax.jit(self.serve_many, donate_argnums=(1,),
                       static_argnames=("flush_every", "collect"))

    @functools.cached_property
    def jit_flush(self):
        return jax.jit(self.flush, donate_argnums=(0,))


# ========================================================== multi-model tier
class MultiServerState(NamedTuple):
    direct: cache_lib.MultiCacheState     # stacked per-model direct tables
    failover: cache_lib.MultiCacheState   # stacked per-model failover tables
    writebuf: WriteBuffer                 # shared ring, records model-tagged
    touchbuf: TouchBuffer                 # shared ring of POOLED hit coords
    budget: rl_lib.InferBudget            # (M,) per-model inference tokens


def init_multi_server_state(cfgs: Sequence[CacheConfig], dtype=jnp.float32,
                            writebuf_capacity: int = 4096,
                            touchbuf_capacity: Optional[int] = None,
                            mesh=None) -> MultiServerState:
    """Allocate the stacked tier for an ordered model registry.

    Every model keeps its own direct/failover capacity (bucket masks);
    value_dim must agree across the tier and heterogeneous ``ways`` are
    normalized up to the tier maximum (extra associativity, never less).
    ``mesh`` bucket-shards both stacked tiers across its ``shard`` axis
    (DESIGN.md §11); every model's bucket counts must divide the shard
    count.
    """
    dims = {c.value_dim for c in cfgs}
    if len(dims) != 1:
        raise ValueError(f"tier needs one value_dim, got {sorted(dims)}")
    dim = dims.pop()
    ways_d = max(c.ways for c in cfgs)
    ways_f = max(c.resolved_failover_ways() for c in cfgs)
    if touchbuf_capacity is None:
        touchbuf_capacity = writebuf_capacity

    def build():
        return MultiServerState(
            direct=cache_lib.init_multi_cache(
                [c.n_buckets for c in cfgs], ways_d, dim, dtype),
            failover=cache_lib.init_multi_cache(
                [c.resolved_failover_n_buckets() for c in cfgs], ways_f, dim,
                dtype),
            writebuf=wb_lib.init_writebuf(writebuf_capacity, dim, dtype),
            touchbuf=wb_lib.init_touchbuf(touchbuf_capacity),
            budget=rl_lib.init_infer_budget(cfgs),
        )

    if mesh is None:
        return build()
    from repro.distributed import sharding as shard_lib

    return shard_lib.init_placed_server_state(build, mesh)


@dataclasses.dataclass(frozen=True)
class MultiModelServer:
    """One serving tier fronting the WHOLE model registry (DESIGN.md §5).

    The paper's headline shape: 30+ ranking models, each with customized
    cache settings, served by one cache deployment. A serve batch is a
    mixed stream of (model slot, user key) pairs; the direct+failover
    probe for ALL models is ONE dispatch (``lookup_dual_multi`` — the
    pallas backend launches ``cache_probe_dual_multi`` once, with
    per-model TTLs gathered in-kernel from the policy table), and the
    async flush applies per-model TTL and eviction policy through one
    shared insert plan.

    ``tower_fn(params, features) -> (B, D)`` stands in for the per-model
    user towers (one shared tower in this reproduction — the cache-tier
    semantics, not the tower zoo, are what's under test).
    """

    cfgs: Tuple[CacheConfig, ...]
    tower_fn: Callable
    miss_budget: int
    fallback_value: float = 0.0
    # "jnp" oracle | "pallas" fused kernel. None (default) resolves from
    # the configs — which must then agree, so a registry built with
    # backend="pallas" is never silently served on the jnp path.
    backend: Optional[str] = None
    # Bucket-sharded stacked tier (DESIGN.md §11); same contract as
    # CachedEmbeddingServer.mesh, sharding every model's bucket range.
    mesh: Optional[jax.sharding.Mesh] = None

    def __post_init__(self) -> None:
        if self.backend is None:
            backends = {c.backend for c in self.cfgs}
            if len(backends) != 1:
                raise ValueError(
                    f"configs disagree on backend {sorted(backends)}; pass "
                    "MultiModelServer(backend=...) explicitly")
            object.__setattr__(self, "backend", backends.pop())
        off = [c.model_id for c in self.cfgs if c.failover_write == "off"]
        if off:
            raise ValueError(
                f"models {off} set failover_write='off': the stacked tier's "
                "shared flush (flush_dual_multi) always writes both slabs — "
                "a per-model cold failover would be silently overwritten. "
                "Serve those models on a single-model server instead.")
        # Materialize the policy table EAGERLY: building it lazily inside
        # the first jit trace would cache trace-bound tracers (leak).
        object.__setattr__(self, "_policy",
                           cache_lib.policy_from_configs(self.cfgs))
        # Static python-level gate: skip touch plumbing entirely when no
        # model in the registry tracks access recency.
        object.__setattr__(self, "_any_touch",
                           any(c.resolved_touch() for c in self.cfgs))
        # Same static gate for in-batch coalescing (DESIGN.md §9): the
        # dedupe/broadcast plumbing only traces when some model opts in;
        # per-model opt-in is realized through the policy's coalesce mask.
        object.__setattr__(self, "_any_coalesce",
                           any(c.coalesce_misses for c in self.cfgs))
        # Admission control (DESIGN.md §8): static gate + eager budget
        # tables. When ANY model has a budget, the failover is probed at
        # the per-model RELAXED TTLs (strict for budget-less models, so
        # their behavior is unchanged) via a policy whose failover column
        # is swapped — _replace keeps the bucket-mask aliasing that
        # _pooled_bucket_pair's identity test relies on.
        any_budget = any(c.infer_budget_per_step is not None
                         for c in self.cfgs)
        object.__setattr__(self, "_any_admission", any_budget)
        # rates/limited come FROM the policy table (its budget columns are
        # built by ratelimit.budget_table) so there is exactly one
        # derivation of the admission tables.
        rates = self._policy.infer_budget
        limited = self._policy.budget_limited
        object.__setattr__(self, "_budget_rates", rates)
        object.__setattr__(self, "_budget_bursts",
                           rl_lib.bursts_of(rates, limited))
        object.__setattr__(self, "_budget_limited", limited)
        probe_policy = self._policy
        if any_budget:
            probe_policy = probe_policy._replace(
                failover_ttl_ms=probe_policy.failover_relax_ttl_ms)
        object.__setattr__(self, "_probe_policy", probe_policy)

    @property
    def policy(self) -> cache_lib.ModelPolicy:
        return self._policy

    @property
    def n_models(self) -> int:
        return len(self.cfgs)

    # ----------------------------------------------------------------- serve
    def serve_step(self, params, state: MultiServerState, slots,
                   keys: Key64, features, now_ms,
                   failure_mask: Optional[jnp.ndarray] = None,
                   chaos=None) -> ServeResult:
        """Serve a MIXED-model batch: ``slots`` (B,) int32 assigns each
        request its model. Steps mirror CachedEmbeddingServer.serve_step
        (the shared ``_serve_tail``); step (1) covers every model in the
        registry in one dispatch, and the stats gain per-model (M,)
        breakdowns. ``chaos`` is one fault-schedule row (same contract as
        the single-model server; ``outage`` is (M,), ``blackout_lo/hi``
        index POOLED buckets); requires admission control on some model."""
        B = keys.hi.shape[0]
        now_ms = jnp.int32(now_ms)
        slots = jnp.asarray(slots, jnp.int32)
        if failure_mask is None:
            failure_mask = jnp.zeros((B,), bool)
        if chaos is not None:
            if not self._any_admission:
                raise ValueError(
                    "chaos fault schedules require admission control: set "
                    "infer_budget_per_step on some model")
            failure_mask = failure_mask | chaos.fail

        # (1) direct + failover check, ALL models — ONE dispatch ----------
        # (the probe policy carries each model's RELAXED failover TTL when
        # any model runs admission control; strict == relaxed otherwise)
        if self.mesh is not None:
            from repro.distributed import collectives as coll

            direct, fo = coll.sharded_lookup_dual_multi(
                self.mesh, state.direct, state.failover, self._probe_policy,
                slots, keys, now_ms, backend=self.backend)
        else:
            direct, fo = cache_lib.lookup_dual_multi(
                state.direct, state.failover, self._probe_policy, slots,
                keys, now_ms, backend=self.backend)

        with jax.named_scope(trace.ADMIT):
            # (1a') pooled-bucket-range blackout, before every downstream stage
            write_drop = None
            if chaos is not None:
                direct, write_drop = _chaos_blackout(direct, chaos)

            # (1b) buffer hit coordinates (POOLED bucket indices) for deferred
            # last-access bumps, gated by each query's per-model touch policy.
            new_tb = state.touchbuf
            if self._any_touch:
                new_tb = wb_lib.touch_append(new_tb, direct, fo, now_ms,
                                             mask=self.policy.touch[slots])

            # (1c) in-batch coalescing (DESIGN.md §9): dedupe missed
            # (model, user) pairs — the dedupe is model-salted, so the same
            # user queried for two models stays two inferences — gated per
            # query by each model's coalesce policy. Misses of non-coalescing
            # models each stand alone (their own representative).
            miss = ~direct.hit
            infer = src_row = None
            if self._any_coalesce:
                co = self.policy.coalesce[slots]
                rep, src_co = cache_lib.dedupe_first_groups(keys, miss & co,
                                                            salt=slots)
                unit = rep | (miss & ~co)
                src_row = jnp.where(miss & ~co, jnp.arange(B, dtype=jnp.int32),
                                    src_co)
            else:
                unit = miss

            # (1d) admission control: ONE vectorized bucket update grants every
            # model's tower share; each model's inference units (unique users
            # when coalescing, miss rows otherwise) are admitted in batch
            # order up to its grant, the rest deferred to the degradation
            # chain. The total admission is additionally clipped to the
            # miss-budget execution window (batch order across models), and
            # each model's tokens are charged only for inferences that RUN —
            # duplicates of an admitted user ride along token-free.
            # Statically skipped when no model has a budget.
            admit = fo_strict = None
            new_budget = state.budget
            if self._any_admission:
                fo_strict = fo.hit & (fo.age_ms
                                      <= self.policy.failover_ttl_ms[slots])
                demand = (jnp.zeros((self.n_models,), jnp.int32)
                          .at[slots].add(unit.astype(jnp.int32)))
                refilled = rl_lib.refill(state.budget, self._budget_rates,
                                         self._budget_bursts)
                grant = rl_lib.grant_from(
                    refilled, self._budget_limited, demand,
                    blocked=None if chaos is None else chaos.outage)
                rank = _per_model_miss_rank(slots, unit, self.n_models)
                admit0 = unit & (rank < grant[slots])
                a_i = admit0.astype(jnp.int32)
                global_rank = jnp.cumsum(a_i) - a_i              # exclusive
                infer = admit0 & (global_rank < jnp.int32(self.miss_budget))
                spent = (jnp.zeros((self.n_models,), jnp.int32)
                         .at[slots].add(infer.astype(jnp.int32)))
                new_budget = rl_lib.spend(refilled, self._budget_limited,
                                          spent)
                if self._any_coalesce:
                    admit = miss & infer[jnp.maximum(src_row, 0)]
                else:
                    admit = infer
            elif self._any_coalesce:
                infer = unit         # window clipping happens in the tail

            # (1e) bounded retry/backoff from the remaining per-model tokens
            n_retries = n_retry_succ = None
            if chaos is not None and chaos.retry_fail.shape[0] > 0:
                failure_mask, new_budget, n_retries, n_retry_succ = \
                    _chaos_retries(chaos, infer, failure_mask, new_budget,
                                   self._budget_limited, slots=slots,
                                   n_models=self.n_models)

        # (2)–(4): shared serve tail, with model-tagged buffer records
        emb, source, age, new_wb, stats = _serve_tail(
            self.tower_fn, self.miss_budget, self.fallback_value, params,
            features, keys, now_ms, failure_mask, direct, fo,
            state.writebuf, model_slots=slots, n_models=self.n_models,
            admit=admit, fo_strict_hit=fo_strict, infer=infer,
            src_row=src_row, write_drop=write_drop)
        if chaos is not None:
            stats["retries"] = (jnp.int32(0) if n_retries is None
                                else n_retries)
            stats["retry_successes"] = (jnp.int32(0) if n_retry_succ is None
                                        else n_retry_succ)
        return ServeResult(
            embeddings=emb, source=source, age_ms=age,
            state=MultiServerState(direct=state.direct,
                                   failover=state.failover,
                                   writebuf=new_wb, touchbuf=new_tb,
                                   budget=new_budget),
            stats=stats)

    # ------------------------------------------------------------ serve_many
    def serve_many(self, params, state: MultiServerState, slots,
                   keys: Key64, features, now_ms,
                   failure_mask: Optional[jnp.ndarray] = None,
                   chaos=None, *, flush_every: int = 1, collect: bool = True):
        """The streaming scan driver for the multi-model tier: S
        mixed-model serve steps per dispatch. Same contract as
        :meth:`CachedEmbeddingServer.serve_many` with an extra (S, B)
        ``slots`` stream; the accumulated counters include the per-model
        (M,) breakdowns. ``chaos`` is a compiled S-row fault schedule
        (None = trace-identical to the pre-chaos scan)."""
        now_ms = jnp.asarray(now_ms, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        if failure_mask is None:
            failure_mask = jnp.zeros(keys.hi.shape, bool)

        def step(st, pay, now, fail, ch=None):
            sl, k, f = pay
            return self.serve_step(params, st, sl, k, f, now, fail, ch)

        return _serve_many_scan(
            step, self.flush, state, (slots, keys, features), now_ms,
            failure_mask, _zero_acc(self.n_models, chaos=chaos is not None),
            flush_every=flush_every, collect=collect, chaos=chaos)

    # ----------------------------------------------------------------- flush
    def flush(self, state: MultiServerState, now_ms) -> MultiServerState:
        """Apply the mixed-model write buffer to both stacked tiers with
        ONE shared insert plan; each record under its model's TTL and
        eviction policy, after the touch buffer's recency bumps. Off the
        serving critical path."""
        tb = state.touchbuf if self._any_touch else None
        direct, failover, wb1, tb1 = wb_lib.flush_dual_multi(
            state.writebuf, state.direct, state.failover, self.policy,
            now_ms, touchbuf=tb, mesh=self.mesh)
        return MultiServerState(direct=direct, failover=failover,
                                writebuf=wb1,
                                touchbuf=state.touchbuf if tb1 is None
                                else tb1,
                                budget=state.budget)

    # ------------------------------------------------------------------ jit
    # Same donation contract as CachedEmbeddingServer: MultiServerState is
    # donated, callers follow the move pattern and never reuse old state.
    @functools.cached_property
    def jit_serve_step(self):
        return jax.jit(self.serve_step, donate_argnums=(1,))

    @functools.cached_property
    def jit_serve_many(self):
        return jax.jit(self.serve_many, donate_argnums=(1,),
                       static_argnames=("flush_every", "collect"))

    @functools.cached_property
    def jit_flush(self):
        return jax.jit(self.flush, donate_argnums=(0,))


def cache_image(state):
    """The durable subset of a server state — what a warm-restart snapshot
    stores (ft/snapshot.py): both cache tables plus the admission token
    bucket. Works on :class:`ServerState` and :class:`MultiServerState`
    alike. The write/touch rings are deliberately NOT part of the image:
    the snapshot path drains them into the tables first (``flush``), so
    the image is a pure cache state with no half-applied async work. The
    tables go in their (buckets, ways, dim) view (``cache.TableImage``),
    the snapshot format."""
    return {"direct": cache_lib.table_image(state.direct),
            "failover": cache_lib.table_image(state.failover),
            "budget": state.budget}


def with_cache_image(state, image):
    """Graft a restored durable image onto a freshly initialized state of
    the SAME shape; the buffers keep their cold (empty) allocation — the
    snapshot drained them, so empty rings are the faithful restore."""
    return state._replace(direct=cache_lib.from_image(image["direct"]),
                          failover=cache_lib.from_image(image["failover"]),
                          budget=image["budget"])


def serve_step_no_cache(tower_fn: Callable, params, keys: Key64, features,
                        failure_mask: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The cache-disabled baseline (the paper's "w/o cache" arm): every
    request pays a tower inference; failures go straight to model fallback."""
    with jax.named_scope(trace.TOWER):
        emb = tower_fn(params, features)
    B = emb.shape[0]
    if failure_mask is None:
        failure_mask = jnp.zeros((B,), bool)
    emb = jnp.where(failure_mask[:, None], jnp.zeros_like(emb), emb)
    source = jnp.where(failure_mask, SRC_FALLBACK, SRC_COMPUTED)
    return emb, source.astype(jnp.int32)
