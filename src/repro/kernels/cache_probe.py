"""Fused ERCache bucket probes — the paper's cache *read* as TPU kernels.

Three kernels share one contract (``ref.cache_probe_ref`` /
``core.cache.lookup``): for each query key, load its set-associative bucket
metadata (keys, write timestamps), do the key-compare + TTL check, and
emit (hit, value, age, way) — the hit way (-1 on miss) is the coordinate
the serve path feeds the touch buffer for deferred last-access bumps — and
the cache table never leaves HBM except for the probed buckets
(DESIGN.md §4).

The kernels read the metadata only. They see each metadata table
**bucket-minor**, as ``(W, N)`` (``N`` buckets): the device layout XLA
gives the ``(N, W)`` tables on a TPU (bucket axis minor, so a narrow ``W``
is never padded to 128 lanes), and in it a query's bucket sits in one
``L = 128``-lane block that a DMA can address:

* each query's ``(W, L)`` metadata block lands in VMEM (key_hi / key_lo /
  write_ts: one (8, 128) int32 tile each at W=8);
* a per-query lane pick packs the bucket's column of every block into
  ``(W, TQ)`` tiles with queries on lanes, and the key-compare / TTL /
  first-valid-way select then runs ONCE, vectorized over the query tile.

The values are a flat ``(D, N * W)`` plane of entry columns (slot
``(bucket, way)`` is column ``bucket * W + way``; ``core.cache``), and the
wrappers gather each hit's column from it after the kernel, zeroing
misses. The kernel cannot read that plane itself: a ``D``-sublane block of
it (D=50 pads to 56) is not tile-aligned, so the chip's DMA refuses it.
The gather copies bits, so every value comes out bit-identical to the
oracle's whatever its bit pattern.

* ``cache_probe_tiled`` (exported as ``cache_probe``): one table.
* ``cache_probe_dual``: the direct AND failover tables for the same
  queries in a single kernel launch — one grid sweep, one shared
  start/drain loop pair for BOTH tables' DMAs — so ``serve_step`` does not
  pay two full-batch kernel dispatches.
* ``cache_probe_dual_multi``: the dual probe over a stacked multi-model
  tier, each query's TTLs taken from its model's row of the policy table.
* ``cache_probe_perquery``: the original one-query-per-grid-step kernel
  (``grid=(B,)``, blocks gathered via BlockSpec index_map).  Kept as the
  dispatch-overhead baseline for ``benchmarks/bench_kernel_probe.py`` —
  it is NOT on the serve path and keeps the legacy 3-output
  (hit, value, age) contract, no way coordinate. Its ``(1, W)`` row
  blocks are not tile-aligned, so the chip's compiler refuses it: it runs
  in the interpreter only.

``interpret`` resolves from the platform: compiled on a TPU backend, the
Pallas interpreter everywhere else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_Q = 128
LANES = 128

# Python-level launch counters (one increment per wrapper call, i.e. per
# kernel launch in eager mode / per trace under jit). Tests use these to
# assert serve_step issues exactly ONE probe launch for direct+failover —
# and, on the multi-model tier, ONE launch for the whole model registry.
LAUNCHES = {"tiled": 0, "dual": 0, "dual_multi": 0, "perquery": 0}

# Single-launch contract: entry wrapper -> LAUNCHES key. One source of
# truth shared by the static checker (erlint ER003 verifies each entry
# reaches exactly one pl.pallas_call) and the runtime contract tests
# (which assert the counter deltas). Keys of LAUNCHES and values here
# must stay in bijection.
LAUNCH_CONTRACT = {
    "cache_probe_tiled": "tiled",
    "cache_probe_dual": "dual",
    "cache_probe_dual_multi": "dual_multi",
    "cache_probe_perquery": "perquery",
}


def resolve_interpret(interpret=None) -> bool:
    """None → compile on a TPU backend, interpret everywhere else."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _pick_tile(batch: int, tile_q) -> int:
    if tile_q is not None:
        return int(tile_q)
    if batch >= DEFAULT_TILE_Q:
        return DEFAULT_TILE_Q
    # small batches: next power of two ≥ 8 to avoid padding 8 queries to 128
    return max(8, 1 << max(batch - 1, 1).bit_length())


def _lane_block(n_buckets: int, interpret: bool) -> int:
    """Lane width ``L`` of the bucket block a probe DMA lands. The chip
    addresses whole 128-lane blocks; the interpreter also runs the small
    tables of the tests, where ``L`` is the largest power of two ≤ 128
    that divides the bucket count."""
    lanes = math.gcd(n_buckets, LANES)
    if lanes != LANES and not interpret:
        raise ValueError(f"a compiled probe needs a multiple of {LANES} "
                         f"buckets per table, got {n_buckets}")
    return lanes


def _pick_lane(block, lane):
    """Column ``lane`` (a scalar) of an int32 (R, L) block, as (R, 1): any
    other lane is the int32 minimum, so the max returns the picked one."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    picked = jnp.where(lanes == lane, block, jnp.iinfo(jnp.int32).min)
    return jnp.max(picked, axis=1, keepdims=True)


def _gather_columns(tq: int, blocks_of, lane_of, accs):
    """``accs[k][:, j] = blocks_of(j)[k][:, lane_of(j)]`` for every query
    ``j`` of the tile: each query's bucket column, queries on lanes."""
    q_lane = jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)

    def step(j, accs):
        lane = lane_of(j)
        return tuple(jnp.where(q_lane == j, _pick_lane(b, lane), a)
                     for b, a in zip(blocks_of(j), accs))

    return jax.lax.fori_loop(0, tq, step, tuple(accs))


def _match_tile(now, ttl, qhi, qlo, khi, klo, ts):
    """Vectorized metadata probe over a (W, TQ) tile (queries on lanes).
    Returns (hit, age, way) rows (1, TQ) — the hit way (-1 on miss) is both
    the value column the wrapper gathers and the coordinate the serve path
    feeds the touch buffer."""
    match = (khi == qhi) & (klo == qlo)
    # TS_EMPTY lanes wrap negative here but never match a real key, so
    # `match` masks them out of `valid`/`age` below.
    fresh = (now - ts) <= ttl        # erlint: allow[ER004]
    valid = match & fresh
    n_ways = khi.shape[0]
    w_iota = jax.lax.broadcasted_iota(jnp.int32, valid.shape, 0)
    # the first valid way: a min over the way iota (n_ways when none is)
    way = jnp.min(jnp.where(valid, w_iota, n_ways), axis=0, keepdims=True)
    hit = way < n_ways
    first = valid & (w_iota == way)
    age = jnp.sum(jnp.where(first, now - ts, 0), axis=0,  # erlint: allow[ER004]
                  keepdims=True)
    return (hit.astype(jnp.int32), jnp.where(hit, age, jnp.int32(-1)),
            jnp.where(hit, way, jnp.int32(-1)))


def _start_then_drain(tq: int, dmas):
    """Start ALL tile DMAs, then drain: the copies overlap each other (and,
    on hardware, the previous tile's output write-back). ``dmas(j)`` must
    rebuild the same copies on both passes."""
    def on_each(op):
        def body(j, c):
            for d in dmas(j):
                op(d)
            return c
        return body

    jax.lax.fori_loop(0, tq, on_each(lambda d: d.start()), 0)
    jax.lax.fori_loop(0, tq, on_each(lambda d: d.wait()), 0)


def _probe_body(tq: int, now, ttls, bkts, qhi, qlo, tiers, outs, scratch,
                sems):
    """Metadata probe of ``len(tiers)`` tables for one query tile.

    ``tiers[i]`` are tier i's bucket-minor HBM refs (key_hi, key_lo,
    write_ts), ``bkts[i]`` its scalar-prefetched buckets, ``ttls[i]`` its
    TTL (a scalar, or a (1, TQ) row of per-query TTLs), ``outs[i]`` its
    (hit, age, way) output blocks and ``scratch[i]`` its (3, TQ, W, L)
    VMEM buffer. ONE start/drain loop pair lands every tier's blocks."""
    t = pl.program_id(0)

    def block_of(i, j):
        """(first lane of the block, lane within it) of query j, tier i."""
        lanes = scratch[i].shape[-1]
        b = bkts[i][t * tq + j]
        return pl.multiple_of(b - b % lanes, lanes), b % lanes

    def meta_dmas(j):
        dmas = []
        for i, (tabs, meta_s) in enumerate(zip(tiers, scratch)):
            start, _ = block_of(i, j)
            lanes = meta_s.shape[-1]
            dmas += [pltpu.make_async_copy(tab.at[:, pl.ds(start, lanes)],
                                           meta_s.at[k, j], sems.at[i, k])
                     for k, tab in enumerate(tabs)]
        return dmas

    _start_then_drain(tq, meta_dmas)

    for i, meta_s in enumerate(scratch):
        n_ways = meta_s.shape[2]
        khi, klo, ts = _gather_columns(
            tq, lambda j, m=meta_s: (m[0, j], m[1, j], m[2, j]),
            lambda j, i=i: block_of(i, j)[1],
            [jnp.zeros((n_ways, tq), jnp.int32)] * 3)
        hit, age, way = _match_tile(now, ttls[i], qhi, qlo, khi, klo, ts)
        hit_ref, age_ref, way_ref = outs[i]
        hit_ref[...] = hit
        age_ref[...] = age
        way_ref[...] = way


def _policy_ttls(policy_ref, slot_v):
    """Per-query direct/failover TTL rows from the scalar-prefetched (M, 2)
    policy table.

    SMEM holds scalars, so the gather is an unrolled select over the model
    axis: M scalar reads broadcast against the slot row (M is the registry
    size — tens, not thousands)."""
    M = policy_ref.shape[0]
    ttl_d = jnp.zeros(slot_v.shape, jnp.int32)
    ttl_f = jnp.zeros(slot_v.shape, jnp.int32)
    for m in range(M):
        sel = slot_v == m
        ttl_d = jnp.where(sel, policy_ref[m, 0], ttl_d)
        ttl_f = jnp.where(sel, policy_ref[m, 1], ttl_f)
    return ttl_d, ttl_f


def _make_probe_kernel(tq: int, n_tiers: int, multi: bool):
    """Kernel over refs laid out as: buckets per tier, [policy], scalars
    (scalar prefetch); q_hi, q_lo, [slots] rows; 3 bucket-minor metadata
    tables per tier; 3 outputs per tier; 1 scratch buffer per tier; DMA
    semaphores. ``scalars`` is [now, ttl per tier] — or [now] for the
    multi-model probe, whose TTLs come from the policy table."""
    def kernel(*refs):
        refs = list(refs)

        def pop(n):
            got = refs[:n]
            del refs[:n]
            return got

        bkts = pop(n_tiers)
        policy = pop(1)[0] if multi else None
        scalars, qhi, qlo = pop(3)
        slots = pop(1)[0] if multi else None
        tiers = [pop(3) for _ in range(n_tiers)]
        outs = [pop(3) for _ in range(n_tiers)]
        scratch = pop(n_tiers)
        sems, = pop(1)
        if multi:
            ttls = _policy_ttls(policy, slots[...])
        else:
            ttls = [scalars[1 + i] for i in range(n_tiers)]
        _probe_body(tq, scalars[0], ttls, bkts, qhi[...], qlo[...], tiers,
                    outs, scratch, sems)

    return kernel


@functools.partial(jax.jit, static_argnames=("name", "tile_q", "interpret"))
def _probe(tiers, q_hi, q_lo, buckets, scalars, slots=None, policy=None, *,
           name: str, tile_q: int, interpret: bool):
    """One ``pallas_call`` probing every tier in ``tiers`` — a tuple of
    (key_hi, key_lo, write_ts) tables — for the same queries. ``buckets``
    holds each tier's (B,) bucket indices. ``name`` is the kernel's stable
    name: the compiled program's instruction and a profile's op event
    carry it. Returns one (hit, age, way) tuple per tier."""
    B = q_hi.shape[0]
    tq = tile_q
    pad = (-B) % tq
    Bp = B + pad
    multi = policy is not None
    # padded queries probe bucket 0 (a valid DMA) of model 0
    rows = [jnp.pad(x, (0, pad)).reshape(1, Bp)
            for x in (q_hi, q_lo) + ((slots,) if multi else ())]
    buckets = [jnp.pad(b, (0, pad)) for b in buckets]
    prefetch = buckets + ([policy] if multi else []) + [scalars]

    row = pl.BlockSpec((1, tq), lambda t, *_: (0, t))
    in_specs = [row] * len(rows)
    out_specs, out_shape, scratch, views = [], [], [], []
    for tables in tiers:
        N, W = tables[0].shape
        # each (N, W) table as the kernel sees it: bucket-minor (W, N)
        views += [tab.T for tab in tables]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 3
        out_specs += [row] * 3
        out_shape += [jax.ShapeDtypeStruct((1, Bp), jnp.int32)] * 3
        scratch.append(pltpu.VMEM((3, tq, W, _lane_block(N, interpret)),
                                  jnp.int32))
    scratch.append(pltpu.SemaphoreType.DMA((len(tiers), 3)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(Bp // tq,),
        in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch)
    outs = pl.pallas_call(
        _make_probe_kernel(tq, len(tiers), multi),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*prefetch, *rows, *views)
    return tuple((hit[0, :B].astype(bool), age[0, :B], way[0, :B])
                 for hit, age, way in zip(*[iter(outs)] * 3))


def _hit_values(values, value_buckets, ways: int, probed):
    """The (hit, value, age, way) contract from a tier's (hit, age, way):
    each hit's column ``value_buckets * ways + way`` of the (D, E) value
    plane, zeros for a miss."""
    hit, age, way = probed
    cols = value_buckets * ways + jnp.maximum(way, 0)
    vals = jnp.take(values, cols, axis=1, mode="clip").T
    vals = jnp.where(hit[:, None], vals, jnp.zeros((), values.dtype))
    return hit, vals, age, way


def cache_probe_tiled(key_hi, key_lo, write_ts, values, q_hi, q_lo, buckets,
                      now_ms, ttl_ms, *, tile_q=None, interpret=None):
    """Tiled Pallas cache probe. Same contract as ref.cache_probe_ref.

    key_hi/key_lo/write_ts: (Nb, W) int32; values: the (D, Nb * W) value
    plane; q_hi/q_lo/buckets: (B,). Returns (hit (B,) bool, value (B, D),
    age (B,), way (B,) int32 — the hit way, -1 on miss).
    Batch sizes that are not a multiple of ``tile_q`` are padded internally.
    """
    LAUNCHES["tiled"] += 1
    probed, = _probe(
        ((key_hi, key_lo, write_ts),), q_hi, q_lo, (buckets,),
        jnp.asarray([now_ms, ttl_ms], jnp.int32), name="ercache_probe_tiled",
        tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))
    return _hit_values(values, buckets, key_hi.shape[1], probed)


# public name: the tiled kernel IS the cache probe
def cache_probe(key_hi, key_lo, write_ts, values, q_hi, q_lo, buckets,
                now_ms, ttl_ms, *, tile_q=None, interpret=None):
    """Alias of :func:`cache_probe_tiled` (the serving probe)."""
    return cache_probe_tiled(key_hi, key_lo, write_ts, values, q_hi, q_lo,
                             buckets, now_ms, ttl_ms, tile_q=tile_q,
                             interpret=interpret)


def cache_probe_dual(d_key_hi, d_key_lo, d_write_ts, d_values,
                     f_key_hi, f_key_lo, f_write_ts, f_values,
                     q_hi, q_lo, buckets_d, buckets_f,
                     now_ms, ttl_direct_ms, ttl_failover_ms,
                     *, tile_q=None, interpret=None):
    """Probe direct + failover tables for the same queries in ONE launch.

    Returns ((hit_d, value_d, age_d, way_d), (hit_f, value_f, age_f,
    way_f)) — each half bit-identical to :func:`cache_probe_tiled` on the
    respective table.
    """
    LAUNCHES["dual"] += 1
    probed_d, probed_f = _probe(
        ((d_key_hi, d_key_lo, d_write_ts), (f_key_hi, f_key_lo, f_write_ts)),
        q_hi, q_lo, (buckets_d, buckets_f),
        jnp.asarray([now_ms, ttl_direct_ms, ttl_failover_ms], jnp.int32),
        name="ercache_probe_dual", tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))
    return (_hit_values(d_values, buckets_d, d_key_hi.shape[1], probed_d),
            _hit_values(f_values, buckets_f, f_key_hi.shape[1], probed_f))


def cache_probe_dual_multi(d_key_hi, d_key_lo, d_write_ts, d_values,
                           f_key_hi, f_key_lo, f_write_ts, f_values,
                           q_hi, q_lo, slots, buckets_d, buckets_f,
                           policy, now_ms, *, tile_q=None, interpret=None):
    """Probe the pooled direct + failover tiers of a multi-model stack for a
    MIXED-model query batch in ONE launch.

    ``d_*``/``f_*`` are the pooled (M*Nb, W) metadata views of the
    stacked tables and their stacked value planes (models interleaved
    within a bucket, ``core.cache.MultiCacheState``), ``slots`` (B,)
    assigns each query its model, ``buckets_*`` already carry the slot
    offset (``core.cache.pooled_buckets``), and ``policy`` is the (M, 2)
    int32 [direct_ttl, failover_ttl] table — scalar-prefetched so each
    query's freshness check uses its own model's TTLs. Returns ((hit_d,
    value_d, age_d, way_d), (hit_f, value_f, age_f, way_f)), each half
    bit-identical to a per-model jnp-oracle loop.
    """
    from repro.core.cache import pooled_value_buckets

    LAUNCHES["dual_multi"] += 1
    probed_d, probed_f = _probe(
        ((d_key_hi, d_key_lo, d_write_ts), (f_key_hi, f_key_lo, f_write_ts)),
        q_hi, q_lo, (buckets_d, buckets_f),
        jnp.asarray([now_ms], jnp.int32), slots,
        jnp.asarray(policy, jnp.int32), name="ercache_probe_dual_multi",
        tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))
    M = policy.shape[0]
    return tuple(
        _hit_values(vals, pooled_value_buckets(b, M, khi.shape[0] // M),
                    khi.shape[1], probed)
        for vals, b, khi, probed in ((d_values, buckets_d, d_key_hi,
                                      probed_d),
                                     (f_values, buckets_f, f_key_hi,
                                      probed_f)))


# ----------------------------------------------- per-query (legacy baseline)
def _perquery_kernel(bucket_ref, scalars_ref,            # scalar prefetch
                     khi_ref, klo_ref, ts_ref, qhi_ref, qlo_ref,
                     hit_ref, age_ref, way_ref):
    now = scalars_ref[0]
    ttl = scalars_ref[1]
    khi = khi_ref[0]                       # (W,)
    klo = klo_ref[0]
    ts = ts_ref[0]
    match = (khi == qhi_ref[0]) & (klo == qlo_ref[0])
    # TS_EMPTY wrap is masked by `match` exactly as in _match_tile.
    fresh = (now - ts) <= ttl        # erlint: allow[ER004]
    valid = match & fresh
    hit = jnp.any(valid)
    first = valid & (jnp.cumsum(valid.astype(jnp.int32)) == 1)
    way = jnp.sum(jnp.where(first, jnp.arange(khi.shape[0]), 0))
    age = jnp.sum(jnp.where(first, now - ts, 0))  # erlint: allow[ER004]
    hit_ref[0] = hit.astype(jnp.int32)
    age_ref[0] = jnp.where(hit, age, jnp.int32(-1))
    way_ref[0] = jnp.where(hit, way, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cache_probe_perquery(key_hi, key_lo, write_ts, q_hi, q_lo,
                          buckets, now_ms, ttl_ms, *, interpret: bool):
    B = q_hi.shape[0]
    W = key_hi.shape[1]
    scalars = jnp.asarray([now_ms, ttl_ms], jnp.int32)
    meta = pl.BlockSpec((1, W), lambda i, b, s: (b[i], 0))
    one = pl.BlockSpec((1,), lambda i, b, s: (i,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[meta] * 3 + [one] * 2,
        out_specs=[one] * 3,
    )
    hit, age, way = pl.pallas_call(
        _perquery_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32)] * 3,
        interpret=interpret,
        name="ercache_probe_perquery",
    )(buckets, scalars, key_hi, key_lo, write_ts, q_hi, q_lo)
    return hit.astype(bool), age, way


def cache_probe_perquery(key_hi, key_lo, write_ts, values, q_hi, q_lo,
                         buckets, now_ms, ttl_ms, *, interpret=None):
    """One-query-per-grid-step probe (pre-tiling implementation). Same
    contract as ``cache_probe_tiled`` less the way; kept as the benchmark
    baseline."""
    LAUNCHES["perquery"] += 1
    probed = _cache_probe_perquery(key_hi, key_lo, write_ts, q_hi, q_lo,
                                   buckets, now_ms, ttl_ms,
                                   interpret=resolve_interpret(interpret))
    return _hit_values(values, buckets, key_hi.shape[1], probed)[:3]
