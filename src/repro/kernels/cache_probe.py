"""Fused ERCache bucket probes — the paper's cache *read* as TPU kernels.

Three kernels share one contract (``ref.cache_probe_ref`` /
``core.cache.lookup``): for each query key, load its set-associative bucket
metadata (keys, write timestamps), do the key-compare + TTL check, and
emit (hit, value, age, way) — the hit way (-1 on miss) is the coordinate
the serve path feeds the touch buffer for deferred last-access bumps — and
the cache table never leaves HBM except for the probed buckets
(DESIGN.md §4).

The kernels see every table **bucket-minor**: metadata as ``(W, N)`` and
values as ``(D, W, N)`` (``N`` buckets). That is the device layout XLA
gives the ``(N, W)`` / ``(N, W, D)`` tables on a TPU (bucket axis minor,
so a narrow ``W`` or ``D`` is never padded to 128 lanes), and in it a
query's bucket sits in one ``L = 128``-lane block that a DMA can address:

* phase 1 lands the ``(W, L)`` metadata block holding each query's bucket
  (key_hi / key_lo / write_ts: one (8, 128) int32 tile each at W=8);
* a per-query lane pick packs the bucket's column of every block into
  ``(W, TQ)`` tiles with queries on lanes, and the key-compare / TTL /
  first-valid-way select then runs ONCE, vectorized over the query tile;
* the winning ways go VMEM→SMEM, and phase 2 lands each HIT query's
  ``(D, W, L)`` value block, a chunk of queries at a time (as many as
  ``VALUE_VMEM_BYTES`` holds), and picks the winning way's bucket column
  out of it, giving a ``(D, TQ)`` value tile (misses fetch nothing and
  come out zero). One way of a ``(W, L)`` tile is a single sublane, which
  the chip's DMA cannot address, so the block carries all ``W`` ways:
  a hit reads ``W * L`` times the value bytes it returns.

Every pick is a select plus a max over the values' bits as int32, so a
32-bit value comes out bit-identical to the oracle's gather whatever its
bit pattern; a narrower float goes through float32, exact for every
non-NaN value.

* ``cache_probe_tiled`` (exported as ``cache_probe``): one table.
* ``cache_probe_dual``: the direct AND failover tables for the same
  queries in a single kernel launch — one grid sweep, one shared
  start/drain loop pair per phase for BOTH tables' DMAs — so
  ``serve_step`` does not pay two full-batch kernel dispatches.
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
# VMEM for the phase-2 value blocks of all tiers: a chunk holds as many
# queries' (D, W, L) blocks as fit, so a wider D takes fewer per chunk
VALUE_VMEM_BYTES = 8 << 20

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


def _value_chunk(tq: int, tiers, interpret: bool) -> int:
    """Queries per phase-2 chunk: as many (D, W, L) value blocks of every
    tier as ``VALUE_VMEM_BYTES`` holds (budgeted at 4 bytes an element: a
    narrower dtype pads its sublanes), a divisor of ``tq``, at least 1."""
    per_query = sum(
        val.shape[1] * val.shape[2] * _lane_block(val.shape[0], interpret) * 4
        for *_, val in tiers)
    chunk = max(1, min(tq, VALUE_VMEM_BYTES // per_query))
    while tq % chunk:
        chunk -= 1
    return chunk


def _bucket_minor(key_hi, key_lo, write_ts, values):
    """One tier's tables as the kernels see them: (W, N) metadata and
    (D, W, N) values."""
    return key_hi.T, key_lo.T, write_ts.T, values.transpose(2, 1, 0)


def _to_bits(x):
    """int32 bits of float values: float32 as they are, a narrower float
    through float32."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _from_bits(bits, dtype):
    """Inverse of :func:`_to_bits`."""
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(dtype)


def _select_max(sel, bits, axes):
    """The one selected element of int32 ``bits`` along ``axes``: any other
    element is the int32 minimum, so the max returns the selected bits."""
    picked = jnp.where(sel, bits, jnp.iinfo(jnp.int32).min)
    *inner, last = sorted(axes, reverse=True)
    for axis in inner:
        picked = jnp.max(picked, axis=axis)
    return jnp.max(picked, axis=last, keepdims=True)


def _pick_lane(block, lane):
    """Column ``lane`` (a scalar) of an int32 (R, L) block, as (R, 1)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return _select_max(lanes == lane, block, (1,))


def _pick_way_lane(block, way, lane):
    """Bits of way ``way`` of bucket column ``lane`` of a (D, W, L) value
    block, as int32 (D, 1)."""
    ways = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, block.shape, 2)
    return _select_max((ways == way) & (lanes == lane), _to_bits(block),
                       (1, 2))


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
    the phase-2 value-fetch index and the coordinate the serve path feeds
    the touch buffer."""
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
    rebuild the same (copy, predicate) pairs on both passes; a copy with a
    predicate (a scalar bool, None for always) is started and waited for
    only where it holds."""
    def on_each(op):
        def body(j, c):
            for d, cond in dmas(j):
                if cond is None:
                    op(d)
                else:
                    pl.when(cond)(functools.partial(op, d))
            return c
        return body

    jax.lax.fori_loop(0, tq, on_each(lambda d: d.start()), 0)
    jax.lax.fori_loop(0, tq, on_each(lambda d: d.wait()), 0)


def _probe_body(tq: int, now, ttls, bkts, qhi, qlo, tiers, outs, scratch,
                sems):
    """Two-phase probe of ``len(tiers)`` tables for one query tile.

    ``tiers[i]`` are tier i's bucket-minor HBM refs (key_hi, key_lo,
    write_ts, values), ``bkts[i]`` its scalar-prefetched buckets,
    ``ttls[i]`` its TTL (a scalar, or a (1, TQ) row of per-query TTLs),
    ``outs[i]`` its (hit, value, age, way) output blocks and
    ``scratch[i]`` its (meta (3, TQ, W, L), values (CHUNK, D, W, L), way
    row (1, TQ) VMEM, way row (1, TQ) SMEM) buffers. ONE start/drain loop
    pair per phase (per chunk in phase 2) lands every tier's blocks."""
    t = pl.program_id(0)

    def block_of(i, j):
        """(first lane of the block, lane within it) of query j, tier i."""
        lanes = scratch[i][0].shape[-1]
        b = bkts[i][t * tq + j]
        return pl.multiple_of(b - b % lanes, lanes), b % lanes

    def meta_dmas(j):
        dmas = []
        for i, (tabs, (meta_s, *_)) in enumerate(zip(tiers, scratch)):
            start, _ = block_of(i, j)
            lanes = meta_s.shape[-1]
            dmas += [(pltpu.make_async_copy(tab.at[:, pl.ds(start, lanes)],
                                            meta_s.at[k, j], sems.at[i, k]),
                      None) for k, tab in enumerate(tabs[:3])]
        return dmas

    _start_then_drain(tq, meta_dmas)

    hits = []
    for i, (meta_s, _, way_v, way_s) in enumerate(scratch):
        n_ways = meta_s.shape[2]
        khi, klo, ts = _gather_columns(
            tq, lambda j, m=meta_s: (m[0, j], m[1, j], m[2, j]),
            lambda j, i=i: block_of(i, j)[1],
            [jnp.zeros((n_ways, tq), jnp.int32)] * 3)
        hit, age, way = _match_tile(now, ttls[i], qhi, qlo, khi, klo, ts)
        hit_ref, _, age_ref, way_ref = outs[i]
        hit_ref[...] = hit
        age_ref[...] = age
        way_ref[...] = way
        hits.append(hit)
        # phase 2 indexes its DMAs with the ways, so they go to SMEM
        way_v[...] = way
        copy = pltpu.make_async_copy(way_v, way_s, sems.at[i, 3])
        copy.start()
        copy.wait()

    # phase 2: each hit's whole (D, W, L) value block — the chip's DMA
    # cannot address one way (one sublane) of a (W, L) tile — a chunk of
    # queries at a time, then the hit way and bucket column are picked.
    # A tier's copies share one semaphore, so a miss neither starts nor
    # waits for one.
    chunk = scratch[0][1].shape[0]
    q_lane = jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)

    def pick_chunk(c, accs):
        def val_dmas(k):
            j = c * chunk + k
            dmas = []
            for i, (tabs, (_, val_s, _, way_s)) in enumerate(
                    zip(tiers, scratch)):
                start, _ = block_of(i, j)
                dmas.append((pltpu.make_async_copy(
                    tabs[3].at[:, :, pl.ds(start, val_s.shape[-1])],
                    val_s.at[k], sems.at[i, 3]), way_s[0, j] >= 0))
            return dmas

        def pick(k, accs):
            j = c * chunk + k
            return tuple(
                jnp.where(q_lane == j, _pick_way_lane(
                    val_s[k], way_s[0, j], block_of(i, j)[1]), acc)
                for i, (acc, (_, val_s, _, way_s))
                in enumerate(zip(accs, scratch)))

        _start_then_drain(chunk, val_dmas)
        return jax.lax.fori_loop(0, chunk, pick, accs)

    vals = jax.lax.fori_loop(
        0, tq // chunk, pick_chunk,
        tuple(jnp.zeros((val_s.shape[1], tq), jnp.int32)
              for _, val_s, _, _ in scratch))
    for i, v in enumerate(vals):
        out_ref = outs[i][1]
        # a miss fetched and picked nothing: zero it
        out_ref[...] = jnp.where(hits[i] == 1, _from_bits(v, out_ref.dtype),
                                 0)


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
    (scalar prefetch); q_hi, q_lo, [slots] rows; 4 bucket-minor tables
    per tier; 4 outputs per tier; 4 scratch buffers per tier; DMA
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
        tiers = [pop(4) for _ in range(n_tiers)]
        outs = [pop(4) for _ in range(n_tiers)]
        scratch = [pop(4) for _ in range(n_tiers)]
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
    (key_hi, key_lo, write_ts, values) tables — for the same queries.
    ``buckets`` holds each tier's (B,) bucket indices. ``name`` is the
    kernel's stable name: the compiled program's instruction and a
    profile's op event carry it. Returns one (hit, value, age, way) tuple
    per tier."""
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
    chunk = _value_chunk(tq, tiers, interpret)
    for tables in tiers:
        khi, _, _, val = tables
        N, W = khi.shape
        D = val.shape[-1]
        lanes = _lane_block(N, interpret)
        views += _bucket_minor(*tables)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 4
        out_specs += [row, pl.BlockSpec((D, tq), lambda t, *_: (0, t)),
                      row, row]
        out_shape += [jax.ShapeDtypeStruct((1, Bp), jnp.int32),
                      jax.ShapeDtypeStruct((D, Bp), val.dtype),
                      jax.ShapeDtypeStruct((1, Bp), jnp.int32),
                      jax.ShapeDtypeStruct((1, Bp), jnp.int32)]
        scratch += [pltpu.VMEM((3, tq, W, lanes), jnp.int32),
                    pltpu.VMEM((chunk, D, W, lanes), val.dtype),
                    pltpu.VMEM((1, tq), jnp.int32),
                    pltpu.SMEM((1, tq), jnp.int32)]
    scratch.append(pltpu.SemaphoreType.DMA((len(tiers), 4)))

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
    return tuple((hit[0, :B].astype(bool), val.T[:B], age[0, :B], way[0, :B])
                 for hit, val, age, way in zip(*[iter(outs)] * 4))


def cache_probe_tiled(key_hi, key_lo, write_ts, values, q_hi, q_lo, buckets,
                      now_ms, ttl_ms, *, tile_q=None, interpret=None):
    """Tiled Pallas cache probe. Same contract as ref.cache_probe_ref.

    key_hi/key_lo/write_ts: (Nb, W) int32; values: (Nb, W, D);
    q_hi/q_lo/buckets: (B,). Returns (hit (B,) bool, value (B, D),
    age (B,), way (B,) int32 — the hit way, -1 on miss).
    Batch sizes that are not a multiple of ``tile_q`` are padded internally.
    """
    LAUNCHES["tiled"] += 1
    out, = _probe(
        ((key_hi, key_lo, write_ts, values),), q_hi, q_lo, (buckets,),
        jnp.asarray([now_ms, ttl_ms], jnp.int32), name="ercache_probe_tiled",
        tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))
    return out


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
    return _probe(
        ((d_key_hi, d_key_lo, d_write_ts, d_values),
         (f_key_hi, f_key_lo, f_write_ts, f_values)),
        q_hi, q_lo, (buckets_d, buckets_f),
        jnp.asarray([now_ms, ttl_direct_ms, ttl_failover_ms], jnp.int32),
        name="ercache_probe_dual", tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))


def cache_probe_dual_multi(d_key_hi, d_key_lo, d_write_ts, d_values,
                           f_key_hi, f_key_lo, f_write_ts, f_values,
                           q_hi, q_lo, slots, buckets_d, buckets_f,
                           policy, now_ms, *, tile_q=None, interpret=None):
    """Probe the pooled direct + failover tiers of a multi-model stack for a
    MIXED-model query batch in ONE launch.

    ``d_*``/``f_*`` are the pooled (M*Nb, W[, D]) views of the stacked
    tables, ``slots`` (B,) assigns each query its model, ``buckets_*``
    already carry the slot offset (``core.cache.pooled_buckets``), and
    ``policy`` is the (M, 2) int32 [direct_ttl, failover_ttl] table —
    scalar-prefetched so each query's freshness check uses its own model's
    TTLs. Returns ((hit_d, value_d, age_d, way_d), (hit_f, value_f,
    age_f, way_f)), each half bit-identical to a per-model jnp-oracle loop.
    """
    LAUNCHES["dual_multi"] += 1
    return _probe(
        ((d_key_hi, d_key_lo, d_write_ts, d_values),
         (f_key_hi, f_key_lo, f_write_ts, f_values)),
        q_hi, q_lo, (buckets_d, buckets_f),
        jnp.asarray([now_ms], jnp.int32), slots,
        jnp.asarray(policy, jnp.int32), name="ercache_probe_dual_multi",
        tile_q=_pick_tile(q_hi.shape[0], tile_q),
        interpret=resolve_interpret(interpret))


# ----------------------------------------------- per-query (legacy baseline)
def _perquery_kernel(bucket_ref, scalars_ref,            # scalar prefetch
                     khi_ref, klo_ref, ts_ref, val_ref, qhi_ref, qlo_ref,
                     hit_ref, out_ref, age_ref):
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
    val = jnp.sum(jnp.where(first[:, None], val_ref[0], 0.0), axis=0)
    age = jnp.sum(jnp.where(first, now - ts, 0))  # erlint: allow[ER004]
    hit_ref[0] = hit.astype(jnp.int32)
    out_ref[0] = val.astype(out_ref.dtype)
    age_ref[0] = jnp.where(hit, age, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _cache_probe_perquery(key_hi, key_lo, write_ts, values, q_hi, q_lo,
                          buckets, now_ms, ttl_ms, *, interpret: bool):
    B = q_hi.shape[0]
    Nb, W = key_hi.shape
    D = values.shape[-1]
    scalars = jnp.asarray([now_ms, ttl_ms], jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, W), lambda i, b, s: (b[i], 0)),
            pl.BlockSpec((1, W), lambda i, b, s: (b[i], 0)),
            pl.BlockSpec((1, W), lambda i, b, s: (b[i], 0)),
            pl.BlockSpec((1, W, D), lambda i, b, s: (b[i], 0, 0)),
            pl.BlockSpec((1,), lambda i, b, s: (i,)),
            pl.BlockSpec((1,), lambda i, b, s: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((1,), lambda i, b, s: (i,)),
            pl.BlockSpec((1, D), lambda i, b, s: (i, 0)),
            pl.BlockSpec((1,), lambda i, b, s: (i,)),
        ],
    )
    hit, out, age = pl.pallas_call(
        _perquery_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, D), values.dtype),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
        name="ercache_probe_perquery",
    )(buckets, scalars, key_hi, key_lo, write_ts, values, q_hi, q_lo)
    return hit.astype(bool), out, age


def cache_probe_perquery(key_hi, key_lo, write_ts, values, q_hi, q_lo,
                         buckets, now_ms, ttl_ms, *, interpret=None):
    """One-query-per-grid-step probe (pre-tiling implementation). Same
    contract as ``cache_probe_tiled``; kept as the benchmark baseline."""
    LAUNCHES["perquery"] += 1
    return _cache_probe_perquery(key_hi, key_lo, write_ts, values, q_hi,
                                 q_lo, buckets, now_ms, ttl_ms,
                                 interpret=resolve_interpret(interpret))
