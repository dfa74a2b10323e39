"""Tiled / dual-probe Pallas kernels vs the jnp oracle, bit for bit.

Property-style sweeps (seeded numpy, no hypothesis dependency) asserting the
serve-path kernels agree EXACTLY with ``core.cache.lookup`` across hit /
miss / expired / empty-slot populations and non-multiple-of-tile batch
sizes, plus the serve_step single-dispatch and donation contracts.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C
from repro.core import server as S
from repro.core import writebuf as wb_lib
from repro.core.config import CacheConfig
from repro.core.hashing import Key64, bucket_index
from repro.kernels import cache_probe as pk

MIN = 60_000
DIM = 8


def keys_of(ids):
    return Key64.from_int(np.asarray(ids, np.int64))


def mixed_state(rng, n_buckets=64, ways=4, dim=DIM, n_fresh=40, n_stale=20):
    """A cache holding fresh entries (age<TTL), expired entries (age>TTL),
    and plenty of never-written slots. Returns (state, fresh_ids, stale_ids);
    probe at now_ms=2*MIN with ttl=MIN."""
    state = C.init_cache(n_buckets, ways, dim)
    fresh_ids = np.arange(n_fresh, dtype=np.int64) * 7
    stale_ids = (np.arange(n_stale, dtype=np.int64) + 1) * 13 + 10_000
    state = C.insert(state, keys_of(stale_ids),
                     jnp.asarray(rng.standard_normal((n_stale, dim)),
                                 jnp.float32), now_ms=0, ttl_ms=MIN)
    state = C.insert(state, keys_of(fresh_ids),
                     jnp.asarray(rng.standard_normal((n_fresh, dim)),
                                 jnp.float32), now_ms=3 * MIN // 2,
                     ttl_ms=MIN)
    return state, fresh_ids, stale_ids


def query_mix(rng, fresh_ids, stale_ids, batch):
    """Batch mixing hits, TTL-expired keys, and never-present keys."""
    pool = np.concatenate([fresh_ids, stale_ids,
                           np.arange(batch, dtype=np.int64) + 10 ** 6])
    return rng.choice(pool, size=batch, replace=True)


def assert_lookup_equal(got: C.LookupResult, want: C.LookupResult):
    np.testing.assert_array_equal(got.hit, want.hit)
    np.testing.assert_array_equal(got.values, want.values)  # copies: exact
    np.testing.assert_array_equal(got.age_ms, want.age_ms)
    # hit coordinates (the touch-buffer feed) must agree bit for bit too
    if got.bucket is not None and want.bucket is not None:
        np.testing.assert_array_equal(got.bucket, want.bucket)
        np.testing.assert_array_equal(got.way, want.way)
        np.testing.assert_array_equal(np.asarray(got.way) >= 0,
                                      np.asarray(got.hit))


# ------------------------------------------------------------- tiled kernel
@pytest.mark.parametrize("batch", [1, 7, 37, 64, 130])
def test_tiled_probe_matches_lookup_any_batch(batch, rng):
    """Bit-exact parity incl. batch sizes that are not tile multiples."""
    state, fresh_ids, stale_ids = mixed_state(rng)
    ids = query_mix(rng, fresh_ids, stale_ids, batch)
    k = keys_of(ids)
    want = C.lookup(state, k, now_ms=2 * MIN, ttl_ms=MIN)
    got = C.lookup(state, k, now_ms=2 * MIN, ttl_ms=MIN, backend="pallas")
    assert_lookup_equal(got, want)
    # the mix actually exercises every case at representative sizes
    if batch >= 64:
        assert bool(want.hit.any()) and not bool(want.hit.all())


def test_tiled_probe_keeps_every_value_bit_pattern(rng):
    """NaNs with payloads, -0.0 and infinities come back as the oracle's
    gather returns them, byte for byte."""
    state = C.init_cache(64, 4, DIM)
    ids = np.arange(48, dtype=np.int64) * 5
    bits = rng.integers(0, 1 << 32, size=(48, DIM), dtype=np.uint32)
    bits[:, 0] = 0x7FC00001              # quiet NaN with a payload
    bits[:, 1] = 0xFF800123              # negative NaN with a payload
    bits[:, 2] = 0x80000000              # -0.0
    bits[:, 3] = 0xFF800000              # -inf
    vals = jnp.asarray(bits.view(np.float32))
    state = C.insert(state, keys_of(ids), vals, now_ms=MIN, ttl_ms=MIN)
    k = keys_of(np.concatenate([ids, ids[:9] + 1]))
    want = C.lookup(state, k, now_ms=MIN, ttl_ms=MIN)
    got = C.lookup(state, k, now_ms=MIN, ttl_ms=MIN, backend="pallas")
    assert bool(want.hit.any())
    assert (np.asarray(got.values).tobytes()
            == np.asarray(want.values).tobytes())
    np.testing.assert_array_equal(got.hit, want.hit)
    np.testing.assert_array_equal(got.way, want.way)


@pytest.mark.parametrize("tile_q", [8, 16, 128])
def test_tiled_probe_tile_size_invariance(tile_q, rng):
    """Output must not depend on the tile size (incl. padding path)."""
    state, fresh_ids, stale_ids = mixed_state(rng)
    ids = query_mix(rng, fresh_ids, stale_ids, 53)
    k = keys_of(ids)
    b = bucket_index(k, state.n_buckets)
    want = C.lookup(state, k, now_ms=2 * MIN, ttl_ms=MIN)
    hit, vals, age, way = pk.cache_probe_tiled(
        state.key_hi, state.key_lo, state.write_ts, state.value_plane,
        k.hi, k.lo, b, 2 * MIN, MIN, tile_q=tile_q)
    assert_lookup_equal(C.LookupResult(hit, vals, age, bucket=b, way=way),
                        want)


def test_tiled_probe_empty_cache(rng):
    state = C.init_cache(16, 4, DIM)
    k = keys_of(np.arange(9))
    got = C.lookup(state, k, now_ms=0, ttl_ms=MIN, backend="pallas")
    assert not bool(got.hit.any())
    np.testing.assert_array_equal(got.values, 0.0)
    np.testing.assert_array_equal(got.age_ms, -1)


def test_tiled_matches_perquery_kernel(rng):
    """The tiled rewrite is a drop-in for the per-query original."""
    state, fresh_ids, stale_ids = mixed_state(rng)
    ids = query_mix(rng, fresh_ids, stale_ids, 48)
    k = keys_of(ids)
    b = bucket_index(k, state.n_buckets)
    args = (state.key_hi, state.key_lo, state.write_ts, state.value_plane,
            k.hi, k.lo, b, 2 * MIN, MIN)
    got = pk.cache_probe_tiled(*args)
    want = pk.cache_probe_perquery(*args)   # legacy 3-output contract
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


# -------------------------------------------------------------- dual kernel
@pytest.mark.parametrize("fo_buckets,fo_ways", [(64, 4), (128, 8), (32, 2)])
def test_dual_probe_matches_two_lookups(fo_buckets, fo_ways, rng):
    """One dual launch == two independent lookups, incl. differently-sized
    failover tables and a longer failover TTL."""
    direct, fresh_ids, stale_ids = mixed_state(rng)
    failover = C.init_cache(fo_buckets, fo_ways, DIM)
    # failover holds the stale ids too (written at t=0, long TTL keeps them)
    failover = C.insert(failover, keys_of(stale_ids),
                        jnp.asarray(rng.standard_normal((len(stale_ids),
                                                         DIM)), jnp.float32),
                        now_ms=0, ttl_ms=10 * MIN)
    ids = query_mix(rng, fresh_ids, stale_ids, 75)
    k = keys_of(ids)
    want_d, want_f = C.lookup_dual(direct, failover, k, 2 * MIN, MIN,
                                   10 * MIN, backend="jnp")
    got_d, got_f = C.lookup_dual(direct, failover, k, 2 * MIN, MIN,
                                 10 * MIN, backend="pallas")
    assert_lookup_equal(got_d, want_d)
    assert_lookup_equal(got_f, want_f)
    # the point of the failover tier: it recovers direct-expired keys
    assert bool((~want_d.hit & want_f.hit).any())


# ------------------------------------------------- insert plan / dual flush
def test_insert_dual_matches_independent_inserts(rng):
    """insert_dual == two sequential inserts per cache, for same and
    differently-sized failover tables."""
    for fo_buckets, fo_ways in [(64, 4), (16, 8)]:
        direct = C.init_cache(64, 4, DIM)
        failover = C.init_cache(fo_buckets, fo_ways, DIM)
        ids = rng.integers(0, 50, size=40)
        k = keys_of(ids)
        vals = jnp.asarray(rng.standard_normal((40, DIM)), jnp.float32)
        mask = jnp.asarray(rng.uniform(size=40) < 0.9)
        ts = jnp.asarray(rng.integers(0, MIN, 40), jnp.int32)
        want_d = C.insert(direct, k, vals, MIN, MIN, write_mask=mask,
                          ts_ms=ts)
        want_f = C.insert(failover, k, vals, MIN, 10 * MIN, write_mask=mask,
                          ts_ms=ts)
        got_d, got_f = C.insert_dual(direct, failover, k, vals, MIN, MIN,
                                     10 * MIN, write_mask=mask, ts_ms=ts)
        for got, want in [(got_d, want_d), (got_f, want_f)]:
            np.testing.assert_array_equal(got.key_hi, want.key_hi)
            np.testing.assert_array_equal(got.key_lo, want.key_lo)
            np.testing.assert_array_equal(got.write_ts, want.write_ts)
            np.testing.assert_array_equal(got.values, want.values)


def test_flush_dual_matches_two_flushes(rng):
    buf = wb_lib.init_writebuf(64, DIM)
    direct = C.init_cache(32, 4, DIM)
    failover = C.init_cache(64, 2, DIM)
    for t in (0, 1000, 2000):
        ids = rng.integers(0, 30, size=16)
        vals = jnp.asarray(rng.standard_normal((16, DIM)), jnp.float32)
        mask = jnp.asarray(rng.uniform(size=16) < 0.8)
        buf = wb_lib.append(buf, keys_of(ids), vals, t, mask=mask)
    want_d, _, _ = wb_lib.flush(buf, direct, 3000, MIN)
    want_f, _, _ = wb_lib.flush(buf, failover, 3000, 10 * MIN)
    got_d, got_f, buf2, _ = wb_lib.flush_dual(buf, direct, failover, 3000,
                                              MIN, 10 * MIN)
    assert int(buf2.count) == 0
    for got, want in [(got_d, want_d), (got_f, want_f)]:
        np.testing.assert_array_equal(got.key_hi, want.key_hi)
        np.testing.assert_array_equal(got.write_ts, want.write_ts)
        np.testing.assert_array_equal(got.values, want.values)


def test_property_insert_lookup_roundtrip_randomized(rng):
    """20 random insert/lookup rounds: pallas lookup stays bit-exact with
    the jnp oracle as the cache fills, expires, and evicts."""
    state = C.init_cache(32, 4, DIM)
    for step in range(20):
        ids = rng.integers(0, 200, size=int(rng.integers(1, 33)))
        t = int(step * MIN // 3)
        state = C.insert(state, keys_of(ids),
                         jnp.asarray(rng.standard_normal((len(ids), DIM)),
                                     jnp.float32), now_ms=t, ttl_ms=MIN)
        probe_ids = rng.integers(0, 250, size=29)
        k = keys_of(probe_ids)
        want = C.lookup(state, k, now_ms=t + 1000, ttl_ms=MIN)
        got = C.lookup(state, k, now_ms=t + 1000, ttl_ms=MIN,
                       backend="pallas")
        assert_lookup_equal(got, want)


# ------------------------------------------------------- serve integration
def tower(params, feats):
    return feats @ params


def make_server(backend, **cfg_kw):
    cfg = CacheConfig(model_id=1, model_type="ctr", n_buckets=64, ways=4,
                      value_dim=DIM, cache_ttl_ms=5 * MIN,
                      failover_ttl_ms=60 * MIN, backend=backend, **cfg_kw)
    srv = S.CachedEmbeddingServer(cfg=cfg, tower_fn=tower, miss_budget=8)
    return cfg, srv, S.init_server_state(cfg), jnp.eye(DIM)


def feats_of(ids):
    return jnp.asarray(np.asarray(ids)[:, None] * np.ones(DIM), jnp.float32)


def test_serve_step_backend_parity():
    """Full serve sequence (cold → flush → warm → expiry+failures) produces
    identical embeddings/provenance on jnp and pallas backends."""
    results = {}
    for backend in ("jnp", "pallas"):
        _, srv, state, params = make_server(backend)
        ids = np.arange(12)
        r1 = srv.serve_step(params, state, keys_of(ids), feats_of(ids), 0)
        state = srv.flush(r1.state, 0)
        r2 = srv.serve_step(params, state, keys_of(ids), feats_of(ids),
                            1000)
        t = 5 * MIN + 2000
        fail = jnp.ones((12,), bool)
        r3 = srv.serve_step(params, state, keys_of(ids), feats_of(ids), t,
                            failure_mask=fail)
        results[backend] = (r1, r2, r3)
    for a, b in zip(results["jnp"], results["pallas"]):
        np.testing.assert_array_equal(a.embeddings, b.embeddings)
        np.testing.assert_array_equal(a.source, b.source)
        np.testing.assert_array_equal(a.age_ms, b.age_ms)
        for key in a.stats:
            np.testing.assert_allclose(np.asarray(a.stats[key]),
                                       np.asarray(b.stats[key]))


def test_launch_contract_registry_parity():
    """LAUNCH_CONTRACT (the static source of truth erlint ER003 checks
    against) and the runtime LAUNCHES counters are in bijection, and each
    contract entry is a real callable that bumps exactly its own key."""
    assert sorted(pk.LAUNCH_CONTRACT.values()) == sorted(pk.LAUNCHES)
    assert len(set(pk.LAUNCH_CONTRACT.values())) == len(pk.LAUNCH_CONTRACT)
    for entry in pk.LAUNCH_CONTRACT:
        assert callable(getattr(pk, entry)), entry


def test_serve_step_single_probe_launch():
    """serve_step on the pallas backend issues EXACTLY ONE probe kernel
    launch covering direct + failover (the fused dual probe)."""
    _, srv, state, params = make_server("pallas")
    ids = np.arange(8)
    before = dict(pk.LAUNCHES)
    srv.serve_step(params, state, keys_of(ids), feats_of(ids), 0)
    assert pk.LAUNCHES["dual"] == before["dual"] + 1
    assert pk.LAUNCHES["tiled"] == before["tiled"]
    assert pk.LAUNCHES["perquery"] == before["perquery"]


def test_failover_sized_independently():
    """CacheConfig sizes the failover cache on its own (paper §4.4)."""
    cfg, srv, state, params = make_server("jnp", failover_n_buckets=16,
                                          failover_ways=2)
    assert state.direct.n_buckets == 64 and state.direct.ways == 4
    assert state.failover.n_buckets == 16 and state.failover.ways == 2
    # the differently-sized failover still recovers expired-direct keys
    ids = np.arange(6)
    r1 = srv.serve_step(params, state, keys_of(ids), feats_of(ids), 0)
    state = srv.flush(r1.state, 0)
    t = cfg.cache_ttl_ms + 1
    fail = jnp.ones((6,), bool)
    r2 = srv.serve_step(params, state, keys_of(ids), feats_of(ids), t,
                        failure_mask=fail)
    assert int(r2.stats["failover_hits"]) == 6
    np.testing.assert_allclose(r2.embeddings, feats_of(ids))


def test_jit_serve_step_donation_move_pattern():
    """jit_serve_step/jit_flush donate ServerState: the move pattern
    (state = res.state) keeps working across steps and the old state's
    buffers are actually released (donated) after the call."""
    _, srv, state, params = make_server("jnp")
    ids = np.arange(8)
    res = srv.jit_serve_step(params, state, keys_of(ids), feats_of(ids), 0)
    assert state.writebuf.count.is_deleted()          # donated
    state = srv.jit_flush(res.state, 0)
    res2 = srv.jit_serve_step(params, state, keys_of(ids), feats_of(ids),
                              1000)
    assert int(res2.stats["direct_hits"]) == 8
