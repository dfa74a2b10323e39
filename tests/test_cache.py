"""ERCache core semantics: TTL lookup/insert/eviction (paper §3.2–3.3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import cache as C
from repro.core.hashing import Key64, bucket_index, hash_u32

MIN = 60_000


def keys_of(ids):
    return Key64.from_int(np.asarray(ids, np.int64))


def test_insert_then_lookup_hit():
    state = C.init_cache(n_buckets=64, ways=4, dim=8)
    k = keys_of([1, 2, 3])
    vals = jnp.arange(24, dtype=jnp.float32).reshape(3, 8)
    state = C.insert(state, k, vals, now_ms=1000, ttl_ms=MIN)
    res = C.lookup(state, k, now_ms=2000, ttl_ms=MIN)
    assert bool(res.hit.all())
    np.testing.assert_allclose(res.values, vals)
    np.testing.assert_array_equal(res.age_ms, [1000, 1000, 1000])


def test_ttl_expiry_boundary():
    state = C.init_cache(16, 4, 4)
    k = keys_of([42])
    state = C.insert(state, k, jnp.ones((1, 4)), now_ms=0, ttl_ms=MIN)
    # exactly at TTL: still valid (<=)
    assert bool(C.lookup(state, k, now_ms=MIN, ttl_ms=MIN).hit[0])
    assert not bool(C.lookup(state, k, now_ms=MIN + 1, ttl_ms=MIN).hit[0])


def test_miss_returns_zeros():
    state = C.init_cache(16, 4, 4)
    res = C.lookup(state, keys_of([7]), now_ms=0, ttl_ms=MIN)
    assert not bool(res.hit[0])
    np.testing.assert_allclose(res.values, 0.0)
    assert int(res.age_ms[0]) == -1


def test_overwrite_same_key_updates_value_and_ts():
    state = C.init_cache(16, 2, 4)
    k = keys_of([5])
    state = C.insert(state, k, jnp.full((1, 4), 1.0), now_ms=0, ttl_ms=MIN)
    state = C.insert(state, k, jnp.full((1, 4), 2.0), now_ms=500, ttl_ms=MIN)
    res = C.lookup(state, k, now_ms=600, ttl_ms=MIN)
    np.testing.assert_allclose(res.values, 2.0)
    assert int(res.age_ms[0]) == 100
    # only one way occupied (match > empty priority)
    assert float(state.occupancy()) * state.capacity == 1.0


def test_eviction_priority_expired_before_oldest():
    """Within a full bucket: expired slots are evicted before live-oldest."""
    state = C.init_cache(1, 2, 2)       # one bucket, two ways
    a, b, c = keys_of([1]), keys_of([2]), keys_of([3])
    one = jnp.ones((1, 2))
    state = C.insert(state, a, one * 1, now_ms=0, ttl_ms=MIN)
    state = C.insert(state, b, one * 2, now_ms=30_000, ttl_ms=MIN)
    # at t=70_000: a (age 70s) is expired (ttl 60s), b is live
    state = C.insert(state, c, one * 3, now_ms=70_000, ttl_ms=MIN)
    assert not bool(C.lookup(state, a, 70_000, MIN).hit[0])     # evicted
    assert bool(C.lookup(state, b, 70_000, MIN).hit[0])         # kept
    assert bool(C.lookup(state, c, 70_000, MIN).hit[0])


def test_eviction_oldest_when_all_live():
    state = C.init_cache(1, 2, 2)
    a, b, c = keys_of([1]), keys_of([2]), keys_of([3])
    one = jnp.ones((1, 2))
    state = C.insert(state, a, one, now_ms=0, ttl_ms=10 * MIN)
    state = C.insert(state, b, one, now_ms=1000, ttl_ms=10 * MIN)
    state = C.insert(state, c, one, now_ms=2000, ttl_ms=10 * MIN)
    assert not bool(C.lookup(state, a, 2000, 10 * MIN).hit[0])  # oldest out
    assert bool(C.lookup(state, b, 2000, 10 * MIN).hit[0])
    assert bool(C.lookup(state, c, 2000, 10 * MIN).hit[0])


def test_duplicate_keys_in_batch_last_writer_wins():
    state = C.init_cache(16, 4, 2)
    k = keys_of([9, 9, 9])
    vals = jnp.asarray([[1., 1.], [2., 2.], [3., 3.]])
    state = C.insert(state, k, vals, now_ms=0, ttl_ms=MIN)
    res = C.lookup(state, keys_of([9]), now_ms=0, ttl_ms=MIN)
    np.testing.assert_allclose(res.values[0], [3., 3.])
    assert float(state.occupancy()) * state.capacity == 1.0


def test_write_mask_skips_rows():
    state = C.init_cache(16, 4, 2)
    k = keys_of([1, 2])
    state = C.insert(state, k, jnp.ones((2, 2)), now_ms=0, ttl_ms=MIN,
                     write_mask=jnp.asarray([True, False]))
    assert bool(C.lookup(state, keys_of([1]), 0, MIN).hit[0])
    assert not bool(C.lookup(state, keys_of([2]), 0, MIN).hit[0])


def test_backdated_ts_ages_from_compute_time():
    state = C.init_cache(16, 4, 2)
    k = keys_of([1])
    state = C.insert(state, k, jnp.ones((1, 2)), now_ms=50_000, ttl_ms=MIN,
                     ts_ms=jnp.asarray([10_000], jnp.int32))
    res = C.lookup(state, k, now_ms=60_000, ttl_ms=MIN)
    assert bool(res.hit[0]) and int(res.age_ms[0]) == 50_000
    assert not bool(C.lookup(state, k, now_ms=70_001, ttl_ms=MIN).hit[0])


def test_hash_determinism_and_spread():
    ids = np.arange(10_000, dtype=np.int64) * 7919
    k = keys_of(ids)
    h1 = hash_u32(k)
    h2 = hash_u32(k)
    np.testing.assert_array_equal(h1, h2)
    buckets = bucket_index(k, 256)
    counts = np.bincount(np.asarray(buckets), minlength=256)
    # roughly uniform: no bucket > 3x the mean
    assert counts.max() < 3 * counts.mean()


@settings(max_examples=25, deadline=None)
@given(ids=st.lists(st.integers(0, 2**62), min_size=1, max_size=32),
       ttl_s=st.integers(1, 3600))
def test_property_insert_lookup_roundtrip(ids, ttl_s):
    """Anything inserted is immediately readable within TTL, with the value
    of the LAST write for duplicate ids."""
    state = C.init_cache(64, 8, 4)
    k = keys_of(ids)
    vals = jnp.arange(len(ids) * 4, dtype=jnp.float32).reshape(-1, 4)
    state = C.insert(state, k, vals, now_ms=0, ttl_ms=ttl_s * 1000)
    res = C.lookup(state, k, now_ms=ttl_s * 1000, ttl_ms=ttl_s * 1000)
    assert bool(res.hit.all())
    last = {i: vals[j] for j, i in enumerate(ids)}
    for j, i in enumerate(ids):
        np.testing.assert_allclose(res.values[j], last[i])


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_property_capacity_never_exceeded(data):
    """Occupied slot count ≤ min(#distinct keys, capacity) after any
    sequence of inserts."""
    state = C.init_cache(4, 2, 2)
    seen = set()
    for _ in range(data.draw(st.integers(1, 6))):
        ids = data.draw(st.lists(st.integers(0, 40), min_size=1,
                                 max_size=16))
        seen.update(ids)
        t = data.draw(st.integers(0, 10_000))
        state = C.insert(state, keys_of(ids),
                         jnp.ones((len(ids), 2)), now_ms=t, ttl_ms=MIN)
        occupied = int(float(state.occupancy()) * state.capacity + 0.5)
        assert occupied <= min(len(seen), state.capacity)


# ------------------------------------------------ the flat value plane
def _bits(rng, shape):
    """float32 values of arbitrary bit patterns (NaN payloads, -0.0, inf)."""
    return rng.integers(0, 1 << 32, size=shape,
                        dtype=np.uint32).view(np.float32)


@pytest.mark.parametrize("fo_buckets,fo_ways", [(8, 2), (4, 4)])
def test_insert_dual_value_plane_matches_numpy_replay(fo_buckets, fo_ways):
    """A sequence of insert_dual batches with duplicate keys, crowded
    buckets (write-drop losers) and overwrites: both tiers' value planes
    equal a numpy replay of each batch's plan through the (N, W, D) view,
    bit for bit, and the plane is that view's flat entry columns."""
    rng = np.random.default_rng(7)
    dim = 5
    direct, failover = C.init_cache(8, 2, dim), C.init_cache(fo_buckets,
                                                              fo_ways, dim)
    want = {"d": np.zeros((8, 2, dim), np.float32),
            "f": np.zeros((fo_buckets, fo_ways, dim), np.float32)}
    dropped = overwritten = 0
    for t in range(6):
        ids = rng.integers(0, 24, size=20)          # duplicates in-batch
        k = keys_of(ids)
        vals = _bits(rng, (20, dim))
        mask = jnp.asarray(rng.uniform(size=20) < 0.85)
        now = t * 1000
        plans = {"d": C.plan_insert(direct, k, now, MIN, mask),
                 "f": C.plan_insert(failover, k, now, 10 * MIN, mask)}
        direct, failover = C.insert_dual(direct, failover, k,
                                         jnp.asarray(vals), now, MIN,
                                         10 * MIN, write_mask=mask)
        for tier, (winner, bucket, way) in plans.items():
            winner, bucket, way = map(np.asarray, (winner, bucket, way))
            dropped += int((np.asarray(mask) & ~winner).sum())
            for i in np.nonzero(winner)[0]:
                overwritten += bool(want[tier][bucket[i], way[i]].any())
                want[tier][bucket[i], way[i]] = vals[i]
    assert dropped and overwritten
    for tier, got in (("d", direct), ("f", failover)):
        n, w, _ = want[tier].shape
        assert got.value_plane.shape == (dim, n * w)
        assert (np.asarray(got.values).tobytes()
                == want[tier].tobytes())
        assert (np.asarray(got.value_plane).tobytes()
                == np.ascontiguousarray(
                    want[tier].reshape(n * w, dim).T).tobytes())


@pytest.mark.parametrize("multi", [False, True], ids=["single", "stacked"])
def test_table_image_round_trip(multi):
    """table_image / from_image: the (buckets, ways, dim) view the
    snapshots store converts back to the same value plane, bit for bit;
    a stacked tier's models interleave within each bucket."""
    rng = np.random.default_rng(3)
    state = (C.init_multi_cache([8, 4, 8], 2, 3) if multi
             else C.init_cache(8, 2, 3))
    state = state._replace(value_plane=jnp.asarray(
        _bits(rng, state.value_plane.shape)))
    image = C.table_image(state)
    back = C.from_image(image)
    assert type(back) is type(state)
    assert (np.asarray(back.value_plane).tobytes()
            == np.asarray(state.value_plane).tobytes())
    values = np.asarray(image.values)
    plane = np.asarray(state.value_plane)
    if multi:
        M, nb, w, _ = values.shape
        for m in range(M):
            for b in range(nb):
                np.testing.assert_array_equal(
                    values[m, b].view(np.uint32),
                    plane[:, (b * M + m) * w:(b * M + m + 1) * w]
                    .T.view(np.uint32))
            view = state.model_view(m)
            assert (np.asarray(view.values).tobytes()
                    == values[m].tobytes())
    else:
        np.testing.assert_array_equal(
            values.reshape(-1, 3).view(np.uint32), plane.T.view(np.uint32))
