"""Pallas kernels vs ref.py oracles: shape/dtype sweeps + property tests.

All kernels run interpret=True on CPU (the kernel body executed by the
Pallas interpreter) — the same body that compiles for the TPU target.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.cache_probe import cache_probe
from repro.kernels.decode_attention import decode_attention
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention


# -------------------------------------------------------------- cache probe
@pytest.mark.parametrize("dim", [8, 64, 256])
@pytest.mark.parametrize("ways", [4, 8])
def test_cache_probe_sweep(dim, ways, rng):
    Nb, B = 32, 64
    key_hi = jnp.asarray(rng.integers(0, 30, (Nb, ways)), jnp.int32)
    key_lo = jnp.asarray(rng.integers(0, 30, (Nb, ways)), jnp.int32)
    ts = jnp.asarray(rng.integers(0, 1000, (Nb, ways)), jnp.int32)
    # the (D, Nb * W) value plane: slot (bucket, way) is one column
    vals = jnp.asarray(rng.standard_normal((dim, Nb * ways)), jnp.float32)
    buckets = jnp.asarray(rng.integers(0, Nb, (B,)), jnp.int32)
    way_pick = rng.integers(0, ways, B)
    q_hi = key_hi[buckets, way_pick]
    q_lo = key_lo[buckets, way_pick]
    q_hi = jnp.where(jnp.asarray(rng.uniform(size=B) < 0.4), 99, q_hi)
    got = cache_probe(key_hi, key_lo, ts, vals, q_hi, q_lo, buckets,
                      900, 500)
    want = ref.cache_probe_ref(key_hi, key_lo, ts, vals, q_hi, q_lo,
                               buckets, 900, 500)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    np.testing.assert_array_equal(got[2], want[2])


def test_cache_probe_matches_core_lookup(rng):
    """The kernel agrees with core.cache.lookup on a real CacheState."""
    from repro.core import cache as C
    from repro.core.hashing import Key64, bucket_index
    state = C.init_cache(64, 8, 16)
    ids = np.arange(40, dtype=np.int64) * 11
    k = Key64.from_int(ids)
    vals = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    state = C.insert(state, k, vals, now_ms=0, ttl_ms=60_000)
    probe_ids = np.concatenate([ids[:20], ids[:20] + 1])
    pk = Key64.from_int(probe_ids)
    want = C.lookup(state, pk, now_ms=1000, ttl_ms=60_000)
    got = cache_probe(state.key_hi, state.key_lo, state.write_ts,
                      state.value_plane, pk.hi, pk.lo,
                      bucket_index(pk, state.n_buckets), 1000, 60_000)
    np.testing.assert_array_equal(got[0], want.hit)
    np.testing.assert_allclose(got[1], want.values, atol=1e-6)
    np.testing.assert_array_equal(got[2], want.age_ms)


# ------------------------------------------------------------ embedding bag
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 1, 8), (16, 5, 32), (8, 12, 128)])
def test_embedding_bag_sweep(shape, dtype, rng):
    B, nnz, D = shape
    V = 64
    table = jnp.asarray(rng.standard_normal((V, D))).astype(dtype)
    ids = jnp.asarray(rng.integers(-1, V, (B, nnz)), jnp.int32)
    for mode in ("sum", "mean"):
        got = embedding_bag(table, ids, mode=mode)
        want = ref.embedding_bag_ref(table, ids, mode=mode)
        atol = 1e-5 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=atol)


def test_embedding_bag_all_padding(rng):
    table = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    ids = jnp.full((3, 5), -1, jnp.int32)
    np.testing.assert_allclose(embedding_bag(table, ids), 0.0)


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("gqa", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(gqa, causal, rng):
    Hq, Hkv = gqa
    B, S, hd = 2, 256, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_flash_attention_block_shape_invariance(rng):
    """Output must not depend on the BlockSpec tiling."""
    B, S, Hq, Hkv, hd = 1, 256, 2, 1, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    outs = [flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
            for bq, bk in [(32, 32), (64, 128), (256, 64), (128, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-5)


def test_flash_attention_bf16(rng):
    B, S, Hq, Hkv, hd = 1, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd))).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.1)


def test_flash_attention_matches_chunked_reference(rng):
    """Kernel vs the model layer's chunked-scan implementation."""
    from repro.models import layers as L
    B, S, Hq, Hkv, hd = 2, 512, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    got = flash_attention(q, k, v, causal=True)
    want = L.chunked_attention(q, k, v, causal=True, kv_chunk=128)
    np.testing.assert_allclose(got, want, atol=2e-5)


# --------------------------------------------------------- decode attention
@pytest.mark.parametrize("gqa", [(8, 2), (4, 1), (4, 4)])
def test_decode_attention_sweep(gqa, rng):
    Hq, Hkv = gqa
    B, S, hd = 4, 1024, 64
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    vl = jnp.asarray(rng.integers(1, S + 1, (B,)), jnp.int32)
    got = decode_attention(q, k, v, vl, bs=256)
    want = ref.decode_attention_ref(q, k, v, vl)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_decode_attention_matches_sharded_combine(rng):
    """Kernel == the shard_map psum-combine path's local reference."""
    from repro.distributed import collectives
    B, S, Hq, Hkv, hd = 2, 512, 8, 2, 32
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    vl = jnp.asarray([100, 512], jnp.int32)
    got = decode_attention(q, k, v, vl, bs=128)
    want = collectives.decode_attention_local(q, k, v, kv_valid_len=vl)
    np.testing.assert_allclose(got, want, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_property_decode_attention_valid_len(data):
    """Changing KV content beyond valid_len never changes the output."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
    B, S, Hq, Hkv, hd = 2, 256, 2, 1, 16
    vl_val = data.draw(st.integers(1, S - 1))
    q = jnp.asarray(rng.standard_normal((B, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), jnp.float32)
    vl = jnp.full((B,), vl_val, jnp.int32)
    o1 = decode_attention(q, k, v, vl, bs=64)
    k2 = k.at[:, vl_val:].set(99.0)
    v2 = v.at[:, vl_val:].set(-99.0)
    o2 = decode_attention(q, k2, v2, vl, bs=64)
    np.testing.assert_allclose(o1, o2, atol=1e-6)
