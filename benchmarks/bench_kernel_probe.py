"""Probe-kernel shootout: tiled vs per-query vs jnp reference.

The serve path's acceptance gate (ISSUE 1): at B=4096 on the default
backend the tiled Pallas probe must be ≥ 2× faster than the original
one-query-per-grid-step kernel, with bit-exact parity against
``core.cache.lookup``. Also times the dual probe (direct + failover in one
launch) against two tiled launches — the dispatch saving ``serve_step``
banks every batch.

Returns a metrics dict merged into ``BENCH_serve.json`` by run.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import cache as C
from repro.core.hashing import Key64, bucket_index
from repro.kernels import cache_probe as pk

N_BUCKETS = 1 << 12
WAYS = 8
DIM = 64
TTL_MS = 60_000


def _populated_state(rng, n_keys):
    state = C.init_cache(N_BUCKETS, WAYS, DIM)
    ids = np.arange(n_keys, dtype=np.int64) * 7919
    keys = Key64.from_int(ids)
    vals = jnp.asarray(rng.standard_normal((n_keys, DIM)), jnp.float32)
    return C.insert(state, keys, vals, now_ms=0, ttl_ms=TTL_MS), ids


def run(report):
    quick = getattr(common, "QUICK", False)
    B = 512 if quick else 4096
    rng = np.random.default_rng(0)
    state, ids = _populated_state(rng, n_keys=B)
    failover, _ = _populated_state(rng, n_keys=B // 2)

    # ~60% hits, rest misses/expired-adjacent — a serving-like mix
    probe_ids = np.where(rng.uniform(size=B) < 0.6,
                         rng.choice(ids, size=B),
                         rng.integers(10 ** 9, 2 * 10 ** 9, size=B))
    k = Key64.from_int(probe_ids)
    buckets = bucket_index(k, N_BUCKETS)
    buckets_f = bucket_index(k, failover.n_buckets)
    args = (state.key_hi, state.key_lo, state.write_ts, state.value_plane,
            k.hi, k.lo, buckets, 1000, TTL_MS)

    # parity gate first: tiled == core.cache.lookup, bit for bit
    want = C.lookup(state, k, 1000, TTL_MS)
    hit, vals, age, way = pk.cache_probe_tiled(*args)
    np.testing.assert_array_equal(hit, want.hit)
    np.testing.assert_array_equal(vals, want.values)
    np.testing.assert_array_equal(age, want.age_ms)
    np.testing.assert_array_equal(way, want.way)

    lookup_jit = jax.jit(lambda s, kk: C.lookup(s, kk, 1000, TTL_MS))
    us_ref = common.time_us(lookup_jit, state, k)
    us_tiled = common.time_us(pk.cache_probe_tiled, *args)
    # the per-query kernel pays B grid steps — seconds per call at B=4096
    # in interpret mode, so keep its sample count small
    us_perq = common.time_us(pk.cache_probe_perquery, *args,
                             warmup=1, iters=3 if not quick else 2)
    us_dual = common.time_us(
        pk.cache_probe_dual, state.key_hi, state.key_lo, state.write_ts,
        state.value_plane, failover.key_hi, failover.key_lo, failover.write_ts,
        failover.value_plane, k.hi, k.lo, buckets, buckets_f, 1000, TTL_MS,
        10 * TTL_MS)

    speedup = us_perq / us_tiled
    qps = lambda us: B / (us * 1e-6)
    report.add(f"probe_jnp_ref_B{B}", us_ref, f"{qps(us_ref):.0f}_qps")
    report.add(f"probe_tiled_B{B}", us_tiled,
               f"{qps(us_tiled):.0f}_qps;parity=exact")
    report.add(f"probe_perquery_B{B}", us_perq,
               f"tiled_speedup={speedup:.1f}x")
    report.add(f"probe_dual_B{B}", us_dual,
               f"vs_2x_tiled={2 * us_tiled / us_dual:.2f}x")
    return {
        "batch": B,
        "n_buckets": N_BUCKETS, "ways": WAYS, "dim": DIM,
        "probe_us": {"jnp_ref": us_ref, "tiled": us_tiled,
                     "perquery": us_perq, "dual": us_dual},
        "probe_qps": {"jnp_ref": qps(us_ref), "tiled": qps(us_tiled),
                      "perquery": qps(us_perq), "dual": qps(us_dual)},
        "tiled_vs_perquery_speedup": speedup,
        "dual_vs_two_tiled_speedup": 2 * us_tiled / us_dual,
        "tiled_parity_with_lookup": "exact",
    }
