"""The serving probe kernels and the serve program compile for a TPU v5e.

The TPU compiler can compile for a chip that is described rather than
attached: these tests lower each serve-path kernel with
``interpret=False`` at sasrec's published value width (D=50, not a
multiple of 128), 8 ways, 2**20 buckets and a batch that is not a
multiple of the query tile — the dual probe also at the widest published
tower (D=256: wide-deep, mind; 2**18 buckets, so both tiers fit the
chip) — and check the program holds the Pallas kernel. Then they compile
both servers' ``serve_many`` and the preload insert at the benchmark
deployments' shapes and check that no value plane is relayouted: the
flush's scatter writes each in place. Nothing runs, so they say nothing
about results (the interpret-mode parity suites do) or times.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import cache as C
from repro.core import server as S
from repro.core.config import CacheConfig
from repro.core.hashing import Key64
from repro.kernels import cache_probe as pk

N_BUCKETS, WAYS, DIM, BATCH = 1 << 20, 8, 50, 1000


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip can be written to it but
    never read back."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tier(sharding, dim=DIM, n_buckets=N_BUCKETS):
    meta = _shape((n_buckets, WAYS), jnp.int32, sharding)
    return [meta, meta, meta,
            _shape((dim, n_buckets * WAYS), jnp.float32, sharding)]


def _queries(sharding, n):
    return [_shape((BATCH,), jnp.int32, sharding)] * n


def _assert_kernel_compiles(name, fn, *args):
    """The program holds the Pallas kernel, under its stable ``name`` (the
    instruction a profile's op event is named after)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    assert all(f"%{name}." in line.split("=")[0] for line in kernels)


def test_tiled_probe_compiles_for_v5e(one_chip):
    _assert_kernel_compiles(
        "ercache_probe_tiled",
        lambda khi, klo, ts, val, qhi, qlo, b: pk.cache_probe_tiled(
            khi, klo, ts, val, qhi, qlo, b, 1000, 500, interpret=False),
        *_tier(one_chip), *_queries(one_chip, 3))


def test_dual_probe_compiles_for_v5e(one_chip):
    _assert_kernel_compiles(
        "ercache_probe_dual",
        lambda *a: pk.cache_probe_dual(*a, 1000, 500, 900, interpret=False),
        *_tier(one_chip), *_tier(one_chip), *_queries(one_chip, 4))


def test_dual_probe_compiles_for_v5e_at_widest_tower(one_chip):
    _assert_kernel_compiles(
        "ercache_probe_dual",
        lambda *a: pk.cache_probe_dual(*a, 1000, 500, 900, interpret=False),
        *_tier(one_chip, 256, N_BUCKETS // 4),
        *_tier(one_chip, 256, N_BUCKETS // 4), *_queries(one_chip, 4))


def test_dual_multi_probe_compiles_for_v5e(one_chip):
    _assert_kernel_compiles(
        "ercache_probe_dual_multi",
        lambda *a: pk.cache_probe_dual_multi(*a, 1000, interpret=False),
        *_tier(one_chip), *_tier(one_chip), *_queries(one_chip, 5),
        _shape((8, 2), jnp.int32, one_chip))


# ---------------------------------------- no relayout of the value planes
STEPS, SERVE_BATCH, PRELOAD_BATCH = 16, 256, 1 << 13
MIN = 60_000
_RELAYOUT = re.compile(r"= \w+\[([\d,]+)\]\S* (copy|pad|slice|transpose)\(")


def _cfg(model_id, n_buckets, failover_n_buckets, eviction="ttl"):
    return CacheConfig(model_id=model_id, model_type="ctr",
                       n_buckets=n_buckets,
                       failover_n_buckets=failover_n_buckets, ways=WAYS,
                       value_dim=DIM, cache_ttl_ms=5 * MIN,
                       failover_ttl_ms=60 * MIN, eviction=eviction,
                       backend="pallas")


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _shape(x.shape, x.dtype, sharding), tree)


def _assert_planes_in_place(compiled, plane_elems):
    """No copy, pad, slice or transpose of a whole value plane anywhere in
    the program, and under 1 GB of temporaries: each table is written in
    place."""
    relayouts = [line.strip() for line in compiled.as_text().splitlines()
                 if (m := _RELAYOUT.search(line))
                 and math.prod(map(int, m.group(1).split(","))) >= plane_elems]
    assert not relayouts, relayouts[:4]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def _lower_serve_many(server, state, lead, sharding):
    row = _shape((STEPS, SERVE_BATCH), jnp.int32, sharding)
    keys = Key64(hi=row, lo=row)
    return server.jit_serve_many.lower(
        _shape((DIM, DIM), jnp.float32, sharding),
        _abstract(state, sharding), *lead, keys,
        _shape((STEPS, SERVE_BATCH, DIM), jnp.float32, sharding),
        _shape((STEPS,), jnp.int32, sharding),
        _shape((STEPS, SERVE_BATCH), jnp.bool_, sharding),
        flush_every=1, collect=True).compile()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The serve path's kernels compiled for the chip, not interpreted."""
    monkeypatch.setattr(pk, "resolve_interpret",
                        lambda interpret=None: bool(interpret))


def test_serve_many_writes_value_planes_in_place_for_v5e(one_chip,
                                                         compiled_kernels):
    """sasrec-ctr-first shapes: 2**20 buckets x 8 ways x D=50 per tier,
    16 steps of 256, a flush every step."""
    cfg = _cfg(14, N_BUCKETS, N_BUCKETS)
    server = S.CachedEmbeddingServer(cfg=cfg, tower_fn=lambda p, f: f @ p,
                                     miss_budget=SERVE_BATCH * 3 // 4)
    state = jax.eval_shape(lambda: S.init_server_state(
        cfg, writebuf_capacity=4 * SERVE_BATCH))
    _assert_planes_in_place(_lower_serve_many(server, state, (), one_chip),
                            N_BUCKETS * WAYS * DIM)


def test_preload_insert_writes_value_planes_in_place_for_v5e(one_chip):
    """The preload's jitted insert_dual of 8,192 records into both tiers,
    donated, as the benchmark's preload calls it."""
    cfg = _cfg(14, N_BUCKETS, N_BUCKETS)
    state = jax.eval_shape(lambda: S.init_server_state(cfg))

    def insert(direct, failover, hi, lo, vals, ts, now, live):
        return C.insert_dual(direct, failover, Key64(hi, lo), vals, now,
                             cfg.cache_ttl_ms, cfg.failover_ttl_ms,
                             write_mask=live, ts_ms=ts, evict_lru=False)

    rec = _shape((PRELOAD_BATCH,), jnp.int32, one_chip)
    compiled = jax.jit(insert, donate_argnums=(0, 1)).lower(
        _abstract(state.direct, one_chip),
        _abstract(state.failover, one_chip), rec, rec,
        _shape((PRELOAD_BATCH, DIM), jnp.float32, one_chip), rec,
        _shape((), jnp.int32, one_chip),
        _shape((PRELOAD_BATCH,), jnp.bool_, one_chip)).compile()
    _assert_planes_in_place(compiled, N_BUCKETS * WAYS * DIM)


def test_multi_serve_many_writes_value_planes_in_place_for_v5e(
        one_chip, compiled_kernels):
    """sasrec-registry8 shapes: eight models, 2**16 buckets per model and
    tier (2**17 for two models' direct tier), LRU for the last two."""
    nb = 1 << 16
    cfgs = tuple(_cfg(10 + m, nb * (2 if m < 2 else 1), nb,
                      "lru" if m >= 6 else "ttl") for m in range(8))
    server = S.MultiModelServer(cfgs=cfgs, tower_fn=lambda p, f: f @ p,
                                miss_budget=SERVE_BATCH * 3 // 4)
    state = jax.eval_shape(lambda: S.init_multi_server_state(
        cfgs, writebuf_capacity=4 * SERVE_BATCH))
    slots = _shape((STEPS, SERVE_BATCH), jnp.int32, one_chip)
    _assert_planes_in_place(
        _lower_serve_many(server, state, (slots,), one_chip),
        len(cfgs) * nb * WAYS * DIM)
