"""The serving probe kernels compile for a TPU v5e chip.

The TPU compiler can compile for a chip that is described rather than
attached: these tests lower each serve-path kernel with
``interpret=False`` at sasrec's published value width (D=50, not a
multiple of 128), 8 ways, 2**20 buckets and a batch that is not a
multiple of the query tile — the dual probe also at the widest published
tower (D=256: wide-deep, mind; 2**18 buckets, so both tiers fit the
chip), where the phase-2 value blocks fill the most VMEM — and check the
program holds the Pallas kernel. Nothing runs, so they say nothing about
results (the interpret-mode parity suites do) or times.
"""
import os

import jax
import jax.numpy as jnp
import pytest

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
            _shape((n_buckets, WAYS, dim), jnp.float32, sharding)]


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
