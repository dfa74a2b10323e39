"""The serve program names its layers (``repro.core.trace``) in the
compiled program's op metadata, and the names change no computation: with
the metadata stripped, the program compiled with the scopes is the one
compiled with ``jax.named_scope`` replaced by a null context. Both servers,
on the jnp backend and on the pallas backend (interpret mode here)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import server as S
from repro.core import trace
from repro.core.config import CacheConfig
from repro.core.hashing import Key64

DIM, B, STEPS, MIN = 8, 16, 2, 60_000
# every layer, and each flush sub-scope under the folded flush
SCOPES = trace.LAYERS + tuple(f"{trace.FLUSH}/{p}/"
                              for p in trace.FLUSH_PARTS)


def _tower(params, feats):
    return feats @ params


def _cfg(model_id, backend, eviction="lru"):
    return CacheConfig(model_id=model_id, model_type="ctr", n_buckets=128,
                       ways=4, value_dim=DIM, cache_ttl_ms=5 * MIN,
                       failover_ttl_ms=60 * MIN, eviction=eviction,
                       backend=backend)


def _single(backend):
    cfg = _cfg(1, backend)
    return (S.CachedEmbeddingServer(cfg=cfg, tower_fn=_tower, miss_budget=12),
            S.init_server_state(cfg, writebuf_capacity=4 * B), ())


def _multi(backend):
    cfgs = (_cfg(1, backend), _cfg(2, backend, eviction="ttl"))
    slots = jnp.asarray(np.arange(STEPS * B).reshape(STEPS, B) % 2,
                        jnp.int32)
    return (S.MultiModelServer(cfgs=cfgs, tower_fn=_tower, miss_budget=12),
            S.init_multi_server_state(cfgs, writebuf_capacity=4 * B),
            (slots,))


def _compiled_text(make, backend) -> str:
    server, state, lead = make(backend)
    ids = np.arange(STEPS * B).reshape(STEPS, B) % 24
    feats = jnp.asarray(np.broadcast_to(ids[..., None], (STEPS, B, DIM)),
                        jnp.float32)
    return server.jit_serve_many.lower(
        jnp.eye(DIM), state, *lead, Key64.from_int(ids), feats,
        jnp.arange(STEPS, dtype=jnp.int32) * 1000, flush_every=1,
        collect=True).compile().as_text()


def _strip_metadata(text: str) -> str:
    """The program without its debug information: each instruction's
    ``metadata`` and the module's tables of source frames."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "", text, flags=re.M)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("make", [_single, _multi],
                         ids=["single", "multi"])
def test_scopes_name_every_layer_and_change_no_computation(
        make, backend, monkeypatch):
    scoped = _compiled_text(make, backend)
    op_names = "\n".join(re.findall(r'op_name="([^"]*)"', scoped))
    for name in SCOPES:
        assert name in op_names, name
    # the tail flush holds a whole flush of its own
    assert f"{trace.TAIL_FLUSH}/{trace.FLUSH}/" in op_names

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(make, backend)
    assert "ercache/" not in plain, "a scope outlived the patch"
    same = _strip_metadata(scoped) == _strip_metadata(plain)
    assert same, "the scopes changed the compiled program"
