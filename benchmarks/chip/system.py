"""The system under test, driven the way a cell's traffic asks: the ERCache
server and its state, the preload, the compiled ``serve_many``, and the
dispatch loop of the backlog traffic.

This is the only file of the benchmark that imports the program
(``repro``), apart from the tower adapters under ``towers/``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
PRELOAD_BATCH = 1 << 13


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tower_modules(config: dict):
    arch = config["tower"]["arch"]
    return (load_module(HERE / "towers" / f"{arch}.py"),
            load_module(HERE / "reference" / f"{arch}.py"))


class _Cursor:
    """Hands ``_stage_chunk`` the traffic's features and failure flags of the
    rows it stages, in order, checking that they belong to its ids."""

    def __init__(self, stream):
        self.stream = stream
        self.pos = 0
        self.fail_pos = 0

    def features_of(self, ids, now_ms):
        a, b = self.pos, self.pos + len(ids)
        if not np.array_equal(np.asarray(ids), self.stream.keys[a:b]):
            raise RuntimeError("staged ids are not the traffic's rows")
        self.pos = b
        return {k: v[a:b] for k, v in self.stream.features.items()}

    def mask(self, n, now_ms):
        a = self.fail_pos
        self.fail_pos = a + n
        return self.stream.fails[a:a + n]


@dataclasses.dataclass
class Dispatch:
    lo: int               # first request (stream index)
    n: int                # requests
    steps: int
    start: float          # host clock at the call
    end: float = 0.0      # host clock once its outputs are on the host
    acc: dict = None
    out: tuple = None     # (embeddings, source, age), (S, B, ...) host


class System:
    def __init__(self, config: dict, traffic: dict, stream, seed: int):
        from repro.core import server as srv
        from repro.core.config import CacheConfig

        self.config, self.traffic, self.stream = config, traffic, stream
        self.S, self.B = traffic["steps_per_dispatch"], traffic["batch"]
        tower = config["tower"]
        adapter, self.reference_tower = tower_modules(config)
        self.key = jax.random.PRNGKey(seed)
        self.params = jax.jit(
            lambda k: self.reference_tower.init_params(k, tower))(
                jax.random.fold_in(self.key, 1))
        tower_fn = adapter.program_tower(tower)
        self.miss_budget = int(self.B * config["miss_budget_frac"])
        models = config["models"]
        self.cfgs = [CacheConfig(
            model_id=m["model_id"], model_type=m["model_type"],
            cache_ttl_ms=m["direct_ttl_ms"],
            failover_ttl_ms=m["failover_ttl_ms"],
            n_buckets=m["direct_buckets"], ways=m["ways"],
            failover_n_buckets=m["failover_buckets"],
            value_dim=tower["embed_dim"], eviction=m["eviction"],
            backend=config["backend"]) for m in models]
        self.multi = len(models) > 1
        cap = self.B * 4
        if self.multi:
            self.server = srv.MultiModelServer(
                cfgs=tuple(self.cfgs), tower_fn=tower_fn,
                miss_budget=self.miss_budget)
            self.state = srv.init_multi_server_state(
                self.cfgs, writebuf_capacity=cap)
        else:
            self.server = srv.CachedEmbeddingServer(
                cfg=self.cfgs[0], tower_fn=tower_fn,
                miss_budget=self.miss_budget)
            self.state = srv.init_server_state(self.cfgs[0],
                                               writebuf_capacity=cap)
        self.cursor = _Cursor(stream)
        self.compiled = None
        self.next_lo = 0

    # ------------------------------------------------------------ preload
    def preload_values(self, batch_index: int):
        """Values of one preload batch, from the seed. The reference
        regenerates them through this same compiled function."""
        if not hasattr(self, "_pv"):
            D = self.config["tower"]["embed_dim"]
            self._pv = jax.jit(lambda k, i: jax.random.normal(
                jax.random.fold_in(k, i), (PRELOAD_BATCH, D), jnp.float32))
        return self._pv(jax.random.fold_in(self.key, 2), batch_index)

    def preload(self):
        """Write every preload record into both tiers through the program's
        insert path, oldest first, in batches of PRELOAD_BATCH records, each
        at the time of its newest record."""
        from repro.core import cache as cache_lib
        from repro.core.hashing import Key64

        st = self.stream
        if self.multi:
            policy = self.server.policy

            def ins(direct, failover, hi, lo, slots, vals, ts, now, live):
                return cache_lib.insert_dual_multi(
                    direct, failover, policy, slots, Key64(hi, lo), vals,
                    now, write_mask=live, ts_ms=ts)
        else:
            c = self.cfgs[0]

            def ins(direct, failover, hi, lo, slots, vals, ts, now, live):
                return cache_lib.insert_dual(
                    direct, failover, Key64(hi, lo), vals, now,
                    c.cache_ttl_ms, c.failover_ttl_ms, write_mask=live,
                    ts_ms=ts, evict_lru=c.eviction == "lru")
        # erlint: allow[ER006] — the two cache tiers are the donated state
        insert = jax.jit(ins, donate_argnums=(0, 1))
        direct, failover = self.state.direct, self.state.failover
        n = st.pre_keys.size
        for i, a in enumerate(range(0, n, PRELOAD_BATCH)):
            b = min(a + PRELOAD_BATCH, n)
            pad = PRELOAD_BATCH - (b - a)
            k = Key64.from_int(np.pad(st.pre_keys[a:b], (0, pad)))
            live = np.arange(PRELOAD_BATCH) < b - a
            direct, failover = insert(
                direct, failover, k.hi, k.lo,
                np.pad(st.pre_slots[a:b], (0, pad)), self.preload_values(i),
                np.pad(st.pre_ts[a:b], (0, pad)).astype(np.int32),
                np.int32(st.pre_ts[b - 1]), live)
        self.state = self.state._replace(direct=direct, failover=failover)

    # ------------------------------------------------------- serve dispatch
    def stage(self, steps: int):
        """Stage the next ``steps`` x B requests as serve_many inputs through
        the program's staging path."""
        from repro.launch.serve import _stage_chunk

        lo = self.next_lo
        if lo + steps * self.B > len(self.stream):
            raise RuntimeError(
                f"the stream is exhausted at request {lo}: it holds "
                f"{len(self.stream)}; the traffic file sizes it too small")
        with jax.profiler.TraceAnnotation("stage"):
            self.cursor.pos = self.cursor.fail_pos = lo
            keys, feats, nows, fails = _stage_chunk(
                self.stream.keys, self.stream.times_ms,
                self.cursor.features_of, lo, steps, self.B,
                injector=self.cursor)
            args = (keys, feats, nows, fails)
            if self.multi:
                slots = self.stream.slots[lo:lo + steps * self.B]
                args = (jnp.asarray(slots.reshape(steps, self.B)),) + args
        self.next_lo = lo + steps * self.B
        return Dispatch(lo=lo, n=steps * self.B, steps=steps,
                        start=0.0), args

    def compile(self, args):
        self.compiled = self.server.jit_serve_many.lower(
            self.params, self.state, *args, flush_every=1,
            collect=True).compile()

    def launch(self, d: Dispatch, args):
        with jax.profiler.TraceAnnotation("dispatch"):
            d.start = time.perf_counter()
            self.state, d.acc, d.out = self.compiled(self.params, self.state,
                                                     *args)

    def fetch(self, d: Dispatch):
        with jax.profiler.TraceAnnotation("fetch"):
            d.acc, d.out = jax.device_get((d.acc, d.out))
            d.end = time.perf_counter()

    def final_tables(self, pick):
        """Host copies of both tiers' metadata, and the values at the flat
        slots ``pick(tier_name, write_ts) -> indices`` chooses."""
        out = {}
        for name in ("direct", "failover"):
            tier = getattr(self.state, name)
            meta = jax.device_get({k: getattr(tier, k) for k in (
                "key_hi", "key_lo", "write_ts", "last_access_ts")})
            meta = {k: np.asarray(v).reshape(-1, v.shape[-1])
                    for k, v in meta.items()}
            idx = pick(name, meta["write_ts"])
            vals = tier.values.reshape(-1, tier.values.shape[-1])
            meta["values_at"] = idx
            meta["values"] = np.asarray(jax.device_get(
                jnp.take(vals, jnp.asarray(idx), axis=0)))
            out[name] = meta
        return out

    def free(self):
        self.state = self.compiled = None


def run_backlog(system: System, seconds: float, hooks):
    """Every request is due at the window's start: dispatch, stage the next
    one while it runs, fetch it; until ``seconds`` have passed at a
    dispatch's start. ``hooks.launching(d)`` and ``hooks.fetched(d)`` are
    told of each dispatch. Returns the window's dispatches."""
    S = system.S
    done = []
    d, args = system.stage(S)
    t0 = time.perf_counter()
    while True:
        hooks.launching(d)
        system.launch(d, args)
        nxt = None
        if time.perf_counter() - t0 < seconds:
            nxt = system.stage(S)
        system.fetch(d)
        hooks.fetched(d)
        done.append(d)
        if nxt is None:
            return done
        d, args = nxt
