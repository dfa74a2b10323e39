"""Serving launcher: request stream → ERCache → tower, end to end.

This is the paper's system running for real (CPU-scale): the access-pattern
generator (Fig. 2 calibrated) drives per-region CachedEmbeddingServer
instances fronting a configurable user tower; counters reproduce the
Table 2/3 accounting; results print as a report.

All three modes run on the **device-resident streaming driver**
(``serve_many``, DESIGN.md §9): the request stream is staged into
(S, B) chunks and each chunk is ONE dispatch — a ``lax.scan`` over S
serve steps with the async flush folded in — whose accumulated counters
come back with a single ``jax.device_get`` per chunk instead of a
per-step (let alone per-key) host sync. ``--coalesce`` additionally
dedupes each batch's missed users so the tower runs once per distinct
user (in-batch inference coalescing).

``--multi`` replays ONE access stream across the WHOLE per-model registry
(paper Table 1 / `configs.multi_model_tier_configs`): every batch is a
mixed-model batch served by a single MultiModelServer dispatch, and the
report breaks hit rates down per model.

``--overload`` replays the stream against a CONSTRAINED inference budget
(SLA-aware admission control, DESIGN.md §8): the server's per-step token
budget is provisioned at ``--budget-frac`` of the stream's steady-state
miss demand, and a mid-run re-access burst (a flash crowd drawn from the
same user population) pushes demand further over capacity. The report
shows the degradation chain engaging phase by phase: deferred misses,
failover serves (with staleness), and the SLA-served fraction. Each
phase is a contiguous batch range served by one server, so phases chunk
onto the scan driver directly.

Usage::

    PYTHONPATH=src python -m repro.launch.serve --arch sasrec \
        --minutes 120 --users 5000 --ttl-min 5 \
        [--no-cache] [--multi] [--overload] [--coalesce]
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import cache as cache_lib
from repro.core import regional as rg_lib
from repro.core import server as srv_lib
from repro.core import trace
from repro.core.config import (CacheConfig, MINUTE_MS, HOUR_MS,
                               multi_model_tier_configs)
from repro.core.hashing import Key64
from repro.core.metrics import ServingCounters, power_savings
from repro.data.access_patterns import (FIG6_KNOTS, InterArrivalDist,
                                        StreamConfig, generate_stream_fast,
                                        simulate_hit_rate, thin_diurnal)
from repro.ft import chaos as chaos_lib
from repro.ft import snapshot as snap_lib
from repro.ft.failure import FailureInjector, StragglerHedger
from repro.launch.compile_cache import enable_compile_cache
from repro.models import recsys as rec_lib


_SHARD_REPLAY_ENV = "ERCACHE_SHARD_REPLAY"


def ensure_shard_devices(n_shards: int) -> None:
    """Guarantee ``n_shards`` local devices for ``--shards N``.

    On an accelerator the chips present are all there is: too few fails at
    once, naming the count. On the CPU, XLA fixes the host device count at
    backend init, before argparse can influence it — so when the
    already-initialized backend is short, the launcher REPLAYS itself:
    re-exec the same command with
    ``--xla_force_host_platform_device_count=N`` appended to XLA_FLAGS. A
    marker env var makes the replay single-shot."""
    n_devices = len(jax.devices())
    if n_shards <= 1 or n_devices >= n_shards:
        return
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"--shards {n_shards} needs {n_shards} {platform} devices; "
            f"this host has {n_devices}")
    if os.environ.get(_SHARD_REPLAY_ENV) == "1":
        raise RuntimeError(
            f"--shards {n_shards}: still only {n_devices} devices after "
            "the forced-device-count replay")
    os.environ[_SHARD_REPLAY_ENV] = "1"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_shards}")
    os.execv(sys.executable,
             [sys.executable, "-m", "repro.launch.serve"] + sys.argv[1:])


def _cache_mesh(n_shards: int):
    if n_shards <= 1:
        return None
    from repro.launch.mesh import make_cache_mesh

    return make_cache_mesh(n_shards)


def build_tower(arch: str, smoke: bool = False, seed: int = 0):
    """The arch's tower at its published config (``smoke=True``: the
    reduced SMOKE config the CPU tests run), random weights from ``seed``,
    + a feature synthesizer."""
    cfg = get_config(arch, smoke=smoke)
    params = rec_lib.init_params(jax.random.PRNGKey(seed), cfg)

    def features_of(user_ids: np.ndarray, now_ms: int):
        rng = np.random.default_rng(now_ms % (2 ** 31))
        if cfg.arch_id.startswith("wide-deep"):
            ids = rng.integers(0, cfg.vocab,
                               (user_ids.size, cfg.n_sparse,
                                cfg.nnz_per_field))
            return {"sparse_ids": jnp.asarray(ids, jnp.int32)}
        seq = rng.integers(0, cfg.vocab, (user_ids.size, cfg.seq_len))
        return {"seq": jnp.asarray(seq, jnp.int32)}

    def tower_fn(p, feats):
        return rec_lib.tower_step(p, feats, cfg)

    return cfg, params, tower_fn, features_of


def _stage_chunk(uids, times_ms, features_of, lo: int, n_steps: int,
                 batch: int, injector=None, override_ids=None):
    """Stage ``n_steps`` consecutive serve batches as (S, B) device arrays
    — the scan driver's pre-staged stream. ``override_ids`` substitutes
    the user ids (the overload flash crowd) while keeping the clock.
    The failure mask is only staged when an injector rides along
    (None otherwise — serve_many synthesizes its own zeros).

    Two host spans, one each per call: ``ercache/stage/rows`` (the per-step
    hashing, features and failure masks) and ``ercache/stage/put`` (the
    stacking and transfers to the device)."""
    khi, klo, feats, nows, fails = [], [], [], [], []
    with jax.profiler.TraceAnnotation(trace.STAGE_ROWS):
        for s in range(n_steps):
            a = lo + s * batch
            ids = (uids[a:a + batch] if override_ids is None
                   else override_ids[s])
            now = int(times_ms[a + batch - 1])
            k = Key64.from_int(np.asarray(ids, np.int64))
            khi.append(k.hi)
            klo.append(k.lo)
            feats.append(features_of(ids, now))
            nows.append(now)
            if injector is not None:
                fails.append(injector.mask(batch, now))
    with jax.profiler.TraceAnnotation(trace.STAGE_PUT):
        keys = Key64(hi=jnp.stack(khi), lo=jnp.stack(klo))
        feats = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *feats)
        return (keys, feats, jnp.asarray(nows, jnp.int32),
                jnp.asarray(np.stack(fails)) if fails else None)


def _chunks(n_batches: int, chunk_steps: int):
    """(lo_batch, n_steps) chunk spans covering ``n_batches``."""
    lo = 0
    while lo < n_batches:
        yield lo, min(chunk_steps, n_batches - lo)
        lo += chunk_steps


def run_serving(arch: str = "sasrec", minutes: int = 60, users: int = 2000,
                ttl_min: float = 5.0, failover_ttl_h: float = 1.0,
                batch: int = 256, miss_budget_frac: float = 0.75,
                failure_rate: float = 0.0, use_cache: bool = True,
                backend: str = "jnp", eviction: str = "ttl",
                coalesce: bool = False, chunk_steps: int = 64,
                n_buckets: int = 1 << 14, n_shards: int = 1, seed: int = 0,
                smoke: bool = False, log=print):
    tower_cfg, params, tower_fn, features_of = build_tower(arch, smoke)
    mesh = _cache_mesh(n_shards)
    cache_cfg = CacheConfig(
        model_id=1, model_type="ctr",
        cache_ttl_ms=int(ttl_min * MINUTE_MS),
        failover_ttl_ms=int(failover_ttl_h * HOUR_MS),
        n_buckets=n_buckets, ways=8,
        value_dim=tower_cfg.user_embed_dim,
        miss_budget_frac=miss_budget_frac,
        backend=backend, eviction=eviction, coalesce_misses=coalesce)
    server = srv_lib.CachedEmbeddingServer(
        cfg=cache_cfg, tower_fn=tower_fn,
        miss_budget=max(int(batch * miss_budget_frac), 1), mesh=mesh)
    state = srv_lib.init_server_state(cache_cfg, writebuf_capacity=batch * 4,
                                      mesh=mesh)

    stream_cfg = StreamConfig(n_users=users, horizon_s=minutes * 60.0,
                              seed=seed)
    times_ms, uids = generate_stream_fast(
        stream_cfg, InterArrivalDist(FIG6_KNOTS))
    injector = FailureInjector(base_rate=failure_rate, seed=seed)

    counters = ServingCounters()
    t0 = time.perf_counter()
    n_batches = len(uids) // batch
    if use_cache:
        # scan driver: one dispatch + ONE stats fetch per chunk
        for lo, n_steps in _chunks(n_batches, chunk_steps):
            keys, feats, nows, fails = _stage_chunk(
                uids, times_ms, features_of, lo * batch, n_steps, batch,
                injector=injector)
            state, acc, _ = server.jit_serve_many(
                params, state, keys, feats, nows, fails,
                flush_every=1, collect=False)
            # erlint: allow[ER002] — the one sanctioned fetch per dispatch
            counters.merge(ServingCounters.from_stats(jax.device_get(acc)))
    else:
        # cache-off baseline: still a Python loop, but the fallback count
        # accumulates ON DEVICE — one transfer at the end, no per-step sync
        nf_dev = jnp.int32(0)
        for b in range(n_batches):
            lo = b * batch
            ids = uids[lo:lo + batch]
            now = int(times_ms[lo + batch - 1])
            feats = features_of(ids, now)
            fail = jnp.asarray(injector.mask(batch, now))
            _, src = srv_lib.serve_step_no_cache(
                tower_fn, params, Key64.from_int(ids), feats, fail)
            nf_dev = nf_dev + jnp.sum(
                (src == srv_lib.SRC_FALLBACK).astype(jnp.int32))
        nf = int(nf_dev)
        counters.merge(ServingCounters(
            requests=n_batches * batch, tower_inferences=n_batches * batch,
            tower_failures=nf, fallbacks=nf))
    wall = time.perf_counter() - t0

    d = counters.as_dict()
    d["wall_s"] = round(wall, 2)
    d["batches"] = n_batches
    d["req_per_s"] = round(counters.requests / max(wall, 1e-9), 1)
    d["power_savings_at_0.8_tower_share"] = round(
        power_savings(counters.hit_rate, 0.8), 4)
    log(f"[serve {arch}] ttl={ttl_min}min evict={eviction}"
        f" cache={'on' if use_cache else 'off'}"
        f" coalesce={'on' if coalesce else 'off'}"
        f" requests={d['requests']} hit_rate={d['hit_rate']:.3f}"
        f" fallback_rate={d['fallback_rate']:.4f}"
        f" tower_inferences={d['tower_inferences']}"
        f" ({wall:.1f}s, {d['req_per_s']:.0f} req/s)")
    d["n_shards"] = n_shards
    return d


def run_serving_overload(arch: str = "sasrec", minutes: int = 60,
                         users: int = 2000, batch: int = 256,
                         ttl_min: float = 5.0, failover_ttl_h: float = 1.0,
                         budget_frac: float = 0.5,
                         burst_start_frac: float = 0.4,
                         burst_len_frac: float = 0.2,
                         failure_rate: float = 0.0,
                         failure_burst_rate: float = None,
                         chunk_steps: int = 64,
                         n_buckets: int = 1 << 14, backend: str = "jnp",
                         seed: int = 0,
                         log=print):
    """The capacity-outage / overload scenario, end to end.

    Timeline: the run starts at FULL capacity (no admission gate) so the
    dual-tier caches warm the way production would; at
    ``burst_start_frac`` the capacity OUTAGE begins — the serving tier is
    swapped for one whose per-step token budget is ``budget_frac`` × the
    stream's own steady-state miss demand (measured with the exact
    TTL-cache simulator on the generated stream, the bench_hit_rate
    calibration tool) while a flash crowd of uniform re-accesses from the
    same population spikes demand — and after ``burst_len_frac`` capacity
    recovers. Deferred misses degrade through the relaxed-TTL failover
    tier (``failover_ttl_relax=None`` → staleness unbounded, SLA
    defended); the per-phase report shows the chain engaging during the
    outage and draining after it. Each phase is a contiguous batch range
    behind ONE server, so it chunks straight onto the scan driver — the
    phase bookkeeping costs one stats fetch per chunk, not per step.

    ``failure_rate`` / ``failure_burst_rate`` wire a ``FailureInjector``
    in as the failures-stream generator (paper Table 3's real inference
    failures, 0.05%–6.5%): a base Bernoulli failure rate everywhere,
    jumping to the burst rate during the outage window — the regional
    incident and the capacity outage coincide, the paper's worst case.
    The per-phase report then carries the Table-3 counterfactual split:
    ``fallback_rate`` (with the failover tier assisting) vs
    ``fallback_rate_wo_failover`` (every failover-tier serve would have
    been a default embedding without it).
    """
    tower_cfg, params, tower_fn, features_of = build_tower(arch)
    stream_cfg = StreamConfig(n_users=users, horizon_s=minutes * 60.0,
                              seed=seed)
    times_ms, uids = generate_stream_fast(
        stream_cfg, InterArrivalDist(FIG6_KNOTS))
    ttl_ms = int(ttl_min * MINUTE_MS)
    # provision: steady-state miss demand per batch, from the exact
    # infinite-capacity TTL simulation of THIS stream (warm-up excluded)
    warm_ms = int(times_ms[len(times_ms) // 4]) if len(times_ms) else 0
    miss_rate = 1.0 - simulate_hit_rate(times_ms, uids, ttl_ms,
                                        measure_from_ms=warm_ms)
    budget = max(budget_frac * miss_rate * batch, 1.0)

    cache_cfg = CacheConfig(
        model_id=1, model_type="ctr", cache_ttl_ms=ttl_ms,
        failover_ttl_ms=int(failover_ttl_h * HOUR_MS),
        n_buckets=n_buckets, ways=8, value_dim=tower_cfg.user_embed_dim,
        backend=backend, infer_budget_per_step=budget,
        failover_ttl_relax=None)
    outage_srv = srv_lib.CachedEmbeddingServer(
        cfg=cache_cfg, tower_fn=tower_fn, miss_budget=batch)
    full_srv = srv_lib.CachedEmbeddingServer(
        cfg=dataclasses.replace(cache_cfg, infer_budget_per_step=None),
        tower_fn=tower_fn, miss_budget=batch)
    state = srv_lib.init_server_state(cache_cfg, writebuf_capacity=batch * 4)

    # no max(..., 1) clamp: a stream shorter than one batch yields zero
    # spans (and an all-zero report) instead of staging past its end
    n_batches_total = len(uids) // batch
    burst_lo = int(n_batches_total * burst_start_frac)
    burst_hi = int(n_batches_total * (burst_start_frac + burst_len_frac))
    burst_rng = np.random.default_rng(seed + 1)

    # inference-failure stream: burst window aligned to the outage phase
    injector = None
    if failure_rate > 0 or failure_burst_rate is not None:
        lo_ms = int(times_ms[min(burst_lo * batch, len(times_ms) - 1)])
        hi_ms = int(times_ms[min(burst_hi * batch, len(times_ms) - 1)]) + 1
        injector = FailureInjector(
            base_rate=failure_rate,
            burst_rate=(failure_rate if failure_burst_rate is None
                        else failure_burst_rate),
            burst_windows_ms=((lo_ms, hi_ms),), seed=seed)

    spans = [("pre", 0, burst_lo, full_srv),
             ("outage", burst_lo, burst_hi, outage_srv),
             ("post", burst_hi, n_batches_total, full_srv)]
    phases = {p: ServingCounters() for p, *_ in spans}
    stale = {p: [0.0, 0] for p in phases}          # [age sum, serve count]
    t0 = time.perf_counter()
    for phase, p_lo, p_hi, server in spans:
        for lo, n_steps in _chunks(p_hi - p_lo, chunk_steps):
            b_lo = p_lo + lo
            override = None
            if phase == "outage":
                # flash crowd: same population, arrival order decorrelated
                # — re-access demand beyond what the renewal stream carries
                override = burst_rng.integers(
                    0, users, size=(n_steps, batch)).astype(np.int64)
            keys, feats, nows, fails = _stage_chunk(
                uids, times_ms, features_of, b_lo * batch, n_steps, batch,
                injector=injector, override_ids=override)
            state, acc, _ = server.jit_serve_many(
                params, state, keys, feats, nows, fails,
                flush_every=1, collect=False)
            s = jax.device_get(acc)  # erlint: allow[ER002] — one fetch per chunk
            phases[phase].merge(ServingCounters.from_stats(s))
            stale[phase][0] += float(s["failover_stale_sum_ms"])
            stale[phase][1] += int(s["failover_serves"])
    wall = time.perf_counter() - t0

    out = {"budget_per_step": round(budget, 2),
           "budget_frac": budget_frac,
           "provisioned_miss_rate": round(miss_rate, 4),
           "failure_rate": failure_rate,
           "failure_burst_rate": (failure_rate if failure_burst_rate is None
                                  else failure_burst_rate),
           "wall_s": round(wall, 2), "phases": {}}
    log(f"[serve-overload {arch}] budget={budget:.1f}/step "
        f"({budget_frac:g}x of {miss_rate:.3f} miss demand) "
        f"burst=batches[{burst_lo}:{burst_hi}]"
        + (f" failures={failure_rate:g}/"
           f"{out['failure_burst_rate']:g}" if injector else "")
        + f" ({wall:.1f}s)")
    for p, c in phases.items():
        d = c.as_dict()
        d["mean_failover_stale_ms"] = round(stale[p][0] / max(stale[p][1], 1),
                                            1)
        # Table 3's counterfactual: without the failover tier, every
        # degradation-chain failover serve would have been a default
        # embedding — the with/without-failover fallback-rate split.
        d["fallback_rate_wo_failover"] = round(
            (c.fallbacks + c.failover_serves) / max(c.requests, 1), 6)
        out["phases"][p] = d
        log(f"  {p:>5}: requests={d['requests']} hit={d['hit_rate']:.3f}"
            f" deferred={d['deferred']}"
            f" failures={d['tower_failures']}"
            f" failover_serves={d['failover_serves']}"
            f" (stale {d['mean_failover_stale_ms']:.0f}ms)"
            f" defaults={d['fallbacks']}"
            f" fallback_rate={d['fallback_rate']:.4f}"
            f"/wo_failover={d['fallback_rate_wo_failover']:.4f}"
            f" sla_served={d['sla_served_rate']:.4f}")
    return out


def _stage_steps(ids, nows_ms, features_of):
    """Stage an explicit (S, B) id matrix + (S,) clock as the scan
    driver's stream — the restart harness's Zipf replay has no underlying
    renewal stream to index into (cf. :func:`_stage_chunk`)."""
    khi, klo, feats = [], [], []
    for s in range(ids.shape[0]):
        k = Key64.from_int(np.asarray(ids[s], np.int64))
        khi.append(k.hi)
        klo.append(k.lo)
        feats.append(features_of(ids[s], int(nows_ms[s])))
    return (Key64(hi=jnp.stack(khi), lo=jnp.stack(klo)),
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *feats),
            jnp.asarray(nows_ms, jnp.int32))


def run_serving_restart(arch: str = "sasrec", pre_steps: int = 240,
                        recovery_steps: int = 120, users: int = 3000,
                        batch: int = 256, ttl_min: float = 5.0,
                        checkpoint_every: int = 40, step_ms: int = 250,
                        zipf_a: float = 1.2, n_buckets: int = 1 << 12,
                        backend: str = "jnp", chunk_steps: int = 40,
                        workdir: str = None, seed: int = 0,
                        smoke: bool = False, log=print):
    """Kill/restore fault-injection harness (DESIGN.md §10).

    Replays a Zipf-skewed request stream while snapshotting the cache at
    every checkpoint boundary (``ft/snapshot.snapshot_server``, last-3
    retention). A ``FailureInjector`` burst window covering the middle of
    the stream models the incident; the process is killed at the first
    checkpoint boundary inside it (``FailureInjector.kill_step``) — the
    in-memory state is discarded and the NEXT save is left torn (a
    directory without its COMMITTED marker), which the restore must skip.

    Recovery is then measured four ways over the SAME post-kill stream:

    * **warm_same** — restore into the identical geometry (bit-exact);
    * **warm_grow** / **warm_shrink** — restore into a 2× / ½× table via
      the elastic rehash;
    * **cold** — a fresh table, the restart without the durability layer.

    The report carries per-chunk hit-rate recovery curves, the
    resized-restore probe-parity check (every live snapshot entry the
    grown table must still serve bit-exactly; the shrunk table serves a
    subset, values bit-exact on survivors), and the counters-provenance
    check (the restored ledger resumes additively across the kill).
    """
    tower_cfg, params, tower_fn, features_of = build_tower(arch, smoke)
    ttl_ms = int(ttl_min * MINUTE_MS)
    base_cfg = CacheConfig(
        model_id=1, model_type="ctr", cache_ttl_ms=ttl_ms,
        failover_ttl_ms=int(2 * HOUR_MS), n_buckets=n_buckets, ways=8,
        value_dim=tower_cfg.user_embed_dim, backend=backend)

    total = pre_steps + recovery_steps
    rng = np.random.default_rng(seed)
    ids_all = rng.zipf(zipf_a, size=(total, batch)).astype(np.int64) % users
    nows_all = (np.arange(total, dtype=np.int64) + 1) * step_ms

    # The incident: a failure burst over the back half of the pre phase;
    # the process dies at the first checkpoint boundary inside it.
    burst = (int(nows_all[pre_steps // 2]), int(nows_all[pre_steps - 1]) + 1)
    injector = FailureInjector(base_rate=0.0, burst_rate=1.0,
                               burst_windows_ms=(burst,), seed=seed)
    kill = injector.kill_step(nows_all, checkpoint_every)
    if kill is None or kill > pre_steps:
        kill = max((pre_steps // checkpoint_every) * checkpoint_every,
                   checkpoint_every)
    if workdir is None:
        workdir = tempfile.mkdtemp(prefix="ercache-restart-")

    def make_server(nb):
        cfg = dataclasses.replace(base_cfg, n_buckets=nb)
        return srv_lib.CachedEmbeddingServer(
            cfg=cfg, tower_fn=tower_fn, miss_budget=batch), cfg

    server, cfg0 = make_server(n_buckets)
    state = srv_lib.init_server_state(cfg0, writebuf_capacity=batch * 4)

    # ---- phase 1: serve to the kill, snapshotting at every boundary ----
    t0 = time.perf_counter()
    pre_counters = ServingCounters()
    for seg_lo in range(0, kill, checkpoint_every):
        n = min(checkpoint_every, kill - seg_lo)
        keys, feats, nows = _stage_steps(ids_all[seg_lo:seg_lo + n],
                                         nows_all[seg_lo:seg_lo + n],
                                         features_of)
        state, acc, _ = server.jit_serve_many(
            params, state, keys, feats, nows, flush_every=1, collect=False)
        # erlint: allow[ER002] — the one sanctioned fetch per dispatch
        pre_counters.merge(ServingCounters.from_stats(jax.device_get(acc)))
        state = snap_lib.snapshot_server(
            workdir, seg_lo + n, server, state,
            int(nows_all[seg_lo + n - 1]), counters=pre_counters,
            retain_last_k=3)
    # The crash: the in-memory state dies, and a save that was in flight
    # is left torn (manifest truncated, no COMMITTED marker) — restore
    # must skip it and pick the kill-boundary snapshot.
    torn = os.path.join(workdir, f"step_{kill + checkpoint_every:08d}")
    os.makedirs(torn, exist_ok=True)
    with open(os.path.join(torn, "manifest.json"), "w") as f:
        f.write("{")
    del state
    restore_now = int(nows_all[kill - 1])

    # ---- phase 2: restore (3 geometries) + cold, replay the SAME stream
    rec_ids = ids_all[kill:kill + recovery_steps]
    rec_nows = nows_all[kill:kill + recovery_steps]
    specs = [("warm_same", n_buckets, True),
             ("warm_grow", n_buckets * 2, True),
             ("warm_shrink", max(n_buckets // 2, 1), True),
             ("cold", n_buckets, False)]
    variants, probes = {}, {}
    uniq = np.unique(ids_all[:kill])
    probe_keys = Key64.from_int(uniq.astype(np.int64))
    for name, nb, warm in specs:
        vsrv, vcfg = make_server(nb)
        if warm:
            r = snap_lib.restore_server(workdir, vsrv, now_ms=restore_now,
                                        writebuf_capacity=batch * 4)
            vstate, ledger = r.state, r.counters
            mode, restored_step = r.mode, r.step
            # probe BEFORE serving mutates (donates) the restored table
            res = cache_lib.lookup(vstate.direct, probe_keys, restore_now,
                                   ttl_ms)
            probes[name] = (np.asarray(res.hit), np.asarray(res.values))
        else:
            vstate = srv_lib.init_server_state(vcfg,
                                               writebuf_capacity=batch * 4)
            ledger, mode, restored_step = ServingCounters(), "cold", None
        resumed = ledger.requests
        rec = ServingCounters()
        curve = []
        for lo, n in _chunks(recovery_steps, chunk_steps):
            keys, feats, nows = _stage_steps(rec_ids[lo:lo + n],
                                             rec_nows[lo:lo + n],
                                             features_of)
            vstate, acc, _ = vsrv.jit_serve_many(
                params, vstate, keys, feats, nows, flush_every=1,
                collect=False)
            c = ServingCounters.from_stats(
                jax.device_get(acc))  # erlint: allow[ER002] — one per chunk
            curve.append(round(c.hit_rate, 4))
            rec.merge(c)
        ledger.merge(rec)
        variants[name] = {
            "mode": mode, "restored_step": restored_step, "n_buckets": nb,
            "recovery_hit_rate": round(rec.hit_rate, 4),
            "recovery_curve": curve,
            "recovery_tower_inferences": rec.tower_inferences,
            "resumed_requests": resumed,
            "total_requests": ledger.requests,
        }
    wall = time.perf_counter() - t0

    # ---- resized-restore probe parity (on the pre-kill key population) -
    h_same, v_same = probes["warm_same"]
    h_grow, v_grow = probes["warm_grow"]
    h_shr, v_shr = probes["warm_shrink"]
    both_g = h_same & h_grow
    both_s = h_same & h_shr
    parity = {
        "probed_keys": int(uniq.size),
        "snapshot_live": int(h_same.sum()),
        "grow_survivors": int(h_grow.sum()),
        "shrink_survivors": int(h_shr.sum()),
        "grow_preserves_all_live": bool((h_grow | ~h_same).all()),
        "shrink_serves_subset": bool((~h_shr | h_same).all()),
        "values_bit_exact": bool(
            np.array_equal(v_grow[both_g], v_same[both_g])
            and np.array_equal(v_shr[both_s], v_same[both_s])),
    }
    parity["pass"] = (parity["grow_preserves_all_live"]
                      and parity["shrink_serves_subset"]
                      and parity["values_bit_exact"])

    out = {
        "pre_steps": kill, "recovery_steps": recovery_steps,
        "kill_step": kill, "checkpoint_every": checkpoint_every,
        "step_ms": step_ms, "users": users, "batch": batch,
        "zipf_a": zipf_a, "ttl_min": ttl_min, "n_buckets": n_buckets,
        "backend": backend,
        "pre_hit_rate": round(pre_counters.hit_rate, 4),
        "torn_step_skipped": all(
            variants[n]["restored_step"] == kill
            for n in ("warm_same", "warm_grow", "warm_shrink")),
        "ledger_continuous": (
            variants["warm_same"]["total_requests"]
            == (kill + recovery_steps) * batch),
        "warm_vs_cold_gain": round(
            variants["warm_same"]["recovery_hit_rate"]
            - variants["cold"]["recovery_hit_rate"], 4),
        "variants": variants, "parity": parity,
        "wall_s": round(wall, 2), "workdir": workdir,
    }
    log(f"[serve-restart {arch}] kill@step {kill} "
        f"(ckpt every {checkpoint_every}), recovery {recovery_steps} steps,"
        f" pre_hit={out['pre_hit_rate']:.3f} ({wall:.1f}s)")
    for name, v in variants.items():
        log(f"  {name:>11}: mode={v['mode']:<8}"
            f" recovery_hit={v['recovery_hit_rate']:.3f}"
            f" tower_inferences={v['recovery_tower_inferences']}"
            f" curve={v['recovery_curve'][:4]}")
    log(f"  parity: live={parity['snapshot_live']}"
        f" grow={parity['grow_survivors']}"
        f" shrink={parity['shrink_survivors']}"
        f" pass={parity['pass']} | warm-vs-cold gain "
        f"{out['warm_vs_cold_gain']:+.3f} | torn skipped "
        f"{out['torn_step_skipped']} | ledger continuous "
        f"{out['ledger_continuous']}")
    return out


def _window_steps(windows_ms, nows_ms, tail_win: int):
    """Map the fault-edge windows (ms spans from ``chaos.fault_windows``)
    onto step ranges of the staged clock, subdividing the trailing quiet
    span into ``tail_win``-step recovery windows (the bounded tail the
    ledger asserts recovery within). Returns [(lo, hi, label), ...] in
    step indices; empty spans are dropped."""
    nows = np.asarray(nows_ms, np.int64)
    spans = []
    for a, b, label in windows_ms:
        steps = np.nonzero((nows >= a) & (nows < b))[0]
        if steps.size:
            spans.append((int(steps[0]), int(steps[-1]) + 1, label))
    if spans and spans[-1][2] == "quiet" and len(spans) > 1:
        lo, hi, _ = spans.pop()
        for s in range(lo, hi, tail_win):
            spans.append((s, min(s + tail_win, hi), "recovery"))
    return spans


def run_serving_chaos(arch: str = "sasrec", scenario: str = "incident",
                      n_models: int = 4, steps: int = 240,
                      users: int = 1000, batch: int = 256,
                      step_ms: int = 250, ttl_min: float = 0.2,
                      failover_ttl_h: float = 2.0, zipf_a: float = 1.2,
                      n_buckets: int = 1 << 10, backend: str = "jnp",
                      chunk_steps: int = 64, fail_rate: float = 0.9,
                      max_retries: int = 2, backoff_ms: int = 500,
                      hedge_after_ms: float = 25.0,
                      checkpoint_every: int = 40, recovery_win: int = 24,
                      recovery_tol_pp: float = 2.0, seed: int = 0,
                      smoke: bool = False, log=print):
    """The chaos engine end to end (DESIGN.md §14): one of the preset
    multi-fault scenarios (``incident`` / ``cascade`` / ``rolling``)
    compiled into a device-resident fault schedule and replayed against
    the multi-model tier through chunked ``serve_many`` dispatches — the
    whole compounding-failure timeline runs with ONE stats fetch per
    chunk and no per-step host sync.

    A Zipf-skewed stream over ``n_models`` (round-robin fan-out, the
    ``--multi`` shape) serves on the schedule's SKEWED clock
    (``ClockSkew`` faults move the TTL ``now`` stream); every model runs
    admission control (ample budget — ``Outage`` windows force its grant
    to 0 regardless) with bounded retry/backoff for failed inferences.
    The degradation ledger reports every fault window and the recovery
    tail separately: SLA-served rate, failover serves + staleness,
    defaults, retry and drop accounting, and the conservation identity
    (requests == direct + computed + failover + defaults). The
    ``StragglerHedger`` rides along: per-window inference latencies are
    sampled with and without hedging (paired draws) so the report carries
    the p99 win and its ``extra_compute_frac`` cost.

    Recovery is asserted against the PRE-fault baseline: the first
    ``recovery_win``-step tail window whose hit rate is back within
    ``recovery_tol_pp`` of the pre-fault hit rate marks the recovery
    point (``recovered_after_windows``); bench_chaos CI-asserts it is
    bounded. ``rolling`` additionally reports the checkpoint boundaries
    ``FailureInjector.kill_steps`` lands inside the outage windows — the
    kill points a rolling-restart harness would use."""
    tower_cfg, params, tower_fn, features_of = build_tower(arch, smoke)
    cfgs = [CacheConfig(
        model_id=m + 1, model_type="ctr",
        cache_ttl_ms=int(ttl_min * MINUTE_MS),
        failover_ttl_ms=int(failover_ttl_h * HOUR_MS),
        n_buckets=n_buckets, ways=8, value_dim=tower_cfg.user_embed_dim,
        backend=backend, infer_budget_per_step=float(batch),
        failover_ttl_relax=None) for m in range(n_models)]
    server = srv_lib.MultiModelServer(cfgs=tuple(cfgs), tower_fn=tower_fn,
                                      miss_budget=batch)
    state = srv_lib.init_multi_server_state(cfgs,
                                            writebuf_capacity=batch * 4)

    rng = np.random.default_rng(seed)
    ids_all = rng.zipf(zipf_a, size=(steps, batch)).astype(np.int64) % users
    nows_all = (np.arange(steps, dtype=np.int64) + 1) * step_ms
    slots_all = ((np.arange(batch)[None, :] + np.arange(steps)[:, None])
                 % n_models).astype(np.int32)
    horizon_ms = int(nows_all[-1]) + step_ms

    # the POOLED direct bucket space (every model same-sized here)
    pooled = n_models * n_buckets
    faults = chaos_lib.preset_faults(scenario, horizon_ms,
                                     n_models=n_models, n_buckets=pooled,
                                     fail_rate=fail_rate)
    sched = chaos_lib.compile_schedule(
        faults, nows_all, batch, n_models=n_models, n_buckets=pooled,
        slots=slots_all, retry=chaos_lib.RetryPolicy(
            max_retries=max_retries, backoff_ms=backoff_ms),
        seed=seed + 1)
    snow = np.asarray(chaos_lib.skewed_now(sched, nows_all))
    spans = _window_steps(chaos_lib.fault_windows(faults, horizon_ms),
                          nows_all, recovery_win)

    windows = []
    lat_hedged, lat_plain, extra_frac = [], [], []
    t0 = time.perf_counter()
    for wi, (w_lo, w_hi, label) in enumerate(spans):
        acc_sum: dict = {}
        for lo, n in _chunks(w_hi - w_lo, chunk_steps):
            a = w_lo + lo
            keys, feats, nows = _stage_steps(ids_all[a:a + n],
                                             snow[a:a + n], features_of)
            state, acc, _ = server.jit_serve_many(
                params, state, jnp.asarray(slots_all[a:a + n]), keys,
                feats, nows, None, chaos_lib.slice_schedule(sched, a, a + n),
                flush_every=1, collect=False)
            s = jax.device_get(acc)  # erlint: allow[ER002] — one per chunk
            for k, v in s.items():
                if np.ndim(v) == 0:
                    acc_sum[k] = acc_sum.get(k, 0) + float(v)
        g = lambda k: acc_sum.get(k, 0.0)
        req = max(g("requests"), 1.0)
        # paired latency draws: same rng seed, hedged samples the backup
        n_lat = int(g("tower_inferences") + g("retries"))
        p99 = p99_plain = None
        if n_lat:
            hd = StragglerHedger(hedge_after_ms=hedge_after_ms,
                                 seed=seed + 100 + wi).latencies(n_lat)
            pl = StragglerHedger(hedge_after_ms=None,
                                 seed=seed + 100 + wi).latencies(n_lat)
            lat_hedged.append(hd["latency_ms"])
            lat_plain.append(pl["latency_ms"])
            extra_frac.append((hd["extra_compute_frac"], n_lat))
            p99 = round(float(np.percentile(hd["latency_ms"], 99)), 2)
            p99_plain = round(float(np.percentile(pl["latency_ms"], 99)), 2)
        row = {
            "label": label, "steps": [w_lo, w_hi],
            "t0_ms": int(nows_all[w_lo]), "t1_ms": int(nows_all[w_hi - 1]),
            "requests": int(g("requests")),
            "hit_rate": round(g("direct_hits") / req, 4),
            "sla_served_rate": round(1.0 - g("fallbacks") / req, 4),
            "deferred": int(g("deferred")),
            "failover_serves": int(g("failover_serves")),
            "mean_failover_stale_ms": round(
                g("failover_stale_sum_ms")
                / max(g("failover_serves"), 1), 1),
            "fallbacks": int(g("fallbacks")),
            "tower_inferences": int(g("tower_inferences")),
            "tower_failures": int(g("tower_failures")),
            "computed_serves": int(g("computed_serves")),
            "retries": int(g("retries")),
            "retry_successes": int(g("retry_successes")),
            "blackout_write_drops": int(g("blackout_write_drops")),
            "write_ring_drops": int(g("write_ring_drops")),
            "touch_ring_drops": int(g("touch_ring_drops")),
            "p99_ms": p99, "p99_unhedged_ms": p99_plain,
            "conservation_ok": int(g("requests")) == int(
                g("direct_hits") + g("computed_serves")
                + g("failover_serves") + g("fallbacks")),
        }
        windows.append(row)
    wall = time.perf_counter() - t0

    tot = lambda k: sum(w[k] for w in windows)
    requests = tot("requests")
    sla = 1.0 - tot("fallbacks") / max(requests, 1)
    pre = next((w for w in windows if w["label"] == "quiet"), None)
    tail = [w for w in windows if w["label"] == "recovery"]
    recovered_after = None
    if pre is not None:
        floor_hit = pre["hit_rate"] - recovery_tol_pp / 100.0
        for i, w in enumerate(tail):
            if w["hit_rate"] >= floor_hit:
                recovered_after = i + 1
                break
    lat_h = (np.concatenate(lat_hedged) if lat_hedged
             else np.zeros(1))
    lat_p = (np.concatenate(lat_plain) if lat_plain else np.zeros(1))
    n_extra = max(sum(n for _, n in extra_frac), 1)
    out = {
        "scenario": scenario, "arch": arch, "backend": backend,
        "n_models": n_models, "steps": steps, "batch": batch,
        "users": users, "step_ms": step_ms, "zipf_a": zipf_a,
        "ttl_min": ttl_min, "n_buckets": n_buckets,
        "fail_rate": fail_rate, "max_retries": max_retries,
        "backoff_ms": backoff_ms, "horizon_ms": horizon_ms,
        "requests": requests,
        "sla_served_rate": round(sla, 5),
        "fallbacks": tot("fallbacks"),
        "failover_serves": tot("failover_serves"),
        "retries": tot("retries"),
        "retry_successes": tot("retry_successes"),
        "blackout_write_drops": tot("blackout_write_drops"),
        "write_ring_drops": tot("write_ring_drops"),
        "touch_ring_drops": tot("touch_ring_drops"),
        "conservation_ok": all(w["conservation_ok"] for w in windows),
        "windows": windows,
        "recovery": {
            "pre_fault_hit_rate": None if pre is None else pre["hit_rate"],
            "tol_pp": recovery_tol_pp,
            "tail_windows": len(tail),
            "recovered_after_windows": recovered_after,
            "recovered": recovered_after is not None,
        },
        "hedging": {
            "hedge_after_ms": hedge_after_ms,
            "p99_ms": round(float(np.percentile(lat_h, 99)), 2),
            "p99_unhedged_ms": round(float(np.percentile(lat_p, 99)), 2),
            "extra_compute_frac": round(
                sum(f * n for f, n in extra_frac) / n_extra, 4),
        },
        "wall_s": round(wall, 2),
    }
    if scenario == "rolling":
        outages = [f for f in faults if isinstance(f, chaos_lib.Outage)]
        inj = FailureInjector(
            base_rate=0.0, burst_rate=1.0,
            burst_windows_ms=tuple((f.t0_ms, f.t1_ms) for f in outages),
            seed=seed)
        out["kill_boundaries"] = inj.kill_steps(nows_all, checkpoint_every)
    log(f"[serve-chaos {arch}] scenario={scenario} models={n_models}"
        f" steps={steps} requests={requests}"
        f" sla_served={out['sla_served_rate']:.4f}"
        f" retries={out['retries']}"
        f" (succ {out['retry_successes']})"
        f" conservation={'ok' if out['conservation_ok'] else 'VIOLATED'}"
        f" p99={out['hedging']['p99_ms']}ms"
        f" (unhedged {out['hedging']['p99_unhedged_ms']}ms,"
        f" +{out['hedging']['extra_compute_frac']:.1%} compute)"
        f" ({wall:.1f}s)")
    for w in windows:
        log(f"  [{w['t0_ms']:>7}-{w['t1_ms']:>7}ms] {w['label']:<32}"
            f" hit={w['hit_rate']:.3f} sla={w['sla_served_rate']:.4f}"
            f" defer={w['deferred']} fo={w['failover_serves']}"
            f" (stale {w['mean_failover_stale_ms']:.0f}ms)"
            f" defaults={w['fallbacks']} retry={w['retries']}"
            f"/{w['retry_successes']}"
            f" drops={w['blackout_write_drops']}"
            f"+{w['write_ring_drops']}+{w['touch_ring_drops']}")
    rec = out["recovery"]
    log(f"  recovery: pre_hit={rec['pre_fault_hit_rate']}"
        f" recovered_after={rec['recovered_after_windows']}"
        f"/{rec['tail_windows']} windows (tol {recovery_tol_pp}pp)")
    return out


def run_serving_multi(arch: str = "sasrec", minutes: int = 60,
                      users: int = 2000, batch: int = 256,
                      miss_budget_frac: float = 0.75,
                      n_buckets: int = 1 << 12, failure_rate: float = 0.0,
                      backend: str = "jnp", coalesce: bool = False,
                      chunk_steps: int = 64, n_shards: int = 1,
                      seed: int = 0,
                      log=print):
    """Replay one access stream across the whole model registry.

    Each arriving user request is fanned out to one of the registry's
    models (round-robin within the batch), so every serve batch is a
    mixed-model batch — served by ONE MultiModelServer dispatch with
    per-model TTL/eviction/capacity policies; chunks of ``chunk_steps``
    batches run as single scan-driver dispatches. Reports global counters
    plus the per-model hit-rate breakdown (the paper's Table 2 shape).
    """
    tower_cfg, params, tower_fn, features_of = build_tower(arch)
    mesh = _cache_mesh(n_shards)
    cfgs = multi_model_tier_configs(value_dim=tower_cfg.user_embed_dim,
                                    n_buckets=n_buckets)
    if coalesce:
        cfgs = [dataclasses.replace(c, coalesce_misses=True) for c in cfgs]
    server = srv_lib.MultiModelServer(
        cfgs=tuple(cfgs), tower_fn=tower_fn,
        miss_budget=max(int(batch * miss_budget_frac), 1), backend=backend,
        mesh=mesh)
    state = srv_lib.init_multi_server_state(cfgs,
                                            writebuf_capacity=batch * 4,
                                            mesh=mesh)
    n_models = server.n_models

    stream_cfg = StreamConfig(n_users=users, horizon_s=minutes * 60.0,
                              seed=seed)
    times_ms, uids = generate_stream_fast(
        stream_cfg, InterArrivalDist(FIG6_KNOTS))
    injector = FailureInjector(base_rate=failure_rate, seed=seed)

    counters = ServingCounters()
    pm_requests = np.zeros(n_models, np.int64)
    pm_hits = np.zeros(n_models, np.int64)
    pm_fallbacks = np.zeros(n_models, np.int64)
    t0 = time.perf_counter()
    n_batches = len(uids) // batch
    for lo, n_steps in _chunks(n_batches, chunk_steps):
        keys, feats, nows, fails = _stage_chunk(
            uids, times_ms, features_of, lo * batch, n_steps, batch,
            injector=injector)
        # fan-out: each request targets one registry model, round-robin
        # phased by the batch index so a user cycles through models.
        slots = jnp.asarray(
            (np.arange(batch)[None, :] + lo + np.arange(n_steps)[:, None])
            % n_models, jnp.int32)
        state, acc, _ = server.jit_serve_many(
            params, state, slots, keys, feats, nows, fails,
            flush_every=1, collect=False)
        s = jax.device_get(acc)  # erlint: allow[ER002] — one fetch per chunk
        counters.merge(ServingCounters.from_stats(s))
        pm_requests += np.asarray(s["per_model_requests"], np.int64)
        pm_hits += np.asarray(s["per_model_direct_hits"], np.int64)
        pm_fallbacks += np.asarray(s["per_model_fallbacks"], np.int64)
    wall = time.perf_counter() - t0

    d = counters.as_dict()
    d["wall_s"] = round(wall, 2)
    d["batches"] = n_batches
    d["n_models"] = n_models
    d["n_shards"] = n_shards
    d["req_per_s"] = round(counters.requests / max(wall, 1e-9), 1)
    d["per_model"] = {
        cfg.model_id: {
            "model_type": cfg.model_type,
            "eviction": cfg.eviction,
            "ttl_min": cfg.cache_ttl_ms / MINUTE_MS,
            "requests": int(pm_requests[i]),
            "hit_rate": round(pm_hits[i] / max(pm_requests[i], 1), 4),
            "fallback_rate": round(
                pm_fallbacks[i] / max(pm_requests[i], 1), 4),
        }
        for i, cfg in enumerate(cfgs)
    }
    log(f"[serve-multi {arch}] models={n_models} backend={backend}"
        f" requests={d['requests']} hit_rate={d['hit_rate']:.3f}"
        f" fallback_rate={d['fallback_rate']:.4f}"
        f" ({wall:.1f}s, {d['req_per_s']:.0f} req/s)")
    for mid, pm in d["per_model"].items():
        log(f"  model {mid} ({pm['model_type']}, ttl={pm['ttl_min']:g}min,"
            f" {pm['eviction']}): hit_rate={pm['hit_rate']:.3f}"
            f" requests={pm['requests']}")
    return d


def run_serving_regional(arch: str = "sasrec", n_regions: int = 4,
                         minutes: int = 60, users: int = 2000,
                         batch: int = 256, ttl_min: float = 5.0,
                         failover_ttl_h: float = 1.0,
                         locality: float = 0.98, drain: bool = False,
                         drain_start_frac: float = 0.4,
                         drain_len_frac: float = 0.25,
                         n_buckets: int = 1 << 12, backend: str = "jnp",
                         eviction: str = "ttl", chunk_steps: int = 64,
                         seed: int = 0,
                         log=print):
    """The regional drain scenario ON DEVICE (paper §3.6–3.7, Fig. 10).

    R regions are stacked as a leading axis over the cache tier
    (core/regional.py): sticky routing reads/updates a device-resident
    home-region table, the drain mask + epoch + event base ride along as
    scan inputs, and the whole drain + flash-crowd + diurnal mix replays
    through chunked ``serve_many`` dispatches with ONE stats fetch per
    chunk — no per-step host sync (contrast the host-loop
    ``DrainTestHarness``, the numpy oracle this path is parity-locked
    against in tests/test_region_parity.py).

    Timeline: the stationary renewal stream is thinned to a day/night
    envelope compressed into the run's horizon (``thin_diurnal``); at
    ``drain_start_frac`` (batch index, aligned to chunk boundaries so
    every chunk is entirely pre/drain/post) region R-1 drains and a
    flash crowd of uniform re-accesses over a hot user pool mixes into
    the window — drain and crowd coincide, the worst case; after
    ``drain_len_frac`` the region undrains. Its users re-home lazily and
    PERMANENTLY (no undrain flap), the Fig. 10 claim being that the
    global hit rate barely dips. The report carries the per-chunk
    hit-rate curve, pre/drain/post means + dip, per-region load, and the
    drained region's in-window load (exactly 0 by construction — routing
    never targets a drained region)."""
    tower_cfg, params, tower_fn, features_of = build_tower(arch)
    cache_cfg = CacheConfig(
        model_id=1, model_type="ctr",
        cache_ttl_ms=int(ttl_min * MINUTE_MS),
        failover_ttl_ms=int(failover_ttl_h * HOUR_MS),
        n_buckets=n_buckets, ways=8,
        value_dim=tower_cfg.user_embed_dim,
        backend=backend, eviction=eviction)
    server = rg_lib.RegionalServer(
        cfgs=(cache_cfg,), n_regions=n_regions, n_users=users,
        tower_fn=tower_fn, miss_budget=batch, locality=locality, seed=seed)
    state = server.init_state(writebuf_capacity=batch * 4)

    stream_cfg = StreamConfig(n_users=users, horizon_s=minutes * 60.0,
                              seed=seed)
    times_ms, uids = generate_stream_fast(
        stream_cfg, InterArrivalDist(FIG6_KNOTS))
    # diurnal mix: one full day/night cycle compressed into the horizon,
    # peak mid-run (so the drain window lands on non-trivial load)
    horizon_h = max(minutes / 60.0, 1e-9)
    times_ms, uids = thin_diurnal(times_ms, uids, seed=seed + 1,
                                  period_h=horizon_h,
                                  peak_h=horizon_h / 2.0)

    n_batches = len(uids) // batch

    def align(b: int) -> int:
        return (b // chunk_steps) * chunk_steps

    drain_lo = align(int(n_batches * drain_start_frac))
    drain_hi = align(int(n_batches * (drain_start_frac + drain_len_frac)))
    if drain:
        # guarantee at least one pre chunk and one in-window chunk even
        # on smoke-sized runs (the window stays chunk-aligned so every
        # chunk is entirely in one phase)
        drain_lo = max(drain_lo, chunk_steps)
        drain_hi = max(drain_hi, drain_lo + chunk_steps)
    drain_region = n_regions - 1
    events = []
    if drain and n_regions > 1 and drain_lo < n_batches:
        events.append((drain_lo, "drain", drain_region))
        if drain_hi < n_batches:
            events.append((drain_hi, "undrain", drain_region))
    drained_all, epoch_all = rg_lib.stage_drain_schedule(
        max(n_batches, 1), n_regions, events)
    ebase_all = rg_lib.event_bases(0, max(n_batches, 1), batch)

    # flash crowd: uniform re-accesses over a small hot pool, mixed into
    # half the window's slots — re-access demand beyond the renewal stream
    crowd_rng = np.random.default_rng(seed + 2)
    hot = crowd_rng.integers(0, users, size=max(users // 50, 1))

    counters = ServingCounters()
    curve = []
    region_load = np.zeros(n_regions, np.int64)
    drained_load = 0
    rehomed = excursions = 0
    t0 = time.perf_counter()
    for lo, n_steps in _chunks(n_batches, chunk_steps):
        ids_mat = uids[lo * batch:(lo + n_steps) * batch].reshape(
            n_steps, batch).astype(np.int64)
        in_window = drain_lo <= lo < drain_hi
        if in_window:
            mix = crowd_rng.random(ids_mat.shape) < 0.5
            ids_mat = np.where(
                mix, hot[crowd_rng.integers(0, hot.size, ids_mat.shape)],
                ids_mat)
        keys, feats, nows, _ = _stage_chunk(
            uids, times_ms, features_of, lo * batch, n_steps, batch,
            override_ids=ids_mat)
        slots = jnp.zeros((n_steps, batch), jnp.int32)
        state, acc, _ = server.jit_serve_many(
            params, state, jnp.asarray(ids_mat, jnp.int32), slots, keys,
            feats, nows, drained_all[lo:lo + n_steps],
            epoch_all[lo:lo + n_steps], ebase_all[lo:lo + n_steps],
            flush_every=1, collect=False)
        s = jax.device_get(acc)  # erlint: allow[ER002] — one fetch per chunk
        c = ServingCounters.from_stats(s)
        counters.merge(c)
        pr = np.asarray(s["per_model_requests"],
                        np.int64).reshape(n_regions, 1).sum(axis=1)
        region_load += pr
        if drain and in_window:
            drained_load += int(pr[drain_region])
        rehomed += int(s["rehomed"])
        excursions += int(s["excursions"])
        phase = ("pre" if lo < drain_lo
                 else "drain" if lo < drain_hi else "post")
        curve.append({"batch_lo": lo, "phase": phase,
                      "hit_rate": round(c.hit_rate, 4)})
    wall = time.perf_counter() - t0

    def phase_mean(p):
        xs = [pt["hit_rate"] for pt in curve if pt["phase"] == p]
        return round(float(np.mean(xs)), 4) if xs else None

    d = counters.as_dict()
    d["wall_s"] = round(wall, 2)
    d["batches"] = n_batches
    d["req_per_s"] = round(counters.requests / max(wall, 1e-9), 1)
    d["n_regions"] = n_regions
    d["locality"] = locality
    d["drain"] = bool(drain)
    d["drain_region"] = drain_region if drain else None
    d["drain_batches"] = [drain_lo, drain_hi]
    d["rehomed"] = rehomed
    d["excursions"] = excursions
    d["region_load"] = region_load.tolist()
    d["drained_load_during_drain"] = drained_load
    d["hit_rate_pre"] = phase_mean("pre")
    d["hit_rate_drain"] = phase_mean("drain")
    d["hit_rate_post"] = phase_mean("post")
    d["dip_pp"] = (round((d["hit_rate_pre"] - d["hit_rate_drain"]) * 100, 2)
                   if d["hit_rate_pre"] is not None
                   and d["hit_rate_drain"] is not None else None)
    d["hit_rate_curve"] = [pt["hit_rate"] for pt in curve]
    log(f"[serve-regional {arch}] regions={n_regions}"
        f" locality={locality:g}"
        f" drain={'batches[%d:%d]' % (drain_lo, drain_hi) if drain else 'off'}"
        f" requests={d['requests']} hit_rate={d['hit_rate']:.3f}"
        f" pre/drain/post={d['hit_rate_pre']}/{d['hit_rate_drain']}"
        f"/{d['hit_rate_post']} dip_pp={d['dip_pp']}"
        f" rehomed={rehomed} excursions={excursions}"
        f" drained_load={drained_load}"
        f" ({wall:.1f}s, {d['req_per_s']:.0f} req/s)")
    return d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec")
    ap.add_argument("--minutes", type=int, default=60)
    ap.add_argument("--users", type=int, default=2000)
    # None (not 5.0) so --multi can tell "flag passed" from "default":
    # per-model TTLs come from the registry and must not be overridden.
    ap.add_argument("--ttl-min", type=float, default=None,
                    help="direct-cache TTL in minutes (default 5; "
                         "incompatible with --multi)")
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--chunk-steps", type=int, default=64,
                    help="serve steps per scan-driver dispatch "
                         "(serve_many, DESIGN.md §9)")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--coalesce", action="store_true",
                    help="in-batch inference coalescing: one tower run "
                         "per distinct missed user per batch "
                         "(DESIGN.md §9; incompatible with --no-cache/"
                         "--overload)")
    ap.add_argument("--multi", action="store_true",
                    help="serve the whole per-model registry as one "
                         "multi-model tier (mixed-model batches, one "
                         "dispatch per batch)")
    ap.add_argument("--overload", action="store_true",
                    help="SLA admission-control scenario: constrained "
                         "inference budget + mid-run re-access burst; "
                         "deferred misses degrade through the relaxed-TTL "
                         "failover tier (DESIGN.md §8)")
    ap.add_argument("--budget-frac", type=float, default=0.5,
                    help="--overload: inference budget as a fraction of "
                         "the stream's steady-state miss demand")
    ap.add_argument("--failure-burst-rate", type=float, default=None,
                    help="--overload: failure probability inside the "
                         "outage window (FailureInjector burst; default: "
                         "same as --failure-rate)")
    ap.add_argument("--restart", action="store_true",
                    help="kill/restore fault-injection harness: snapshot "
                         "at checkpoint boundaries, kill mid-stream, "
                         "restore same/grown/shrunk geometries and "
                         "compare hit-rate recovery vs a cold restart "
                         "(DESIGN.md §10)")
    ap.add_argument("--checkpoint-every", type=int, default=40,
                    help="--restart: serve steps between snapshots")
    ap.add_argument("--chaos", default=None,
                    choices=list(chaos_lib.PRESETS),
                    help="chaos engine (DESIGN.md §14): compile the named "
                         "multi-fault scenario into a device-resident "
                         "schedule and replay it against the multi-model "
                         "tier with retry/backoff, reporting the per-window "
                         "degradation ledger")
    ap.add_argument("--chaos-models", type=int, default=4,
                    help="--chaos: registry size for the fan-out")
    ap.add_argument("--chaos-steps", type=int, default=240,
                    help="--chaos: serve steps in the scenario horizon")
    ap.add_argument("--chaos-retries", type=int, default=2,
                    help="--chaos: max retry attempts per failed inference")
    ap.add_argument("--hedge-after-ms", type=float, default=25.0,
                    help="--chaos: straggler hedge deadline for the "
                         "p99-with/without-hedging report")
    ap.add_argument("--regions", type=int, default=None,
                    help="regional serving on device: stack N regions as a "
                         "leading axis over the cache tier, sticky routing "
                         "via a device-resident home table (DESIGN.md §13)")
    ap.add_argument("--drain", action="store_true",
                    help="--regions: drain one region mid-run (the Fig. 10 "
                         "drain test) — its users re-home lazily while a "
                         "flash crowd coincides with the window")
    ap.add_argument("--locality", type=float, default=0.98,
                    help="--regions: probability a request stays in its "
                         "home region (paper: 'good locality')")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--eviction", default="ttl", choices=["ttl", "lru"],
                    help="direct/failover victim order (paper §3.3); lru "
                         "enables access-recency touches (incompatible "
                         "with --multi: the registry sets it per model)")
    ap.add_argument("--multi-buckets", type=int, default=1 << 12,
                    help="per-model direct-cache buckets in --multi mode")
    ap.add_argument("--shards", type=int, default=1,
                    help="bucket-shard the cache tier across N devices "
                         "(DESIGN.md §11); on CPU the launcher re-execs "
                         "itself with "
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args()
    enable_compile_cache()
    if args.shards > 1:
        if args.restart or args.overload or args.no_cache:
            ap.error("--shards drives the plain/--multi serving modes")
        ensure_shard_devices(args.shards)
    if args.drain and args.regions is None:
        ap.error("--drain requires --regions")
    if args.chaos is not None:
        if (args.restart or args.overload or args.multi
                or args.regions is not None):
            ap.error("--chaos is its own scenario; drop "
                     "--restart/--overload/--multi/--regions")
        if args.no_cache or args.coalesce:
            ap.error("--chaos is a cache-tier scenario; drop "
                     "--no-cache/--coalesce")
        if args.shards > 1:
            ap.error("--chaos runs on one device; drop --shards")
        if args.eviction != "ttl":
            ap.error("--chaos fixes eviction=ttl (the scenario isolates "
                     "fault handling, not victim order)")
        run_serving_chaos(
            arch=args.arch, scenario=args.chaos,
            n_models=args.chaos_models, steps=args.chaos_steps,
            users=args.users, batch=args.batch,
            ttl_min=0.2 if args.ttl_min is None else args.ttl_min,
            backend=args.backend, chunk_steps=args.chunk_steps,
            max_retries=args.chaos_retries,
            hedge_after_ms=args.hedge_after_ms,
            checkpoint_every=args.checkpoint_every)
    elif args.regions is not None:
        if args.regions < 1:
            ap.error("--regions must be >= 1")
        if args.restart or args.overload or args.multi:
            ap.error("--regions drives the regional server; drop "
                     "--restart/--overload/--multi")
        if args.no_cache or args.coalesce:
            ap.error("--regions is a cache-tier scenario; drop "
                     "--no-cache/--coalesce")
        if args.shards > 1:
            ap.error("--regions stacks regions on one device; drop --shards")
        run_serving_regional(
            arch=args.arch, n_regions=args.regions, minutes=args.minutes,
            users=args.users, batch=args.batch,
            ttl_min=5.0 if args.ttl_min is None else args.ttl_min,
            locality=args.locality, drain=args.drain,
            backend=args.backend, eviction=args.eviction,
            chunk_steps=args.chunk_steps)
    elif args.restart:
        if args.multi or args.overload:
            ap.error("--restart drives the single-model server; drop "
                     "--multi/--overload")
        if args.no_cache or args.coalesce:
            ap.error("--restart is a cache-durability scenario; drop "
                     "--no-cache/--coalesce")
        run_serving_restart(
            arch=args.arch, users=args.users, batch=args.batch,
            ttl_min=5.0 if args.ttl_min is None else args.ttl_min,
            checkpoint_every=args.checkpoint_every, backend=args.backend,
            chunk_steps=args.chunk_steps)
    elif args.overload:
        if args.multi:
            ap.error("--overload drives the single-model server; the "
                     "multi-model registry sets budgets per model "
                     "(CacheConfig.infer_budget_per_step)")
        if args.no_cache:
            ap.error("--overload is a cache-tier scenario; drop --no-cache")
        if args.coalesce:
            ap.error("--overload isolates admission control; run "
                     "--coalesce on the plain/--multi modes")
        if args.eviction != "ttl":
            ap.error("--overload fixes eviction=ttl (the scenario "
                     "isolates admission, not victim order)")
        run_serving_overload(
            arch=args.arch, minutes=args.minutes, users=args.users,
            batch=args.batch,
            ttl_min=5.0 if args.ttl_min is None else args.ttl_min,
            budget_frac=args.budget_frac,
            failure_rate=args.failure_rate,
            failure_burst_rate=args.failure_burst_rate,
            backend=args.backend,
            chunk_steps=args.chunk_steps)
    elif args.multi:
        # fail loudly on flags the multi tier cannot honor: TTLs come from
        # the per-model registry and the tier has no cache-off baseline.
        if args.no_cache:
            ap.error("--no-cache has no multi-model baseline; drop --multi")
        if args.ttl_min is not None:
            ap.error("--ttl-min is per-model in --multi mode (see "
                     "docs/model_registry.md); it cannot be overridden")
        if args.eviction != "ttl":
            ap.error("--eviction is per-model in --multi mode (registry "
                     "second-stage models already run lru)")
        run_serving_multi(arch=args.arch, minutes=args.minutes,
                          users=args.users, batch=args.batch,
                          n_buckets=args.multi_buckets,
                          failure_rate=args.failure_rate,
                          backend=args.backend, coalesce=args.coalesce,
                          chunk_steps=args.chunk_steps,
                          n_shards=args.shards)
    else:
        if args.no_cache and args.coalesce:
            ap.error("--coalesce dedupes cache misses; drop --no-cache")
        run_serving(arch=args.arch, minutes=args.minutes, users=args.users,
                    ttl_min=5.0 if args.ttl_min is None else args.ttl_min,
                    failure_rate=args.failure_rate,
                    batch=args.batch, use_cache=not args.no_cache,
                    backend=args.backend, eviction=args.eviction,
                    coalesce=args.coalesce, chunk_steps=args.chunk_steps,
                    n_shards=args.shards)


if __name__ == "__main__":
    main()
