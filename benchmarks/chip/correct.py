"""The comparison that decides ``correct``: what the timed window served and
what the tables hold afterwards, against the plain references.

Every number compared has a limit (``LIMITS`` holds the exact ones, the
configuration file the tolerance of the tower):

* ``source_mismatch``, ``age_mismatch``: requests whose provenance (direct
  hit, tower, failover, default) or age differs from the reference replay;
* ``counter_mismatch``: counters of a dispatch that differ from the replay;
* ``table_mismatch``: slots of either tier whose key, write time or
  last-access time differs after the window;
* ``value_mismatch``: served cache values (direct or failover hit), default
  embeddings and table values that are not, bit for bit, the value of the
  origin the replay names: a preload value, or the tower output the system
  served to the request that wrote it;
* ``unanswered``: requests dispatched whose answer never reached the host;
* ``tower_gap``: the largest absolute difference between a tower output the
  system served and the reference tower on the same features, over every
  request served by the tower.
"""
from __future__ import annotations

import numpy as np

from reference.ercache import COMPUTED, FALLBACK, Reference

LIMITS = {"source_mismatch": 0, "age_mismatch": 0, "counter_mismatch": 0,
          "table_mismatch": 0, "value_mismatch": 0, "unanswered": 0}
REF_TOWER_ROWS = 2048          # rows per block of the reference tower


def key64(hi, lo) -> np.ndarray:
    return (np.asarray(hi, np.int64) << 32) | (np.asarray(lo, np.int64)
                                                & 0xFFFFFFFF)


def replay(config, stream, miss_budget, preload_batch, dispatches):
    """Run the reference over the preload and every dispatched step."""
    ref = Reference(config["models"], miss_budget)
    n = stream.pre_keys.size
    for a in range(0, n, preload_batch):
        b = min(a + preload_batch, n)
        ref.put(stream.pre_keys[a:b], stream.pre_slots[a:b].astype(
            np.int64), stream.pre_ts[a:b], np.arange(a, b),
            int(stream.pre_ts[b - 1]))
    B = dispatches[0].n // dispatches[0].steps
    total = dispatches[-1].lo + dispatches[-1].n
    source = np.full(total, -9, np.int64)
    age = np.full(total, -9, np.int64)
    origin = np.full(total, -1, np.int64)
    counters = []
    for d in dispatches:
        acc = {}
        for s in range(d.steps):
            a = d.lo + s * B
            now = int(stream.times_ms[a + B - 1])
            src, ag, org, c = ref.serve_batch(
                stream.keys[a:a + B], stream.slots[a:a + B].astype(np.int64),
                stream.fails[a:a + B], now, first_request=n + a)
            source[a:a + B], age[a:a + B], origin[a:a + B] = src, ag, org
            for k, v in c.items():
                acc[k] = acc.get(k, 0) + v
        counters.append(acc)
    return ref, source, age, origin, counters


def tower_gap(ref_tower, params, tower, stream, idx, served, precision):
    """Largest |served - reference| over the requests ``idx``."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, f: ref_tower.forward(p, f, tower, precision))
    gap = 0.0
    for a in range(0, idx.size, REF_TOWER_ROWS):
        rows = idx[a:a + REF_TOWER_ROWS]
        pad = REF_TOWER_ROWS - rows.size
        feats = {k: jnp.asarray(np.pad(v[rows], [(0, pad)] + [(0, 0)] * (
            v.ndim - 1))) for k, v in stream.features.items()}
        want = np.asarray(jax.device_get(fwd(params, feats)))[:rows.size]
        gap = max(gap, float(np.max(np.abs(served[a:a + rows.size] - want))))
    return gap


def compare(config, stream, system, dispatches, tables, preload_values,
            controls=()):
    """All compared numbers, each as (value, limit). ``preload_values(ids)``
    regenerates preload values; ``tables`` are the system's final tiers.
    ``controls`` (the control experiment only) lists lower precisions of the
    reference tower to read, in the system's place, as ``control_gap:<p>``
    over the same requests."""
    import jax

    lo0 = dispatches[0].lo
    total = dispatches[-1].lo + dispatches[-1].n
    n_pre = stream.pre_keys.size
    got_src = np.full(total, -1, np.int64)
    got_age = np.full(total, -1, np.int64)
    emb = None
    unanswered = 0
    for d in dispatches:
        if d.out is None:
            unanswered += d.n
            continue
        e, s, a = d.out
        e = np.asarray(e).reshape(d.n, -1)
        if emb is None:
            emb = np.zeros((total, e.shape[1]), e.dtype)
        emb[d.lo:d.lo + d.n] = e
        got_src[d.lo:d.lo + d.n] = np.asarray(s).reshape(-1)
        got_age[d.lo:d.lo + d.n] = np.asarray(a).reshape(-1)

    from system import PRELOAD_BATCH

    ref, source, age, origin, counters = replay(
        config, stream, system.miss_budget, PRELOAD_BATCH, dispatches)
    win = slice(lo0, total)
    out = {"source_mismatch": int(np.sum(got_src[win] != source[win])),
           "age_mismatch": int(np.sum(got_age[win] != age[win])),
           "unanswered": unanswered}

    bad = 0
    for d, want in zip(dispatches, counters):
        if d.acc is None:
            bad += 1
            continue
        for k, v in want.items():
            if not np.array_equal(np.asarray(d.acc[k]), np.asarray(v)):
                bad += 1
    out["counter_mismatch"] = bad

    def expected(orig):
        """Values the origins name: preload values, served tower outputs of
        window requests, zeros for none."""
        vals = np.zeros((orig.size, emb.shape[1]), emb.dtype)
        pre = (orig >= 0) & (orig < n_pre)
        if pre.any():
            vals[pre] = preload_values(orig[pre])
        win_o = orig >= n_pre
        vals[win_o] = emb[orig[win_o] - n_pre]
        return vals

    req = np.arange(lo0, total)
    # hits serve what their origin wrote; a fall-through serves zeros
    cached = req[(source[win] != COMPUTED)]
    vbad = int(np.sum(np.any(emb[cached].view(np.int32)
                             != expected(origin[cached]).view(np.int32),
                             axis=1)))
    tbad = 0
    for name in ("direct", "failover"):
        t, r = tables[name], getattr(ref, name)
        tbad += int(np.sum(key64(t["key_hi"], t["key_lo"]) != r.key))
        tbad += int(np.sum(t["write_ts"] != r.ts))
        tbad += int(np.sum(t["last_access_ts"] != r.last))
        orig = r.origin.reshape(-1)[t["values_at"]]
        vbad += int(np.sum(np.any(t["values"].view(np.int32)
                                  != expected(orig).view(np.int32), axis=1)))
    out["table_mismatch"] = tbad
    out["value_mismatch"] = vbad

    computed = req[source[win] == COMPUTED]
    out["tower_inputs"] = int(computed.size)
    out["tower_gap"] = tower_gap(
        system.reference_tower, system.params, config["tower"], stream,
        computed, emb[computed], jax.lax.Precision.HIGHEST)
    out["fallbacks"] = int(np.sum(source[win] == FALLBACK))
    for p in controls:
        ref_tower, tower = system.reference_tower, config["tower"]
        lower = np.zeros_like(emb[computed])
        fwd = jax.jit(lambda prm, f: ref_tower.forward(prm, f, tower, p))
        for a in range(0, computed.size, REF_TOWER_ROWS):
            rows = computed[a:a + REF_TOWER_ROWS]
            feats = {k: v[rows] for k, v in stream.features.items()}
            lower[a:a + rows.size] = np.asarray(jax.device_get(
                fwd(system.params, feats)))
        out[f"control_gap:{p}"] = tower_gap(
            ref_tower, system.params, tower, stream, computed, lower,
            jax.lax.Precision.HIGHEST)
    return out


def verdict(numbers: dict, tower_limit: float):
    """(correct, [(name, value, limit)]) of the compared numbers."""
    rows = [(k, numbers[k], v) for k, v in LIMITS.items()]
    rows.append(("tower_gap", numbers["tower_gap"], tower_limit))
    ok = all(v <= lim for _, v, lim in rows) and numbers["tower_inputs"] > 0
    return ok, rows
