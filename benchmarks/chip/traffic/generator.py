"""The one traffic generator: a cell's request stream from its traffic file,
its configuration and ``--seed``.

Users form a stationary superposition of renewal processes whose gaps follow
the ERCache paper's Fig. 6 inter-arrival CDF (piecewise log-linear between
knots, the calibration of ``repro.data.access_patterns.FIG6_KNOTS``, copied
here so that the yardstick does not move with the program). Each user's
process is sampled in steady state at the window's logical start ``T0``:

* the interval that straddles ``T0`` is drawn length-biased (inside a
  log-uniform segment, length bias makes the gap uniform on the segment, and
  the segments are weighted by their share of the mean gap);
* it is split uniformly into the age (time since the user's last access) and
  the residual (time to the next one);
* later gaps are plain draws.

A user whose last access lies within a model's failover TTL before ``T0``
is a cache entry at ``T0``: ``preload`` lists them, oldest first. The
window's requests follow in time order; each access fans out to every model
of the configuration (an ad request is ranked by every funnel stage).

Logical time (int32 ms from the stream's epoch) drives TTLs, so hit and
fallback rates do not depend on how fast the system runs.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# Logical start of the window: room before it for the longest failover TTL.
T0_MS = 4 * 3_600_000


@dataclasses.dataclass
class Stream:
    """Everything the program receives, as host arrays, plus what set-up
    preloads. Request arrays are in serve order."""

    uids: np.ndarray       # (N,) int64 user id of each request
    keys: np.ndarray       # (N,) int64 cache key of each request
    times_ms: np.ndarray   # (N,) int64 logical time
    slots: np.ndarray      # (N,) int32 model slot
    fails: np.ndarray      # (N,) bool injected tower failure
    features: dict         # name -> (N, ...) host array
    # preload records, oldest first: key, slot, logical write time
    pre_keys: np.ndarray
    pre_slots: np.ndarray
    pre_ts: np.ndarray
    logical_rps: float     # requests per logical second

    def __len__(self) -> int:
        return int(self.keys.shape[0])


def user_key(uids: np.ndarray) -> np.ndarray:
    """64-bit cache key of a user: both words non-zero, hi word >= 0."""
    return uids.astype(np.int64) * 2654435761 + 7


class Fig6Gaps:
    """Inverse-transform and length-biased sampling of the inter-arrival
    CDF given as ((t_s, F), ...) knots, log-linear in t between knots."""

    def __init__(self, knots):
        t = np.array([k[0] for k in knots], np.float64)
        f = np.array([k[1] for k in knots], np.float64)
        if not (np.all(np.diff(t) > 0) and np.all(np.diff(f) >= 0)
                and abs(f[-1] - 1.0) < 1e-9 and f[0] == 0.0):
            raise ValueError(f"knots are not a CDF from 0 to 1: {knots}")
        self.t, self.f = t, f
        mass = np.diff(f)
        a, b = t[:-1], t[1:]
        # mean gap contributed by each segment: mass * E[t | segment]
        self.seg_mean = mass * (b - a) / np.log(b / a)
        self.mean_s = float(self.seg_mean.sum())

    def sample(self, rng: np.random.Generator, n: int) -> np.ndarray:
        u = rng.uniform(0.0, 1.0, n)
        return np.exp(np.interp(u, self.f, np.log(self.t)))

    def sample_length_biased(self, rng: np.random.Generator, n: int
                             ) -> np.ndarray:
        seg = rng.choice(self.seg_mean.size, size=n,
                         p=self.seg_mean / self.mean_s)
        return rng.uniform(self.t[seg], self.t[seg + 1])

    def cdf(self, t_s) -> np.ndarray:
        x = np.log(np.clip(np.asarray(t_s, np.float64), 1e-12, None))
        return np.interp(x, np.log(self.t), self.f, left=0.0, right=1.0)


def stream_requests(traffic: dict, seconds: float) -> int:
    """Requests the stream must hold: the warm-up dispatch, and the window
    at ``stream_rps`` (ten times the measured rate)."""
    per_dispatch = traffic["steps_per_dispatch"] * traffic["batch"]
    rps = traffic["stream_rps"]
    return per_dispatch * (2 + math.ceil(rps * seconds / per_dispatch))


def generate(traffic: dict, config: dict, seed: int, seconds: float,
             features_fn) -> Stream:
    """The stream of one run. ``features_fn(rng, n)`` draws the tower's
    features for ``n`` user accesses (the tower's own traffic model)."""
    models = config["models"]
    n_models = len(models)
    gaps = Fig6Gaps(traffic["interarrival_knots_s"])
    n_users = int(config["users"])
    need = stream_requests(traffic, seconds)
    need_accesses = -(-need // n_models)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))

    straddle = gaps.sample_length_biased(rng, n_users)
    age = rng.uniform(0.0, 1.0, n_users) * straddle
    first = straddle - age                      # time to the next access

    # preload: per model, users whose last access is within its failover TTL
    fo_ttl_s = np.array([m["failover_ttl_ms"] for m in models]) / 1e3
    recent = age[:, None] <= fo_ttl_s[None, :]  # (U, M)
    pu, pm = np.nonzero(recent)
    pre_t = T0_MS - np.round(age[pu] * 1e3).astype(np.int64)
    order = np.lexsort((pm, pre_t))
    pre_keys = user_key(pu[order])
    pre_slots = pm[order].astype(np.int32)
    pre_ts = pre_t[order]

    # window: the logical span whose expected accesses cover the need,
    # with room for the variance; a run that still runs out fails loudly
    access_rate = n_users / gaps.mean_s
    span_s = 1.2 * need_accesses / access_rate + 1.0
    t_list, u_list = [], []
    users = np.nonzero(first < span_s)[0]
    t = first[users]
    while users.size:
        t_list.append(t)
        u_list.append(users)
        t = t + gaps.sample(rng, users.size)
        keep = t < span_s
        users, t = users[keep], t[keep]
    acc_t = np.concatenate(t_list)
    acc_u = np.concatenate(u_list)
    order = np.argsort(acc_t, kind="stable")
    acc_t = T0_MS + np.round(acc_t[order] * 1e3).astype(np.int64)
    acc_u = acc_u[order]
    if acc_u.size < need_accesses:
        raise RuntimeError(f"stream holds {acc_u.size} accesses, "
                           f"{need_accesses} needed")
    acc_t, acc_u = acc_t[:need_accesses], acc_u[:need_accesses]
    if acc_t[-1] >= 2 ** 31 - 1:
        raise RuntimeError("logical clock overflows int32")

    feats = features_fn(rng, acc_u.size)
    n = acc_u.size * n_models
    uids = np.repeat(acc_u, n_models)
    fails = rng.uniform(0.0, 1.0, n) < config["failure_rate"]
    return Stream(
        uids=uids, keys=user_key(uids), times_ms=np.repeat(acc_t, n_models),
        slots=np.tile(np.arange(n_models, dtype=np.int32), acc_u.size),
        fails=fails,
        features={k: np.repeat(v, n_models, axis=0) for k, v in feats.items()},
        pre_keys=pre_keys, pre_slots=pre_slots, pre_ts=pre_ts,
        logical_rps=n_models * access_rate)
