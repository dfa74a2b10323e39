"""The system's SASRec user tower at the configuration's sizes, and the
features its requests carry: a user's item history of ``seq_len`` ids,
left-padded with -1, its length uniform from ``history_len_min`` up."""
from __future__ import annotations

import numpy as np


def program_tower(tower: dict):
    """``tower_fn(params, features) -> (B, embed_dim)`` of the system."""
    from repro.configs.base import RecsysConfig
    from repro.models import recsys

    cfg = RecsysConfig(arch_id="sasrec", interaction="self-attn-seq",
                       embed_dim=tower["embed_dim"],
                       n_blocks=tower["n_blocks"], n_heads=tower["n_heads"],
                       seq_len=tower["seq_len"], vocab=tower["vocab"])
    return lambda params, feats: recsys.tower_step(params, feats, cfg)


def features(rng: np.random.Generator, n: int, tower: dict,
             traffic: dict) -> dict:
    S, V = tower["seq_len"], tower["vocab"]
    lo = traffic.get("history_len_min", 1)
    length = rng.integers(lo, S + 1, n)
    ids = rng.integers(0, V, (n, S), dtype=np.int32)
    pad = np.arange(S)[None, :] < (S - length)[:, None]
    return {"seq": np.where(pad, np.int32(-1), ids)}
