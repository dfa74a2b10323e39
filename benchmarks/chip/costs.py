"""Operations and bytes the benchmark counts for the system's work, from the
configuration's sizes and the program's counters alone, whatever
implements it."""
from __future__ import annotations

META_BYTES_PER_WAY = 12     # key hi, key lo, write time: three int32
OUT_WORDS = 3               # hit flag, age, way: int32 each, per tier


def probe_bytes(config: dict, requests: int, direct_hits: int,
                failover_serves: int) -> int:
    """Least HBM bytes a dual probe of ``requests`` queries moves: each
    query's key (and model slot) and two bucket indices in; per tier its
    bucket's metadata, and its results out (flags and a value row); plus the
    value row of each hit that is served (direct hits, failover serves)."""
    ways = config["models"][0]["ways"]
    D = config["tower"]["embed_dim"]
    multi = len(config["models"]) > 1
    per_query = 8 + (4 if multi else 0) + 2 * (
        4 + ways * META_BYTES_PER_WAY + 4 * (OUT_WORDS + D))
    return requests * per_query + (direct_hits + failover_serves) * D * 4
