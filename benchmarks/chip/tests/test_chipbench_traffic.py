"""The traffic generator and the cost functions: the stream is a function
of the seed, lands on the paper's Fig. 6 anchors, and the FLOP and byte
counts match hand counts."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import costs  # noqa: E402
from traffic.generator import T0_MS, Fig6Gaps, generate  # noqa: E402

TRAFFIC = json.loads((HERE / "traffic" / "fig6.backlog.json").read_text())
GAPS = Fig6Gaps(TRAFFIC["interarrival_knots_s"])
SMOKE = json.loads((HERE / "tests" / "data" / "smoke_registry.json")
                   .read_text())


def _features(rng, n):
    return {"seq": rng.integers(0, 7, (n, 3))}


def _stream(seed, config=SMOKE):
    traffic = dict(TRAFFIC, stream_rps=2000)
    return generate(traffic, config, seed, 1.0, _features)


def test_stream_is_a_function_of_the_seed():
    a, b, c = _stream(2 ** 33 + 1), _stream(2 ** 33 + 1), _stream(7)
    for k in ("keys", "times_ms", "slots", "fails", "pre_keys", "pre_ts"):
        assert np.array_equal(getattr(a, k), getattr(b, k))
    assert np.array_equal(a.features["seq"], b.features["seq"])
    assert not np.array_equal(a.keys, c.keys)


def test_stream_shape_and_clock():
    s = _stream(5)
    n_models = len(SMOKE["models"])
    assert len(s) % (16 * 256) == 0
    assert np.all(np.diff(s.times_ms) >= 0) and s.times_ms[0] >= T0_MS
    assert np.array_equal(s.slots[:2 * n_models],
                          np.tile(np.arange(n_models), 2))
    # one access fans out to every model, with one feature row
    assert np.all(s.keys[:n_models] == s.keys[0])
    assert np.all(np.diff(s.pre_ts) >= 0) and s.pre_ts[-1] <= T0_MS
    ttl = np.array([m["failover_ttl_ms"] for m in SMOKE["models"]])
    assert np.all(s.pre_ts >= T0_MS - ttl[s.pre_slots])


@pytest.mark.parametrize("t_s,cdf", [(60, 0.52), (600, 0.795),
                                     (3600, 0.956)])
def test_gaps_land_on_fig6_anchors(t_s, cdf):
    gaps = GAPS.sample(np.random.default_rng(0), 400_000)
    assert abs(np.mean(gaps <= t_s) - cdf) < 0.004
    assert GAPS.cdf(t_s) == pytest.approx(cdf)


def test_preloaded_share_is_the_stationary_age_law():
    """A user is preloaded when its age is within the failover TTL: for a
    stationary renewal process P(age <= h) = E[min(gap, h)] / E[gap]."""
    h = 3600.0
    t = np.geomspace(1e-3, h, 200_001)
    e_min = np.trapezoid(1.0 - GAPS.cdf(t), t)
    x = GAPS.sample_length_biased(np.random.default_rng(1), 400_000)
    age = np.random.default_rng(2).uniform(size=x.size) * x
    assert np.mean(age <= h) == pytest.approx(e_min / GAPS.mean_s, abs=0.003)


def test_flops_per_inference_hand_count():
    from system import load_module

    ref = load_module(HERE / "reference" / "sasrec.py")
    # D=4, S=3, one block: projections 2*3*16*4, scores and mixing 2*9*4*2,
    # feed-forward 2*3*16*2
    small = {"embed_dim": 4, "seq_len": 3, "n_blocks": 1}
    assert ref.flops_per_inference(small) == 384 + 144 + 192
    sasrec = {"embed_dim": 50, "seq_len": 50, "n_blocks": 2}
    assert ref.flops_per_inference(sasrec) == 4_000_000


def test_probe_bytes_hand_count():
    one = {"models": [{"ways": 2}], "tower": {"embed_dim": 4}}
    # per query: key 8 + per tier (bucket 4 + 2 ways x 12 + 4 x (3 + 4))
    assert costs.probe_bytes(one, 10, 3, 1) == 10 * 120 + 4 * 16
    two = {"models": [{"ways": 2}] * 2, "tower": {"embed_dim": 4}}
    assert costs.probe_bytes(two, 1, 0, 0) == 124


def test_readers_return_nothing_without_a_trace():
    import harness

    view = harness.RunView(config=one_cfg(), trace=None,
                           traced=[], peak={"bf16_flops": 1.0,
                                            "hbm_bytes_per_s": 1.0},
                           flops_per_inference=1, miss_budget=1)
    for name in ("stage_exposed_ms", "idle_share", "step_ms", "step_mfu",
                 "probe_roofline", "tower_waste"):
        assert harness.reader(name)(view) is None


def one_cfg():
    return {"models": [{"ways": 8}], "tower": {"embed_dim": 50}}
