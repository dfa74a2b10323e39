"""The comparison that decides ``correct``, at a size a CPU test run holds:
a sound run is correct, and the control (the reference tower one precision
step down) is not."""
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import correct  # noqa: E402
import harness  # noqa: E402

SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold


def smoke_run(cell="ctr-first.fig6.backlog", config="smoke_config",
              fault=None, seconds=0.05, controls=()):
    spec = harness.read_json(ROOT / "BENCHMARK.json")
    cfg = harness.read_json(HERE / "tests" / "data" / f"{config}.json")
    traffic = harness.read_json(
        HERE / "traffic" / f"{harness.load_cell(spec, cell)[0]['traffic']}"
        f".json")
    traffic.update(stream_rps=400000)
    return harness.run_cell(spec, cell, SEED, seconds, False,
                            time.monotonic(), require_tpu=False, config=cfg,
                            traffic=traffic, fault=fault, log=lambda *a: None,
                            controls=controls)


@pytest.fixture(scope="module")
def sound():
    return smoke_run()


def test_sound_run_is_correct(sound):
    result, extra = sound
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    numbers = extra["numbers"]
    assert numbers["tower_inputs"] > 0 and numbers["fallbacks"] >= 0
    assert set(result["metrics"]) == {"served_rps", "setup_s"}


def test_registry_run_is_correct():
    result, _ = smoke_run("registry8.fig6.backlog", "smoke_registry")
    assert result["correct"], result["checks"]


def test_control_one_precision_down_fails_the_tower_gap():
    """The reference at three bfloat16 passes, put in the system's place,
    reads a tower gap above the limit on every seed; the system's tower
    reads one below it."""
    cfg = harness.read_json(HERE / "tests" / "data" / "smoke_config.json")
    tower, limit = cfg["tower"], cfg["tower_gap_limit"]
    from system import tower_modules

    adapter, ref = tower_modules(cfg)
    exact = jax.jit(lambda p, f: ref.forward(p, f, tower,
                                             jax.lax.Precision.HIGHEST))
    control = jax.jit(lambda p, f: ref.forward(p, f, tower, "bf16_3x"))
    program = jax.jit(adapter.program_tower(tower))
    for seed in range(3):
        params = ref.init_params(jax.random.PRNGKey(seed), tower)
        seq = adapter.features(np.random.default_rng(seed), 2048, tower, {})
        feats = {"seq": jnp.asarray(seq["seq"])}
        want = np.asarray(exact(params, feats))
        assert np.max(np.abs(np.asarray(control(params, feats)) - want)) \
            > limit
        assert np.max(np.abs(np.asarray(program(params, feats)) - want)) \
            < limit
    stream = type("Stream", (), {"features": seq})()
    assert correct.tower_gap(ref, params, tower, stream, np.arange(2048),
                             want, jax.lax.Precision.HIGHEST) == 0.0


def test_control_through_the_verdict_is_not_correct():
    """The control's outputs, swapped into the comparison in the system's
    place, read ``correct`` false: the control_gap is what the system's
    tower_gap would read had the control served those requests."""
    result, extra = smoke_run(controls=("bf16_3x",))
    n = extra["numbers"]
    cfg = harness.read_json(HERE / "tests" / "data" / "smoke_config.json")
    assert result["correct"]
    ok, rows = correct.verdict(dict(n, tower_gap=n["control_gap:bf16_3x"]),
                               cfg["tower_gap_limit"])
    assert not ok, rows
