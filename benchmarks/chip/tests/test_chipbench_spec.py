"""BENCHMARK.json and the files it names: every configuration, traffic mix
and per-layer metric reader is found by name and parsed, the names and units
keep to the contract, and a host without the chips gets no result."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_every_config_file_is_found_and_states_its_cut():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] and cfg["assumed"] and cfg["deployment"]
        from system import tower_modules

        adapter, ref = tower_modules(cfg)
        assert callable(adapter.program_tower) and callable(ref.forward)


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_every_cell_resolves_and_reports_its_metrics(cell):
    c, config, traffic = harness.load_cell(SPEC, cell)
    assert NAME.match(cell) and NAME.match(c["traffic"])
    assert c["chips"] in (1, 4) and 0 < len(c["why"]) <= 200
    assert traffic["arrivals"] == "backlog"
    e2e = {m["name"] for m in harness.cell_metrics(SPEC, cell,
                                                    "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.cell_metrics(SPEC, cell, "per_layer")
    assert per_layer and all(m["moves"] in e2e for m in per_layer)


def test_metrics_keep_to_the_contract():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {c["name"] for c in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(harness.reader(m["name"]))
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reporting
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_unknown_device_kind_is_refused(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("TPU v99")])
    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.devices_and_peak(1)
    rc = run.main(["--workload", "ctr-first.fig6.backlog", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_too_few_chips_are_refused(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("TPU v5 lite")])
    with pytest.raises(harness.BenchError, match="needs 4"):
        harness.devices_and_peak(4)
    assert harness.devices_and_peak(1)[1]["hbm_bytes_per_s"] == 819e9


def test_cpu_only_host_gets_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "ctr-first.fig6.backlog", "--seed",
                        "3000000000", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_get_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    r = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "ctr-first.fig6.backlog", "--seed",
                        "1", "--seconds", "1"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
