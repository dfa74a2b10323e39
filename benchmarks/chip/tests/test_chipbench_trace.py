"""The trace reducer: busy time as the union of device op intervals inside
the traced window, idle gaps named by the host span that covers them, kernel
and program time, and the top operations; checked on a hand-made trace and
on a small trace recorded on a TPU v5e."""
import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402

RECORDED = HERE / "tests" / "data" / "trace_v5e_ctr_backlog.json.gz"


def _hand_trace():
    ms = 1e6
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["window", 0.0, 10 * ms],
            ["stage", 0.0, 2 * ms],
            ["fetch", 6 * ms, 3 * ms],
            ["stage", 20 * ms, 1 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 2 * ms, 2 * ms],
                ["custom-call.7", 3 * ms, 2 * ms],
                ["fusion.2", 9.5 * ms, 1 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_serve_many(123)", 2 * ms, 3 * ms],
                ["jit_other(1)", 9.5 * ms, 1 * ms]]}]}]}


def test_hand_made_trace():
    r = tr.reduce(_hand_trace(), kernel_names=["custom-call.7"])
    assert r["window_s"] == pytest.approx(0.010)
    # ops cover 2-5 ms and 9.5-10 ms (clipped to the window)
    assert r["busy_s"] == pytest.approx(0.0035)
    assert r["kernel_s"] == pytest.approx(0.002)
    assert r["module_s"] == pytest.approx(0.003)
    assert (r["module_runs"], r["module_cut_s"]) == (1, 0)
    gaps = dict(r["idle_gaps"])
    assert gaps["stage"] == pytest.approx(0.002)     # 0-2 ms
    assert gaps["fetch"] == pytest.approx(0.0045)    # 5-9.5 ms, mid in fetch
    assert r["device_ops"][0][0] in ("fusion.1", "custom-call.7")
    assert r["host_spans_exposed"]["stage"] == [pytest.approx(0.002)]


def test_program_runs_cut_by_the_window_are_counted():
    """A serve program run that the window cuts is named, so the harness can
    refuse a window whose programs are not the dispatches it counts."""
    t = _hand_trace()
    t["planes"][1]["lines"][1]["events"].append(
        ["jit_serve_many(123)", 8 * 1e6, 4 * 1e6])
    t["planes"][0]["lines"][0]["events"].append(["stage", 7 * 1e6, 2 * 1e6])
    r = tr.reduce(t)
    assert r["module_runs"] == 2
    assert r["module_cut_s"] == pytest.approx(0.002)
    assert r["module_s"] == pytest.approx(0.005)
    # staging under a running serve program is hidden; 7-8 ms is not
    assert r["host_spans_exposed"]["stage"] == [pytest.approx(0.002),
                                                pytest.approx(0.001)]


def test_kernel_instructions_from_hlo():
    hlo = ('  %custom-call.3 = (s32[1,256]{1,0}) custom-call(%a), '
           'custom_call_target="tpu_custom_call"\n'
           '  %fusion.9 = f32[2]{0} fusion(%b)\n'
           '  ROOT %custom-call.11 = f32[8] custom-call(%c), '
           'custom_call_target="tpu_custom_call"\n')
    assert tr.kernel_instructions(hlo) == ["custom-call.3", "custom-call.11"]


def test_trim_keeps_the_window_start():
    t = tr.trim(_hand_trace(), 4e6)
    assert tr.window(t) == (0.0, 4e6)
    assert tr.reduce(t)["busy_s"] == pytest.approx(0.002)


def test_recorded_v5e_trace():
    """First 0.12 s of a traced window of ctr-first.fig6.backlog on a TPU
    v5e: the serve program runs back to back, its time goes to whole-table
    copies and pads, and the probe kernel is found by its instruction."""
    data = json.loads(gzip.open(RECORDED).read())
    r = tr.reduce(data["trace"], data["kernels"])
    assert r["window_s"] == pytest.approx(0.12)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["kernel_s"] < r["busy_s"]
    assert r["module_s"] == pytest.approx(r["busy_s"], rel=0.01)
    names = [n for n, _ in r["device_ops"]]
    assert len(names) == tr.TOP and "_probe.9" in names
    assert not any(n.startswith("while") for n in names)
    assert sum(s for n, s in r["device_ops"][:5]) > 0.5 * r["busy_s"]
    assert dict(r["idle_gaps"]).get("stage", 0) > 0
