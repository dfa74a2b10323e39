"""Device time by the serve program's layer scopes: the instruction-to-
scope map of a compiled program, the attribution of the compiler's unscoped
instructions, and the reduction that adds to ``trace_reduce``'s; checked on
a hand-made program and trace and on traces recorded on a TPU v5e."""
import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402
import trace_scopes as ts  # noqa: E402

DATA = HERE / "tests" / "data"
RECORDED = DATA / "trace_v5e_ctr_backlog.json.gz"
RECORDED_SCOPES = {name: DATA / f"trace_v5e_{name}_scopes.json.gz"
                   for name in ("ctr_backlog", "registry8_backlog")}
BODY = "jit(serve_many)/while/body/closed_call"

HLO = f"""HloModule jit_serve_many, is_scheduled=true

%fused_computation.1 (param_0: f32[8,8], param_1: s32[8]) -> f32[8,4] {{
  %param_0 = f32[8,8]{{1,0}} parameter(0)
  %param_1 = s32[8]{{0}} parameter(1)
  ROOT %scatter.3 = f32[8,4]{{1,0}} scatter(%param_0, %param_1), metadata={{op_name="{BODY}/ercache/flush/scatter/scatter"}}
}}

%relayout.4 (q: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {{
  %q = (s32[], f32[8,4]{{1,0}}) parameter(0)
  %get-tuple-element.30 = s32[] get-tuple-element(%q), index=0
  %get-tuple-element.31 = f32[8,4]{{1,0}} get-tuple-element(%q), index=1
  %dynamic-update-slice.32 = f32[8,4]{{0,1}} dynamic-update-slice(%get-tuple-element.31, %get-tuple-element.31, %get-tuple-element.30)
  ROOT %tuple.33 = (s32[], f32[8,4]{{0,1}}) tuple(%get-tuple-element.30, %dynamic-update-slice.32)
}}

%body.2 (p: (f32[8,4], s32[8])) -> (f32[8,4], f32[8,4], s32[1,256]) {{
  %p = (f32[8,4]{{1,0}}, s32[8]{{0}}) parameter(0)
  %get-tuple-element.1 = f32[8,4]{{1,0}} get-tuple-element(%p), index=0
  %get-tuple-element.2 = s32[8]{{0}} get-tuple-element(%p), index=1
  %tuple.13 = (s32[], f32[8,4]{{1,0}}) tuple(%get-tuple-element.2, %get-tuple-element.1)
  %while.14 = (s32[], f32[8,4]{{0,1}}) while(%tuple.13), condition=%cond.3, body=%relayout.4, metadata={{op_name="jit(serve_many)/while"}}
  %get-tuple-element.15 = f32[8,4]{{0,1}} get-tuple-element(%while.14), index=1
  %pad.5 = f32[8,8]{{1,0}} pad(%get-tuple-element.15, %get-tuple-element.1), padding=0_0x0_4
  %copy.6 = f32[8,8]{{0,1}} copy(%pad.5)
  %fusion.7 = f32[8,4]{{1,0}} fusion(%copy.6, %get-tuple-element.2), kind=kLoop, calls=%fused_computation.1
  %copy.8 = f32[8,4]{{0,1}} copy(%get-tuple-element.1)
  %ercache_probe_dual.9 = (s32[1,256]{{1,0}}) custom-call(%get-tuple-element.2), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/ercache/probe/jit(_probe)/ercache_probe_dual/pallas_call"}}, backend_config={{"body":"x"}}
  %get-tuple-element.11 = s32[1,256]{{1,0}} get-tuple-element(%ercache_probe_dual.9), index=0
  %copy.12 = s32[1,256]{{0,1}} copy(%get-tuple-element.11)
  ROOT %tuple.10 = (f32[8,4]{{1,0}}, f32[8,4]{{0,1}}, s32[1,256]{{0,1}}) tuple(%fusion.7, %copy.8, %copy.12)
}}

ENTRY %main.20 (a: f32[8,4], b: s32[8]) -> f32[8,4] {{
  %a = f32[8,4]{{1,0}} parameter(0)
  %b = s32[8]{{0}} parameter(1)
  %tuple.21 = (f32[8,4]{{1,0}}, s32[8]{{0}}) tuple(%a, %b)
  %while.22 = (f32[8,4]{{1,0}}, f32[8,4]{{0,1}}, s32[1,256]{{0,1}}) while(%tuple.21), condition=%cond.3, body=%body.2, metadata={{op_name="jit(serve_many)/while"}}
  %get-tuple-element.23 = f32[8,4]{{1,0}} get-tuple-element(%while.22), index=0
  ROOT %sort.24 = f32[8,4]{{1,0}} sort(%get-tuple-element.23), dimensions={{0}}, metadata={{op_name="jit(serve_many)/ercache/tail_flush/ercache/flush/plan/sort"}}
}}
"""


def _keeps(r, base):
    """Every key trace_reduce gives reads the same; the spans it reads
    keep their numbers beside the program's."""
    for key, value in base.items():
        if key == "host_spans_exposed":
            assert {k: r[key][k] for k in value} == value
        else:
            assert r[key] == value, key


def _trace():
    ms = 1e6
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["window", 0.0, 10 * ms],
            ["stage", 0.0, 1 * ms],
            ["ercache/stage/rows", 0.0, 0.4 * ms],
            ["ercache/stage/put", 0.4 * ms, 0.6 * ms],
            ["dispatch", 1 * ms, 0.1 * ms],
            ["fetch", 8 * ms, 2 * ms]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%while.22 = (f32[8,4]) while(%tuple.21)", 1 * ms, 6 * ms],
                ["%pad.5 = f32[8,8]{1,0} pad(...)", 1 * ms, 1 * ms],
                ["copy.6", 2 * ms, 1 * ms],
                ["fusion.7", 3 * ms, 2 * ms],
                ["ercache_probe_dual.9", 5 * ms, 1 * ms],
                ["copy.12", 6 * ms, 0.5 * ms],
                ["copy.8", 6.5 * ms, 0.5 * ms],
                ["sort.24", 7 * ms, 0.5 * ms],
                # a staging program's op, outside the serve program
                ["broadcast.1", 9.5 * ms, 0.5 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_serve_many(7)", 1 * ms, 6.5 * ms],
                ["jit_broadcast_in_dim(3)", 9.5 * ms, 0.5 * ms]]}]}]}


@pytest.mark.parametrize("op_name,scope", [
    (f"{BODY}/ercache/probe/jit(_probe)/ercache_probe_dual/pallas_call",
     "ercache/probe"),
    (f"{BODY}/ercache/flush/plan/jit(_dedupe)/sort", "ercache/flush/plan"),
    (f"{BODY}/ercache/flush/scatter", "ercache/flush"),
    ("jit(serve_many)/ercache/tail_flush/ercache/flush/touch/scatter",
     "ercache/tail_flush/touch"),
    (f"{BODY}/gather", None),
    ("", None),
])
def test_scope_of_an_op_name(op_name, scope):
    """The last part of an op_name is the operation, not a scope: a scatter
    directly under the flush is the flush's, not its sub-scope."""
    assert ts.scope_of(op_name) == scope


def test_unscoped_instructions_take_their_neighbours_scope():
    m = ts.program_map(HLO)
    s = m["scopes"]
    # a fusion takes its fused computation's scope
    assert s["fusion.7"] == "ercache/flush/scatter"
    # the pad and the relayout copy feed the scatter: the flush's
    assert s["pad.5"] == s["copy.6"] == "ercache/flush/scatter"
    # no user has a scope: the nearest operand's
    assert s["copy.12"] == "ercache/probe"
    # neither users nor operands have one
    assert s["copy.8"] == ts.UNSCOPED
    # a compiler's loop names no scope anywhere: its instructions take the
    # scope of the loop, which feeds the scatter
    assert s["while.14"] == "ercache/flush/scatter"
    assert s["dynamic-update-slice.32"] == "ercache/flush/scatter"
    # the tail flush is its own layer, the flush inside it its part
    assert s["sort.24"] == "ercache/tail_flush/plan"
    # fused computations are not mapped: their ops never run alone
    assert "scatter.3" not in s
    assert m["kernels"] == {"ercache_probe_dual.9": "ercache_probe_dual"}


def test_reduce_adds_scopes_spans_and_kernels():
    trace = _trace()
    program = ts.program_map(HLO)
    r = ts.reduce(trace, ["ercache_probe_dual.9"], program)
    _keeps(r, tr.reduce(trace, ["ercache_probe_dual.9"]))
    ms = 1e-3
    sc = r["scope_s"]
    assert sc["ercache/flush"] == pytest.approx(4 * ms)
    assert sc["ercache/flush/scatter"] == pytest.approx(4 * ms)
    assert sc["ercache/probe"] == pytest.approx(1.5 * ms)
    assert sc["ercache/tail_flush"] == pytest.approx(0.5 * ms)
    assert sc["ercache/tail_flush/plan"] == pytest.approx(0.5 * ms)
    assert sc[ts.UNSCOPED] == pytest.approx(0.5 * ms)
    # the while loop holds ops; the staging program's op is not the serve
    # program's: neither counts
    assert r["serve_op_s"] == pytest.approx(6.5 * ms)
    assert r["unscoped_ops"] == [["copy.8", pytest.approx(0.5 * ms)]]
    assert r["kernels_by_name"] == {"ercache_probe_dual": pytest.approx(ms)}
    assert r["host_spans_exposed"]["ercache/stage/rows"] == [
        pytest.approx(0.4 * ms)]
    assert r["host_spans_exposed"]["ercache/stage/put"] == [
        pytest.approx(0.6 * ms)]
    # the first gap is inside staging: the innermost span is the program's
    # put; the idle device under the fetch stays the fetch's
    assert dict(r["idle_gaps"]) == {"stage": pytest.approx(ms),
                                    "fetch": pytest.approx(2 * ms)}
    assert dict(r["idle_gaps_by_span"]) == {
        "ercache/stage/put": pytest.approx(ms),
        "fetch": pytest.approx(2 * ms)}


def test_reduce_without_a_program_map():
    r = ts.reduce(_trace())
    assert r["scope_s"] == {ts.UNSCOPED: pytest.approx(6.5e-3)}
    assert r["kernels_by_name"] == {}


def test_recorded_trace_keeps_every_key_trace_reduce_gives():
    """The trace recorded before the program had scopes reads as it did
    through trace_reduce, and its serve program is all unscoped."""
    data = json.loads(gzip.open(RECORDED).read())
    r = ts.reduce(data["trace"], data["kernels"])
    _keeps(r, tr.reduce(data["trace"], data["kernels"]))
    assert set(r["scope_s"]) == {ts.UNSCOPED}
    assert r["serve_op_s"] == pytest.approx(r["module_s"], rel=0.02)


@pytest.mark.parametrize("name", sorted(RECORDED_SCOPES))
def test_recorded_scoped_trace(name):
    """The first 1.2 s of a traced window on a TPU v5e, with the text of
    its compiled program: the named scopes hold at least 95% of the serve
    program's op time, the flush the most, and the probe kernel shows
    under its stable name."""
    data = json.loads(gzip.open(RECORDED_SCOPES[name]).read())
    r = ts.reduce(data["trace"], data["kernels"],
                  ts.program_map(data["hlo"]))
    _keeps(r, tr.reduce(data["trace"], data["kernels"]))
    sc = r["scope_s"]
    layers = {k: v for k, v in sc.items() if k.count("/") < 2}
    assert sum(layers.values()) == pytest.approx(r["serve_op_s"])
    assert sc.get(ts.UNSCOPED, 0.0) < 0.05 * r["serve_op_s"]
    assert r["serve_op_s"] == pytest.approx(r["module_s"], rel=0.02)
    assert max(layers, key=layers.get) == "ercache/flush"
    for layer in ("ercache/probe", "ercache/admit", "ercache/tower",
                  "ercache/serve", "ercache/tail_flush"):
        assert sc.get(layer, 0.0) > 0, layer
    kernel = ("ercache_probe_dual_multi" if "registry8" in name
              else "ercache_probe_dual")
    assert set(r["kernels_by_name"]) == {kernel}
    assert r["kernels_by_name"][kernel] == pytest.approx(r["kernel_s"])
    assert all(r["host_spans_exposed"][s] for s in ts.PROGRAM_SPANS)
