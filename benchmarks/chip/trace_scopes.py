"""Device time by the serve program's own layer names.

The program runs its layers under ``jax.named_scope`` names, which the
compiled program keeps in each instruction's ``op_name``: ``ercache/probe``,
``ercache/admit``, ``ercache/tower``, ``ercache/serve``, ``ercache/flush``
(sub-scopes ``plan``, ``scatter``, ``touch``) and ``ercache/tail_flush``.
An instruction's scope is the outermost ``ercache/<layer>`` on its name
stack, with the flush sub-scope below it as ``<layer>/<sub>``. The
compiler's own instructions (layout copies, pads) carry no scope: each
takes the scope of its nearest user that has one, breadth-first within its
computation, else of its nearest operand; in a compiler's loop that names
nothing, the scope of the loop; else it stays ``unscoped``. The program's
host staging runs under the ``ercache/stage/rows`` and
``ercache/stage/put`` spans.

``reduce`` returns what ``trace_reduce.reduce`` returns, unchanged, and
adds ``scope_s``, ``serve_op_s``, ``unscoped_ops``, ``kernels_by_name``,
the staging spans in ``host_spans_exposed`` and ``idle_gaps_by_span``.

Run as a program it runs one cell traced, as ``run.py --trace 1`` does,
and prints each layer's device ms per serve step:

    python3 benchmarks/chip/trace_scopes.py --workload <cell> --seed <n> \
        --seconds <s> [--fixture <file.json.gz>]

``--fixture`` writes the first 1.2 s of the traced window with the text of
its compiled program, as the tests keep them.
"""
from __future__ import annotations

import bisect
import collections
import math
import re

import trace_reduce as tr

LAYER = "ercache"
FLUSH_PARTS = ("plan", "scatter", "touch")
PROGRAM_SPANS = ("ercache/stage/rows", "ercache/stage/put")
UNSCOPED = "unscoped"
FIXTURE_NS = 1.2e9

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_ATTRS = re.compile(r", (?:metadata|backend_config|frontend_attributes)=")


def scope_of(op_name: str):
    """``ercache/<layer>`` or ``ercache/<layer>/<sub>`` of an ``op_name``,
    or None. Its last part names the operation, not a scope."""
    parts = op_name.split("/")[:-1]
    starts = [i for i in range(len(parts) - 1) if parts[i] == LAYER]
    if not starts:
        return None
    layer = f"{LAYER}/{parts[starts[0] + 1]}"
    sub = next((p for p in parts[starts[-1] + 2:] if p in FLUSH_PARTS),
               None)
    return layer if sub is None else f"{layer}/{sub}"


def _parse(hlo_text: str):
    """Computations, in the text's order, as (name, root, [(instruction,
    scope, refs, fused computations called)]); the fused ones (a
    fusion's or an async operation's, which never run as operations of
    their own); and the Pallas kernels' instructions with their names."""
    comps, fused, kernels, cur = [], set(), {}, None
    for line in hlo_text.splitlines():
        if cur is None:
            if line and not line[0].isspace() and line.rstrip().endswith("{"):
                cur = []
                comps.append([line.split()[1 if line.startswith("ENTRY")
                                           else 0].lstrip("%"), None, cur])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = tr.INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        if line.lstrip().startswith("ROOT"):
            comps[-1][1] = name
        head = _ATTRS.split(rest, 1)[0]
        calls = _CALLS.findall(head)
        fused.update(calls)
        meta = _OP_NAME.search(line)
        op_name = meta.group(1) if meta else ""
        if 'custom_call_target="tpu_custom_call"' in line:
            parts = op_name.split("/")
            kernels[name] = (parts[-2] if len(parts) > 1
                             and parts[-1] == "pallas_call"
                             else name.rsplit(".", 1)[0])
        cur.append((name, scope_of(op_name), _REF.findall(head), calls))
    return comps, fused, kernels


def _nearest(start, edges, scope):
    """Scope of the nearest instruction reachable over ``edges``,
    breadth-first, or None."""
    seen, queue = {start}, collections.deque(edges.get(start, ()))
    while queue:
        n = queue.popleft()
        if n in seen:
            continue
        seen.add(n)
        if scope.get(n):
            return scope[n]
        queue.extend(edges.get(n, ()))
    return None


def program_map(hlo_text: str) -> dict:
    """``{"scopes": {instruction: scope}, "kernels": {instruction: kernel
    name}}`` for every instruction outside fused computations. A fusion
    whose own metadata names no scope takes its fused computation's: that
    of the root, else of the instruction nearest the root. The compiler's
    own loops (a relayout in chunks) name no scope anywhere in their
    computations: their instructions take the scope of the instruction
    that runs the loop."""
    comps, fused, kernels = _parse(hlo_text)
    names = {comp for comp, _, _ in comps}
    inner, graphs = {}, {}
    for comp, root, instrs in comps:
        own = {n: s or next(filter(None, map(inner.get, calls)), None)
               for n, s, _, calls in instrs}
        operands = {n: [r for r in refs if r in own and r != n]
                    for n, _, refs, _ in instrs}
        if comp in fused:
            inner[comp] = own.get(root) or _nearest(root, operands, own)
        else:
            graphs[comp] = own, operands, {
                n: [r for r in refs if r in names and r not in fused]
                for n, _, refs, _ in instrs}
    scopes, caller = {}, {}
    for comp in reversed(list(graphs)):       # callers before callees
        own, operands, called = graphs[comp]
        users = collections.defaultdict(list)
        for n in own:
            for r in operands[n]:
                users[r].append(n)
        fallback = None if any(own.values()) else caller.get(comp)
        for n in own:
            scopes[n] = (own[n] or _nearest(n, users, own)
                         or _nearest(n, operands, own) or fallback
                         or UNSCOPED)
            for c in called[n]:
                if scopes[n] != UNSCOPED:
                    caller.setdefault(c, scopes[n])
    return {"scopes": scopes, "kernels": kernels}


def _inside(runs, t):
    """Whether ``t`` falls in one of the sorted, disjoint [start, end]
    ``runs``."""
    i = bisect.bisect_right(runs, [t, math.inf]) - 1
    return i >= 0 and t < runs[i][1]


def reduce(trace, kernel_names=(), program=None,
           module_match="serve_many") -> dict:
    """``trace_reduce.reduce`` plus, averaged over the chips, the device
    seconds of the serve program's operations by scope (``scope_s``: each
    layer, each ``<layer>/<sub>`` part of it, and ``unscoped``; the layers
    and ``unscoped`` sum to ``serve_op_s``), the largest unscoped
    operations, the seconds of each Pallas kernel by name, the exposed
    part of each staging span and the idle gaps by the innermost host span
    that covers them."""
    out = tr.reduce(trace, kernel_names, module_match)
    program = program or {"scopes": {}, "kernels": {}}
    scopes, kernels = program["scopes"], program["kernels"]
    t0, t1 = tr.window(trace)
    n = max(out["chips"], 1)

    runs = {}
    for plane, events in tr._events(trace, tr.DEVICE_PLANE.match,
                                    tr.MODULES_LINE):
        iv = [(max(s, t0), min(s + d, t1)) for name, s, d in events
              if module_match in name and min(s + d, t1) > max(s, t0)]
        if iv:
            runs[plane] = tr._union(iv)
    scope_ns = collections.Counter()
    unscoped = collections.Counter()
    kernel_ns = collections.Counter()
    busy = {}
    for plane, events in tr._events(trace, tr.DEVICE_PLANE.match,
                                    tr.OPS_LINE):
        iv = []
        for name, start, dur in events:
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s:
                continue
            iv.append((s, e))
            inst = tr._instruction(name)
            if (inst.split(".")[0] in tr.CONTAINERS
                    or not _inside(runs.get(plane, []), (s + e) / 2)):
                continue
            scope = scopes.get(inst, UNSCOPED)
            layer = "/".join(scope.split("/")[:2])
            scope_ns[layer] += e - s
            if scope != layer:
                scope_ns[scope] += e - s
            if scope == UNSCOPED:
                unscoped[inst] += e - s
            if inst in kernels:
                kernel_ns[kernels[inst]] += e - s
        busy[plane] = tr._union(iv)

    spans = []
    for _, events in tr._events(trace, lambda p: p == tr.HOST_PLANE):
        spans += [(s, s + d, name) for name, s, d in events
                  if name in tr.SPANS + PROGRAM_SPANS
                  and t0 <= s and s + d <= t1]

    def exposed(a, b):
        hidden = sum(max(0.0, min(b, e) - max(a, s))
                     for iv in runs.values() for s, e in iv)
        return (b - a - hidden / max(len(runs), 1)) / 1e9

    gaps = collections.Counter()
    chips = [c for c in busy if busy[c]]
    for plane in chips:
        edges = [t0] + [x for iv in busy[plane] for x in iv] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            cover = [(b - a, name) for a, b, name in spans if a <= mid < b]
            gaps[min(cover)[1] if cover else "other"] += (e - s) / len(chips)

    def seconds(counter):
        return {k: v / n / 1e9 for k, v in counter.items()}

    out["scope_s"] = seconds(scope_ns)
    out["serve_op_s"] = sum(v for k, v in scope_ns.items()
                            if k.count("/") < 2) / n / 1e9
    out["unscoped_ops"] = [[k, v / n / 1e9]
                           for k, v in unscoped.most_common(tr.TOP)]
    out["kernels_by_name"] = seconds(kernel_ns)
    out["host_spans_exposed"] = dict(out["host_spans_exposed"], **{
        name: [exposed(a, b) for a, b, n2 in spans if n2 == name]
        for name in PROGRAM_SPANS})
    out["idle_gaps_by_span"] = [[k, v / 1e9] for k, v in gaps.most_common()]
    return out


def trim(trace, keep_ns: float) -> dict:
    """The first ``keep_ns`` of the window, each op named by its
    instruction, with the host spans both reducers read."""
    t0, _ = tr.window(trace)
    t1 = t0 + keep_ns
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            host = plane["name"] == tr.HOST_PLANE
            ev = [[n if host or line["name"] != tr.OPS_LINE
                   else tr._instruction(n), s, min(d, t1 - s)]
                  for n, s, d in line["events"] if s < t1 and s + d > t0
                  and (not host or n in tr.SPANS + PROGRAM_SPANS
                       or n == "window")]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def compact_hlo(hlo_text: str) -> str:
    """The compiled program's text with what ``program_map`` reads: no
    source tables, no backend configs, metadata cut to its ``op_name``."""
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:.+\n)*", "", hlo_text, flags=re.M)
    text = re.sub(r", backend_config=.*$", "", text, flags=re.M)
    text = re.sub(r',? metadata=\{(?!op_name=)[^}]*\}', "", text)
    return re.sub(r'metadata=\{(op_name="[^"]*")[^}]*\}', r"metadata={\1}",
                  text)


def summary(trace, hlo_text: str, traced, window, result: dict,
            setup_s: float) -> dict:
    """A traced run's layers per serve step: ``traced`` are the traced
    dispatches, ``window`` all of the window's."""
    program = program_map(hlo_text)
    r = reduce(trace, tr.kernel_instructions(hlo_text), program)
    steps = sum(d.steps for d in traced)

    def per_step(d):
        return {k: 1e3 * v / steps for k, v in sorted(d.items())}

    named = r["serve_op_s"] - r["scope_s"].get(UNSCOPED, 0.0)
    return {
        "correct": result["correct"], "setup_s": setup_s,
        "served_rps": (sum(d.n for d in window)
                       / (window[-1].end - window[0].start)),
        "steps": steps, "dispatches": len(traced),
        "step_ms": 1e3 * r["module_s"] / steps,
        "serve_op_ms": 1e3 * r["serve_op_s"] / steps,
        "scoped_share": named / r["serve_op_s"] if r["serve_op_s"] else 0.0,
        "scope_ms": per_step(r["scope_s"]),
        "kernels_ms": per_step(r["kernels_by_name"]),
        "unscoped_ops_ms": [[k, 1e3 * v / steps]
                            for k, v in r["unscoped_ops"]],
        "host_spans_exposed_ms": {
            k: 1e3 * sum(v) / len(traced)
            for k, v in r["host_spans_exposed"].items()},
        "idle_gaps": r["idle_gaps"],
        "idle_gaps_by_span": r["idle_gaps_by_span"],
        "device_ops": r["device_ops"],
        "metrics": result["metrics"],
    }


def main(argv=None) -> int:
    import argparse
    import gzip
    import json
    import os
    import pathlib
    import sys
    import time

    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)
    here = pathlib.Path(__file__).resolve().parent
    root = here.parent.parent
    sys.path.insert(0, str(root / "src"))
    import harness

    class Tracer(harness._Tracer):
        """The harness's tracer, keeping the compiled program's text and
        every dispatch of the window."""

        def start(self):
            self.setup_s = time.monotonic() - t_start
            self.window = []
            super().start()

        def fetched(self, d):
            self.window.append(d)
            super().fetched(d)

        def reduce(self, hlo_text):
            self.hlo_text = hlo_text
            return super().reduce(hlo_text)

    made = []
    harness._Tracer = lambda on: made.append(Tracer(on)) or made[-1]
    try:
        spec = harness.read_json(root / "BENCHMARK.json")
        harness.devices_and_peak(
            harness.load_cell(spec, args.workload)[0]["chips"])
        import jax

        # the compile cache as run.py keeps it
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(root / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        result, extra = harness.run_cell(spec, args.workload, args.seed,
                                         args.seconds, True, t_start)
    except harness.BenchError as e:
        print(f"trace_scopes.py: {e}", file=sys.stderr, flush=True)
        return 1
    t = made[0]
    if args.fixture:
        with gzip.open(args.fixture, "wt") as f:
            json.dump({"trace": trim(extra["trace"], FIXTURE_NS),
                       "kernels": tr.kernel_instructions(t.hlo_text),
                       "hlo": compact_hlo(t.hlo_text)}, f)
    print(json.dumps(summary(extra["trace"], t.hlo_text, t.traced, t.window,
                             result, t.setup_s)), flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
