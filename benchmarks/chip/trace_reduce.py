"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is first turned into a plain dict, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, so that the
reduction runs the same on a trace read from the profiler's ``.xplane.pb``
and on the small recorded trace the tests keep.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run on the chip, their ``XLA Modules`` line one per program
run. Host spans are the benchmark's own ``TraceAnnotation`` events on the
host plane; ``window`` brackets the traced part of the run.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPANS = ("stage", "dispatch", "fetch")
INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
# ops that only contain other ops (a scan's loop): busy time, not op time
CONTAINERS = ("while", "conditional", "call")
TOP = 10


def load_xspace(path) -> dict:
    """The plain-dict form of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        lines = []
        for line in plane.lines:
            if plane.name == HOST_PLANE or line.name in (OPS_LINE,
                                                         MODULES_LINE):
                lines.append({"name": line.name, "events": [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _events(trace, plane_pred, line_name=None):
    for plane in trace["planes"]:
        if plane_pred(plane["name"]):
            for line in plane["lines"]:
                if line_name is None or line["name"] == line_name:
                    yield plane["name"], line["events"]


def window(trace):
    """(start_ns, end_ns) of the ``window`` host span."""
    for _, events in _events(trace, lambda n: n == HOST_PLANE):
        for name, start, dur in events:
            if name == "window":
                return start, start + dur
    raise ValueError("the trace holds no 'window' span")


def _instruction(event_name: str) -> str:
    """An op event's HLO instruction name: a TPU trace names each op by its
    whole instruction text (``%copy.206 = f32[...] copy(...)``)."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace, kernel_names=(), module_match="serve_many") -> dict:
    """Device busy time, idle gaps by host span, kernel and program time,
    the part of each host span not hidden under the serve program, and the
    operations that took most time, inside the ``window`` span.
    Times are in seconds; busy and kernel time are averaged over the chips
    whose ops appear."""
    t0, t1 = window(trace)
    kernels = set(kernel_names)

    def clip(start, dur):
        return max(start, t0), min(start + dur, t1)

    busy, kernel, module, ops = {}, {}, {}, {}
    runs, cut = 0, 0.0
    per_chip = {}
    for plane, events in _events(trace, DEVICE_PLANE.match, OPS_LINE):
        iv = []
        for name, start, dur in events:
            s, e = clip(start, dur)
            if e <= s:
                continue
            iv.append((s, e))
            name = _instruction(name)
            if name.split(".")[0] not in CONTAINERS:
                ops[name] = ops.get(name, 0.0) + (e - s)
            if name in kernels:
                kernel[plane] = kernel.get(plane, 0.0) + (e - s)
        per_chip[plane] = _union(iv)
        busy[plane] = sum(e - s for s, e in per_chip[plane])
    program = {}
    for plane, events in _events(trace, DEVICE_PLANE.match, MODULES_LINE):
        for name, start, dur in events:
            s, e = clip(start, dur)
            if e > s and module_match in name:
                module[plane] = module.get(plane, 0.0) + (e - s)
                program.setdefault(plane, []).append((s, e))
                runs += 1
                cut += dur - (e - s)

    spans = []
    for _, events in _events(trace, lambda n: n == HOST_PLANE):
        spans += [(s, s + d, n) for n, s, d in events
                  if n in SPANS and t0 <= s and s + d <= t1]
    gaps = {}
    chips = [c for c in per_chip if per_chip[c]]
    for plane in chips:
        edges = [t0] + [x for iv in per_chip[plane] for x in iv] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            owner = next((n for a, b, n in spans if a <= mid < b), "other")
            gaps[owner] = gaps.get(owner, 0.0) + (e - s) / len(chips)
    n = max(len(chips), 1)

    def exposed(a, b):
        """Seconds of [a, b) in which the serve program ran on no chip,
        averaged over the chips."""
        if not program:
            return (b - a) / 1e9
        hidden = sum(max(0.0, min(b, e) - max(a, s))
                     for iv in program.values() for s, e in _union(iv))
        return (b - a - hidden / len(program)) / 1e9

    def mean(d):
        return sum(d.values()) / n / 1e9

    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": mean(busy),
        "kernel_s": mean(kernel),
        "module_s": mean(module),
        # serve program runs that overlap the window, and the seconds of
        # them that fall outside it, per chip
        "module_runs": runs / n,
        "module_cut_s": cut / n / 1e9,
        "chips": len(chips),
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]],
        # the part of each span in which no serve program ran: what the
        # device's serve step waited for
        "host_spans_exposed": {name: [exposed(a, b) for a, b, n2 in spans
                                      if n2 == name] for name in SPANS},
    }


def trim(trace, keep_ns: float) -> dict:
    """The first ``keep_ns`` of the window, for a small recorded trace."""
    t0, _ = window(trace)
    t1 = t0 + keep_ns
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            ev = [[n, s, min(d, t1 - s)] for n, s, d in line["events"]
                  if s < t1 and s + d > t0]
            if plane["name"] == HOST_PLANE:
                ev = [[n, s, d] for n, s, d in ev
                      if n in SPANS or n == "window"]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def kernel_instructions(hlo_text: str):
    """Names of the Pallas kernels' custom-call instructions in a compiled
    program's HLO."""
    return [_instruction(line) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and INSTRUCTION.match(line)]
