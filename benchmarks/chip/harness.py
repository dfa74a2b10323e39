"""One run of one cell: set-up, the measured window, the trace, the
comparison with the references, and the result line.

Everything a cell needs is found by name: its configuration in
``configs/<config>.json`` (with the tower's adapter ``towers/<arch>.py`` and
plain reference ``reference/<arch>.py``), its traffic in
``traffic/<traffic>.json``, and each per-layer metric's reader in
``metrics/<name up to its first dot>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
TRACE_SECONDS = 3.0        # traced part of a --trace 1 window
TABLE_VALUE_SAMPLE = 4096  # preloaded slots whose values are compared


class BenchError(RuntimeError):
    """A run that cannot give a result: no result line, exit code 1."""


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"{path} is missing")
    return json.loads(path.read_text())


def load_cell(spec: dict, name: str):
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = read_json(HERE.parent.parent / configs[cell["config"]]["file"])
    traffic = read_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def cell_metrics(spec: dict, cell: str, group: str):
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader(metric_name: str):
    from system import load_module

    return load_module(HERE / "metrics" /
                       f"{metric_name.split('.')[0]}.py").read


def devices_and_peak(chips: int):
    """The chips and their peaks; refuses a host without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX finds no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips; JAX finds "
                         f"{len(devices)}")
    peaks = read_json(HERE / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return devices, peaks[kind]


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read."""

    config: dict
    trace: dict
    traced: list
    peak: dict
    flops_per_inference: int
    miss_budget: int


class _Tracer:
    """Profiles whole dispatches from the first of the window until
    TRACE_SECONDS have passed: the ``window`` span opens as the first
    dispatch is launched and closes once the outputs of the last traced
    one are on the host, before the next is launched. So the device work
    inside the span is that of exactly the traced dispatches (and the
    staging of the next one, which runs under them)."""

    def __init__(self, on: bool):
        self.on, self.dir, self.span = on, None, None
        self.traced, self.active = [], False

    def start(self):
        """Starts the profiler, before the window."""
        if not self.on:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.active = True

    def launching(self, d):
        if self.active and self.span is None:
            import jax

            self.span = jax.profiler.TraceAnnotation("window")
            self.span.__enter__()
            self.t0 = time.perf_counter()

    def fetched(self, d):
        if not self.active or self.span is None:
            return
        self.traced.append(d)
        if time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        if self.active:
            import jax

            if self.span is not None:
                self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False

    def reduce(self, hlo_text: str):
        import trace_reduce as tr

        self.stop()
        try:
            files = list(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            if not files:
                raise BenchError("the profiler wrote no trace")
            trace = tr.load_xspace(files[0])
            kernels = tr.kernel_instructions(hlo_text)
            return trace, tr.reduce(trace, kernels), kernels
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True, config=None,
             traffic=None, fault=None, log=None,
             controls=()):
    """One run; returns the result dict (without printing it). ``config``
    and ``traffic`` replace the cell's files (tests run a small size);
    ``fault(system)`` breaks the timed path underneath (tests)."""
    import jax

    from system import PRELOAD_BATCH, System, run_backlog
    from traffic.generator import generate

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell, config0, traffic0 = load_cell(spec, name)
    config, traffic = config or config0, traffic or traffic0
    if traffic["arrivals"] != "backlog":
        raise BenchError(f"arrivals {traffic['arrivals']!r}: the harness "
                         f"drives backlog traffic only")
    if require_tpu:
        devices, peak = devices_and_peak(cell["chips"])
    else:
        devices, peak = jax.devices(), None
    tower = config["tower"]
    jax.config.update("jax_default_matmul_precision",
                      tower["matmul_precision"])

    from system import tower_modules

    adapter, ref_tower = tower_modules(config)
    stream = generate(traffic, config, seed, seconds,
                      lambda rng, n: adapter.features(rng, n, tower, traffic))
    log(f"stream: {len(stream)} requests, {stream.pre_keys.size} preload "
        f"records, logical {stream.logical_rps:.1f} req/s")
    system = System(config, traffic, stream, seed)
    if fault is not None:
        fault(system)
    system.preload()
    S = traffic["steps_per_dispatch"]
    warm, args = system.stage(S)
    system.compile(args)
    system.launch(warm, args)
    system.fetch(warm)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    setup_s = time.monotonic() - t_start
    log(f"setup_s={setup_s}")

    tracer = _Tracer(trace)
    tracer.start()
    window = run_backlog(system, seconds, tracer)
    tracer.stop()
    if compiles:
        log(f"WARNING: {len(compiles)} compilations inside the window")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell["chips"]])
    window_s = window[-1].end - window[0].start
    served = sum(d.n for d in window)
    result_metrics = {}
    acc = {k: sum(int(d.acc[k]) for d in window)
           for k in ("requests", "direct_hits", "tower_inferences",
                     "failover_serves", "fallbacks")}
    took = [1e3 * (d.end - d.start) for d in window]
    log(f"dispatch_ms: min={min(took)} median={np.median(took)} "
        f"max={max(took)} at dispatch {int(np.argmax(took))}")
    log(f"window: {len(window)} dispatches, {served} requests in "
        f"{window_s} s; hit_rate={acc['direct_hits'] / acc['requests']} "
        f"fallback_rate={acc['fallbacks'] / acc['requests']} "
        f"failover_serves={acc['failover_serves']} "
        f"tower_inferences={acc['tower_inferences']} "
        f"peak_bytes_in_use={peak_bytes}")

    run_view = reduced = None
    if trace:
        raw, reduced, kernels = tracer.reduce(system.compiled.as_text())
        log(f"kernels: {kernels}")
        run_view = RunView(config, reduced, tracer.traced, peak,
                           ref_tower.flops_per_inference(tower),
                           system.miss_budget)
        spans = {k: [sum(v), len(v)] for k, v in
                 reduced["host_spans_exposed"].items()}
        log("trace: " + json.dumps({k: v for k, v in reduced.items()
                                    if k != "host_spans_exposed"}))
        log(f"host spans, exposed s and count: {json.dumps(spans)}")
        # the device clock is mapped onto the host's to within some
        # microseconds: a program run may reach a little past an edge
        if (reduced["module_runs"] != len(tracer.traced)
                or reduced["module_cut_s"] > 0.01 * reduced["module_s"]):
            raise BenchError(
                f"the traced window holds {reduced['module_runs']} runs of "
                f"the serve program ({reduced['module_cut_s']} s of them "
                f"outside it), not the {len(tracer.traced)} dispatches the "
                f"per-layer metrics count")

    import correct
    from traffic.generator import T0_MS

    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC0DE]))

    def pick(_name, write_ts):
        fresh = np.nonzero(write_ts.reshape(-1) >= T0_MS)[0]
        old = np.nonzero((write_ts.reshape(-1) > -(2 ** 31))
                         & (write_ts.reshape(-1) < T0_MS))[0]
        old = rng.choice(old, min(TABLE_VALUE_SAMPLE, old.size),
                         replace=False)
        return np.sort(np.concatenate([fresh, old]))

    tables = system.final_tables(pick)
    system.free()
    gc.collect()

    def preload_values(ids):
        out = np.zeros((ids.size, tower["embed_dim"]), np.float32)
        for b in np.unique(ids // PRELOAD_BATCH):
            sel = ids // PRELOAD_BATCH == b
            vals = np.asarray(jax.device_get(system.preload_values(int(b))))
            out[sel] = vals[ids[sel] % PRELOAD_BATCH]
        return out

    t_ref = time.monotonic()
    numbers = correct.compare(config, stream, system, [warm] + window,
                              tables, preload_values, controls)
    ok, rows = correct.verdict(numbers, config["tower_gap_limit"])
    log(f"reference_s={time.monotonic() - t_ref} tower_inputs="
        f"{numbers['tower_inputs']} fallbacks={numbers['fallbacks']}")

    if trace:
        for m in cell_metrics(spec, name, "per_layer"):
            v = reader(m["name"])(run_view)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"served_rps": served / window_s, "setup_s": setup_s}
        for m in cell_metrics(spec, name, "end_to_end"):
            result_metrics[m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    attempted = served
    failed = int(numbers["unanswered"])
    result = {"correct": bool(ok), "attempted": int(attempted),
              "failed": failed, "metrics": result_metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(peak_bytes)}}
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k} = {v} (limit {lim})")
    return result, {"trace": raw if trace else None, "numbers": numbers}
