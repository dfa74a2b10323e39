"""The on-chip benchmark of the ERCache serve path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` names the cells; this
program finds each cell's configuration, traffic and metric readers by name
under ``benchmarks/chip/``. It sets up the cell (weights, stream and preload
from ``--seed``, compile or compile-cache load, one warm-up dispatch),
measures for ``--seconds``, then compares what the window served with the
plain references. With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the first seconds of the window are profiled and
the result holds its per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced) and, last, ``checks``: each compared number with its limit, also
printed as the last lines of standard error. A host without the TPU chips
the cell asks for, or whose device kind is not in ``peaks.json``, gets no
result and exit code 1.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: write the first 0.3 s of the "
                         "traced window, as the reducer reads it, to this "
                         "JSON file")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import (BenchError, devices_and_peak, load_cell, read_json,
                         run_cell)

    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"the system under test is not at {ROOT}/src")
        spec = read_json(ROOT / "BENCHMARK.json")
        devices_and_peak(load_cell(spec, args.workload)[0]["chips"])
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        result, extra = run_cell(spec, args.workload, args.seed,
                                 args.seconds, bool(args.trace), T_START)
        if args.dump_trace and extra["trace"] is not None:
            import trace_reduce

            pathlib.Path(args.dump_trace).write_text(json.dumps(
                trace_reduce.trim(extra["trace"], 0.3e9)))
    except BenchError as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
