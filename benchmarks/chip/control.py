"""The control experiment behind a cell's ``tower_gap`` limit, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 2

For each seed, one run of the cell as the benchmark makes it, with a short
window at the cell's own load: its compared numbers (the lower reading of
``tower_gap`` is the largest over the seeds), and the control: the reference
tower one precision step below the configuration's float32 at ``highest``,
the TPU's ``high`` (three bfloat16 passes), put in the system's place over
the same requests. The control's outputs go through the same verdict as the
system's, which has to read ``correct`` false; the upper reading is the
control's smallest ``tower_gap`` over the seeds. ``bf16_3x``, the same three
passes written out (the stand-in the CPU tests use, where ``high`` is
``highest``), is read beside it. One process runs every seed, so set-up
compiles once. Prints one JSON line per seed, then the two readings.
"""
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    import correct
    from harness import load_cell, read_json, run_cell

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    spec = read_json(ROOT / "BENCHMARK.json")
    high = jax.lax.Precision.HIGH
    limit = load_cell(spec, args.workload)[1]["tower_gap_limit"]
    lower, upper, control_ok = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        result, extra = run_cell(
            spec, args.workload, seed, args.seconds, False,
            time.monotonic(), log=lambda *a: None,
            controls=(high, "bf16_3x"))
        n = extra["numbers"]
        ctrl = {k: v for k, v in n.items() if k.startswith("control_gap")}
        high_gap = ctrl[f"control_gap:{high}"]
        ok, _ = correct.verdict(dict(n, tower_gap=high_gap), limit)
        lower.append(n["tower_gap"])
        upper.append(high_gap)
        control_ok.append(ok)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "control_correct": ok,
                          "tower_gap": n["tower_gap"],
                          "tower_inputs": n["tower_inputs"], **{
                              k.replace("Precision.", ""): v
                              for k, v in ctrl.items()},
                          "checks": result["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower_reading": max(lower),
                      "upper_reading": min(upper),
                      "control_ever_correct": any(control_ok)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
