"""The whole step's share of the chip's bf16 peak, in %: useful tower
FLOPs (tower inferences the program counted, times the FLOPs of one
inference; padded tower rows do not count) over the traced window's wall
time times the peak."""


def read(run):
    inferences = sum(int(d.acc["tower_inferences"]) for d in run.traced)
    if not run.trace or not inferences:
        return None
    flops = inferences * run.flops_per_inference
    return 100.0 * flops / (run.trace["window_s"] * run.peak["bf16_flops"])
