"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's op intervals) / window."""


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
