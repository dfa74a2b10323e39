"""The dual probe kernel's share of its roofline, in %: the least time its
work needs at the chip's HBM bandwidth (bytes counted from the queries and
hits by ``costs.probe_bytes``, the same whatever implements the probe), over
the device time of the serve program's Pallas kernels in the trace."""

from costs import probe_bytes


def read(run):
    if not run.trace or run.trace["kernel_s"] <= 0:
        return None
    moved = sum(probe_bytes(run.config, d.n, int(d.acc["direct_hits"]),
                            int(d.acc["failover_serves"]))
                for d in run.traced)
    least = moved / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / run.trace["kernel_s"]
