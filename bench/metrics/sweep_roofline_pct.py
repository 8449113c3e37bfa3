"""sweep_roofline_pct: a sweep's least bytes at peak HBM bandwidth, over its device time.

The bytes come from ``bench.roofline.sweep_min_bytes`` on the benchmark's
own graph (every edge active: the cells that list this metric run full
frontiers); the peak from ``bench/peaks.json`` by device kind.
"""
from bench import roofline


def read(run):
    t = run.trace
    if t is None or run.sweeps == 0 or t["busy_s"] <= 0:
        return None
    nbytes = roofline.sweep_min_bytes(
        run.n, run.m, attr_bytes=run.traffic.attr_bytes
    )
    least_s = nbytes / roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] / run.sweeps)
