"""sweep_device_ms: device busy time of the traced window per sweep, in ms."""


def read(run):
    t = run.trace
    if t is None or run.sweeps == 0:
        return None
    return 1e3 * t["busy_s"] / run.sweeps
