"""stream_fetch_ms_per_sweep: host milliseconds fetching streamed chunks per sweep.

Read from the engine's registry: ``repro_engine_stream_fetch_seconds_total``
(``time.perf_counter`` around each chunk's mmap slice or RAM copy and its
``device_put`` call) over ``repro_engine_sweeps_total``, for every sweep
of the process: warm-up and window. None where the engine has no fetch
counter.
"""
from repro.obs import REGISTRY

FETCH = "repro_engine_stream_fetch_seconds_total"
SWEEPS = "repro_engine_sweeps_total"


def read(run):
    if REGISTRY.get(FETCH) is None:
        return None
    sweeps = REGISTRY.value(SWEEPS)
    if sweeps <= 0:
        return None
    return 1000.0 * REGISTRY.value(FETCH) / sweeps
