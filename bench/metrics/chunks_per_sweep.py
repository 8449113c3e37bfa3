"""chunks_per_sweep: streamed tile chunks a sweep dispatches, averaged over sweeps.

Read from the engine's registry: ``repro_engine_stream_chunks_total``
(charged at each streamed chunk's scan dispatch) over
``repro_engine_sweeps_total``, for every sweep of the process: warm-up and
window. None where the engine has no chunk counter.
"""
from repro.obs import REGISTRY

CHUNKS = "repro_engine_stream_chunks_total"
SWEEPS = "repro_engine_sweeps_total"


def read(run):
    if REGISTRY.get(CHUNKS) is None:
        return None
    sweeps = REGISTRY.value(SWEEPS)
    if sweeps <= 0:
        return None
    return REGISTRY.value(CHUNKS) / sweeps
