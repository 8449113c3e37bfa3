"""stream_bytes_per_sweep: host→device bytes of streamed tile chunks per sweep.

Read from the engine's registry: ``repro_engine_bytes_total{kind="h2d"}``
(charged at each streamed chunk's transfer, with the chunk's raw padded
bytes; the device-pinned prefix is staged once and not charged) over
``repro_engine_sweeps_total``, for every sweep of the process: warm-up and
window. None before any sweep.
"""
from repro.obs import REGISTRY

BYTES = "repro_engine_bytes_total"
SWEEPS = "repro_engine_sweeps_total"


def read(run):
    if REGISTRY.get(BYTES) is None:
        return None
    sweeps = REGISTRY.value(SWEEPS)
    if sweeps <= 0:
        return None
    return REGISTRY.value(BYTES, kind="h2d") / sweeps
