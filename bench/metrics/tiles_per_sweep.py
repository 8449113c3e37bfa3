"""tiles_per_sweep: packed tiles a sweep's scans cover, averaged over the run's sweeps.

Read from the engine's registry counters, ``repro_engine_tiles_swept_total``
(charged where each scan is dispatched) over ``repro_engine_sweeps_total``,
for every sweep of the process: warm-up and window. At a full frontier it
equals the graph's tile count. None where the engine has no tile counter.
"""
from repro.obs import REGISTRY

TILES = "repro_engine_tiles_swept_total"
SWEEPS = "repro_engine_sweeps_total"


def read(run):
    if REGISTRY.get(TILES) is None:
        return None
    sweeps = REGISTRY.value(SWEEPS)
    if sweeps <= 0:
        return None
    return REGISTRY.value(TILES) / sweeps
