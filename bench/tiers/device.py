"""Tier ``device``: the whole graph staged once into the chip's HBM.

Each memory tier is one file ``bench/tiers/<tier>.py`` with
``open_session(graph, config, workdir)``: the engine's session over the
built ``DSSSGraph``, served from that tier. ``workdir`` is the run's own
directory under ``bench/work``, deleted at exit, for any file the tier
writes.
"""


def open_session(graph, config: dict, workdir):
    from repro.core import GraphSession

    return GraphSession(graph)
