"""Tier ``disk``: the graph written to a ``.dsss`` store and streamed from it.

The built graph is written with ``write_dsss`` into the run's ``workdir``
and opened with ``GraphSession.open``: every sweep reads its tiles from the
mmap'd file. The device budget is the deployment's, converted into the
engine's own units: both attribute copies (``2 · n_pad · Ba``, Ba the
PageRank program's ``attr_bytes``) plus the configured share of the real
edge bytes (``pinned_tile_share · m · Be``, Be the session's model bytes
per edge), which the SPU rule turns into a device-pinned tile prefix; the
rest streams host→device in chunks every sweep. ``host_memory_budget``
bounds the host RAM cache of streamed chunks (0: none, every chunk is
sliced from the file).
"""


def memory_budget(session, config: dict) -> int:
    """Device bytes that pin ``pinned_tile_share`` of the edges under SPU."""
    from repro.core import PageRank

    g = session.graph
    share = float(config["pinned_tile_share"])
    return 2 * g.n_pad * PageRank.attr_bytes + round(share * g.m * session.Be)


def open_session(graph, config: dict, workdir):
    from repro.core import GraphSession
    from repro.storage import write_dsss

    path = workdir / "graph.dsss"
    write_dsss(graph, str(path))
    session = GraphSession.open(
        str(path), host_memory_budget=int(config["host_memory_budget"])
    )
    # Set before the first compile, which is where the engine reads it.
    session.memory_budget = memory_budget(session, config)
    resolved = session.resolved_residency()
    if resolved != "disk":
        raise RuntimeError(f"the store resolved residency {resolved!r}, not 'disk'")
    return session
