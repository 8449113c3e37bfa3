"""Engine spans on the profiler's clock, and the tiles-swept counter.

* With the tracer on, every :class:`repro.obs.Tracer` span also lands in a
  JAX profile as a ``repro.<name>`` host event, nested as the work is:
  preprocessing phases inside ``preprocess.*``, sweep phases inside
  ``sweep``, sweeps inside ``run``. With the tracer off none does, and no
  annotation object is built.
* ``repro_engine_tiles_swept_total`` and the ``sweep`` spans' ``tiles``
  count the tiles each scan covers: all of them at a full frontier, the
  active ones under selective execution, the pinned slab plus the
  streamed chunks under host residency.
* ``repro_engine_vertex_message_tiles_total`` counts the same tiles where
  the scan gathers one per-vertex message per edge, and none where a
  program's gather reads weights or destination aux.
* The ``tiles_per_sweep`` benchmark reader, and the recorded chip trace's
  reduction, which this instrumentation must leave as it was.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import (
    BFS,
    SSSP,
    ExecutionPlan,
    GraphSession,
    PageRank,
    TraceSpec,
    build_dsss,
)
from repro.core.dsss import active_tile_mask, tile_source_spans
from repro.core.vertex_programs import ReachBackward
from repro.graph.generators import erdos_renyi, rmat
from repro.graph.preprocess import degree_and_densify
from repro.obs import REGISTRY, TRACER, MetricsRegistry, disable_tracing, enable_tracing
from repro.obs import trace as trace_mod

REPO = Path(__file__).resolve().parents[1]
TILES = "repro_engine_tiles_swept_total"
MESSAGE_TILES = "repro_engine_vertex_message_tiles_total"


def _build(n=130, m=800, seed=7, P=4):
    src, dst = erdos_renyi(n, m, seed=seed)
    return build_dsss(degree_and_densify(src, dst, drop_self_loops=True), P)


@pytest.fixture
def tracing():
    enable_tracing()
    mark = TRACER.mark()
    try:
        yield mark
    finally:
        disable_tracing()


def _host_events(log_dir):
    """``(name, start_ns, end_ns, line)`` of every ``repro.`` host event."""
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [
        (e.name, e.start_ns, e.end_ns, (plane.name, line.name))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("repro.")
    ]


def _profiled_build_and_run(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        g = _build()
        GraphSession(g).run(
            ExecutionPlan(PageRank(), max_iters=3, tol=0.0, execution="packed")
        )
    finally:
        jax.profiler.stop_trace()
    return _host_events(log_dir)


def _inside(child, parents):
    return any(
        p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
        for p in parents
    )


def test_profile_holds_the_engine_spans_nested(tracing, tmp_path):
    events = _profiled_build_and_run(tmp_path)
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    for name in (
        "repro.preprocess.densify", "repro.densify.unique_ids",
        "repro.densify.dedup", "repro.densify.degrees",
        "repro.preprocess.build_dsss", "repro.build_dsss.sort",
        "repro.build_dsss.blocks", "repro.build_dsss.hubs",
        "repro.stage_packed_host", "repro.stage_packed_tiles", "repro.run",
        "repro.sweep", "repro.sweep.plan", "repro.sweep.scan",
        "repro.sweep.apply", "repro.sweep.sync",
    ):
        assert name in by_name, f"{name} missing from the profile"
    assert len(by_name["repro.sweep"]) == 3
    for phase in ("plan", "scan", "apply", "sync"):
        assert len(by_name[f"repro.sweep.{phase}"]) == 3
    nesting = {
        "repro.densify.": "repro.preprocess.densify",
        "repro.build_dsss.": "repro.preprocess.build_dsss",
        "repro.sweep.": "repro.sweep",
    }
    for e in events:
        for prefix, parent in nesting.items():
            if e[0].startswith(prefix):
                assert _inside(e, by_name[parent]), f"{e[0]} outside {parent}"
    for e in by_name["repro.sweep"]:
        assert _inside(e, by_name["repro.run"])
    # The ring and the profile agree on the spans recorded.
    ring = {s.name for s in TRACER.spans(since=tracing)}
    assert {name[len("repro."):] for name in by_name} <= ring


def test_profile_holds_no_engine_span_with_the_tracer_off(tmp_path):
    assert not TRACER.enabled
    assert _profiled_build_and_run(tmp_path) == []


def test_no_annotation_is_built_with_the_tracer_off(monkeypatch):
    built = []

    class Counting:
        def __init__(self, name, **args):
            built.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_annotation_cls", Counting)
    g = _build()
    plan = ExecutionPlan(PageRank(), max_iters=2, tol=0.0, execution="packed")
    GraphSession(g).run(plan)
    assert built == []
    enable_tracing()
    try:
        GraphSession(g).run(plan)
    finally:
        disable_tracing()
    names = [name for name, _ in built]
    assert "repro.run" in names and names.count("repro.sweep") == 2
    sweep_args = [args for name, args in built if name == "repro.sweep"]
    assert [a["sweep"] for a in sweep_args] == [0, 1]
    assert all(type(v) is int for _, args in built for v in args.values())


def test_spans_record_without_jax(monkeypatch):
    monkeypatch.setattr(trace_mod, "_annotation_cls", False)
    tr = trace_mod.Tracer()
    tr.enabled = True
    with tr.span("work", k=1) as span:
        span.set(done=True)
    (s,) = tr.spans()
    assert s.args_dict() == {"k": 1, "done": True}


def test_sweeps_off_keeps_the_phase_spans_out():
    g = _build()
    mark = TRACER.mark()
    GraphSession(g).run(
        ExecutionPlan(
            PageRank(), max_iters=2, tol=0.0, execution="packed",
            trace=TraceSpec(sweeps=False),
        )
    )
    names = {s.name for s in TRACER.spans(since=mark)}
    assert "run" in names
    assert not any(n == "sweep" or n.startswith("sweep.") for n in names)


def test_staging_span_carries_the_tile_width(tracing):
    g = _build()
    GraphSession(g).run(
        ExecutionPlan(PageRank(), max_iters=1, tol=0.0, execution="packed")
    )
    (stage,) = [s for s in TRACER.spans(since=tracing) if s.name == "stage_packed_host"]
    packed = g.packed_sweep("adaptive")
    args = stage.args_dict()
    assert args["tile_edges"] == packed.tile_edges
    assert args["num_tiles"] == packed.num_tiles
    assert args["padded_edge_slots"] == packed.num_tiles * packed.tile_edges


# ---------------------------------------------------------------------------
# tiles swept
# ---------------------------------------------------------------------------
def _sweep_spans(mark):
    return [s.args_dict() for s in TRACER.spans(since=mark) if s.name == "sweep"]


def test_full_frontier_sweeps_every_tile(tracing):
    g = _build()
    sess = GraphSession(g)
    before = REGISTRY.value(TILES)
    res = sess.run(ExecutionPlan(PageRank(), max_iters=4, tol=0.0, execution="packed"))
    nt = sess._staged.packed_host(sess.packing).num_tiles
    assert res.iterations == 4
    assert REGISTRY.value(TILES) - before == nt * 4
    assert [a["tiles"] for a in _sweep_spans(tracing)] == [nt] * 4


def test_selective_sweeps_count_only_active_tiles(tracing):
    el = degree_and_densify(*rmat(10, edge_factor=4, seed=3), drop_self_loops=True)
    g = build_dsss(el, 8)
    sess = GraphSession(g)
    before = REGISTRY.value(TILES)
    res = sess.run(ExecutionPlan(BFS(), max_iters=g.n + 1, execution="packed",
                                 program_kwargs={"root": 0}))
    packed = sess._staged.packed_host(sess.packing)
    first, last = tile_source_spans(packed, g.interval_size)
    want = [
        packed.num_tiles if row.all() else int(active_tile_mask(row, first, last).sum())
        for row in res.activity_log
    ]
    assert any(w < packed.num_tiles for w in want)  # the frontier did shrink
    assert [a["tiles"] for a in _sweep_spans(tracing)] == want
    assert REGISTRY.value(TILES) - before == sum(want)


def test_host_residency_counts_pins_and_streamed_chunks(tracing):
    g = _build()
    budget = int(g.total_edge_bytes(8) * 0.3)
    sess = GraphSession(g, memory_budget=budget, residency="host")
    before = REGISTRY.value(TILES)
    sess.run(ExecutionPlan(PageRank(), max_iters=3, tol=0.0, execution="packed"))
    nt = sess._staged.packed_host(sess.packing).num_tiles
    spans = TRACER.spans(since=tracing)
    chunks = [s for s in spans if s.name == "sweep.chunk"]
    scans = [s for s in spans if s.name == "sweep.scan"]
    assert len(chunks) >= 3 * 2  # several streamed chunks per sweep
    streamed = sum(s.args_dict()["tiles"] for s in chunks + scans)
    assert streamed == nt * 3
    assert [a["tiles"] for a in _sweep_spans(tracing)] == [nt] * 3
    assert REGISTRY.value(TILES) - before == nt * 3


def _message_tile_run(case):
    """(session, plan) of one counter case on a small graph."""
    src, dst = erdos_renyi(130, 800, seed=7)
    weights = None
    if case == "sssp-weighted":
        weights = np.random.default_rng(7).uniform(0.1, 2.0, len(src)).astype(np.float32)
    el = degree_and_densify(src, dst, weights=weights, drop_self_loops=True)
    g = build_dsss(el, 4)
    if case == "pagerank":
        return GraphSession(g), ExecutionPlan(PageRank(), max_iters=4, tol=0.0)
    if case == "pagerank-host":
        budget = int(g.total_edge_bytes(8) * 0.3)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        return sess, ExecutionPlan(PageRank(), max_iters=3, tol=0.0)
    if case in ("bfs-full", "bfs-selective"):
        activity = "off" if case == "bfs-full" else "auto"
        return GraphSession(g), ExecutionPlan(
            BFS(), max_iters=g.n + 1, activity=activity, program_kwargs={"root": 0}
        )
    if case == "sssp-weighted":
        return GraphSession(g), ExecutionPlan(
            SSSP(), max_iters=g.n + 1, program_kwargs={"root": 0}
        )
    reach = np.zeros(g.n_pad, np.int32)
    reach[:3] = 1
    colors = (np.arange(g.n_pad) % 2).astype(np.int32)
    return GraphSession(g), ExecutionPlan(
        ReachBackward(), max_iters=g.n + 1,
        program_kwargs={"reach": reach, "colors": colors},
    )


@pytest.mark.parametrize(
    "case, engaged",
    [
        ("pagerank", True),
        ("pagerank-host", True),
        ("bfs-full", True),
        ("bfs-selective", True),
        ("sssp-weighted", False),
        ("reach-backward", False),
    ],
)
def test_vertex_message_tiles_count_the_message_path(case, engaged):
    sess, plan = _message_tile_run(case)
    tiles0, msg0 = REGISTRY.value(TILES), REGISTRY.value(MESSAGE_TILES)
    res = sess.run(dataclasses.replace(plan, execution="packed"))
    tiles = REGISTRY.value(TILES) - tiles0
    assert tiles > 0
    if case == "bfs-selective":
        assert not all(row.all() for row in res.activity_log)  # compacted scans ran
    assert REGISTRY.value(MESSAGE_TILES) - msg0 == (tiles if engaged else 0)


# ---------------------------------------------------------------------------
# the benchmark's side
# ---------------------------------------------------------------------------
def _reader(name):
    path = REPO / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize(
    "tiles, sweeps, want",
    [(None, 12, None), (0.0, 0, None), (3 * 1024.0, 3, 1024.0), (1010.0, 10, 101.0)],
)
def test_tiles_per_sweep_reader(monkeypatch, tiles, sweeps, want):
    reader = _reader("tiles_per_sweep")
    reg = MetricsRegistry(enabled=True)
    reg.counter(reader.SWEEPS).inc(sweeps)
    if tiles is not None:
        reg.counter(reader.TILES).inc(tiles)
    monkeypatch.setattr(reader, "REGISTRY", reg)
    assert reader.read(object()) == want


def test_recorded_chip_trace_reduces_as_before(monkeypatch):
    """The fixture's numbers as the reduction gave them before the engine
    spans existed (a trace of the program without them, one v5e, scale 12)."""
    monkeypatch.syspath_prepend(str(REPO))
    bench_trace = importlib.import_module("bench.trace")
    data = REPO / "bench" / "tests" / "data"
    r = bench_trace.reduce_trace(bench_trace.read_trace(data / "v5e_pagerank_s12"))
    expected = json.loads((data / "v5e_pagerank_s12.json").read_text())
    assert r["busy_s"] == expected["busy_s"]
    assert r["window_s"] == expected["window_s"]
    assert [name for name, _ in r["device_ops"]] == [
        "jit__packed_sweep_impl: %while.1",
        "jit__packed_sweep_impl: %fusion.28",
        "jit__packed_sweep_impl: %fusion.27",
        "jit__packed_sweep_impl: %fusion.26",
        "jit__packed_sweep_impl: %fusion.24",
        "jit__packed_sweep_impl: %fusion.25",
        "jit__packed_sweep_impl: %constant_dynamic-slice_fusion.2",
        "jit__packed_sweep_impl: %compare_select_fusion.5",
        "jit__packed_sweep_impl: %compare_select_fusion.4",
        "jit__packed_sweep_impl: %and_select_fusion.2",
    ]
    assert [s for _, s in r["device_ops"]] == [
        0.005331054, 0.001085305, 0.001078808, 0.000937429, 0.000818402,
        0.000818196, 0.000130603, 0.000111306, 8.1861e-05, 8.1467e-05,
    ]
