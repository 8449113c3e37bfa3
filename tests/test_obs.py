"""The observability layer (repro.obs) and its engine/serving wiring.

The load-bearing contract under test is *exactness*: metrics and trace
spans are emitted at the same lines that charge ``Meters``, so

* registry deltas across a run recombine field-for-field with
  ``Result.meters`` — checked over the residency × execution matrix;
* a traced run's per-sweep ``bytes_h2d``/``bytes_disk_read`` span
  attributes sum exactly to the run totals;
* a ``/metrics`` scrape of a :class:`GraphServer` endpoint equals the
  ``ServerStats`` snapshot field-for-field, and per-request
  ``split_meters`` shares re-sum to the scraped serving meter totals.

Plus the plumbing: Prometheus render/parse round-trip, registry gating
(``REPRO_OBS=0`` semantics), tracer ring + Chrome export + the
``python -m repro.obs export-trace`` CLI, iomodel drift gauges and
checkpoint/storage counters.
"""
import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    BFS,
    CheckpointSpec,
    ExecutionPlan,
    GraphSession,
    PageRank,
    TraceSpec,
    build_dsss,
    modelled_io,
)
from repro.graph.generators import erdos_renyi
from repro.graph.preprocess import degree_and_densify
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS,
    HistogramValue,
    MetricsRegistry,
    REGISTRY,
    TRACER,
    Tracer,
    parse_prometheus,
)
from repro.serving import GraphServer, QueryRequest, SessionPool
from repro.serving.api import split_meters
from repro.storage import write_dsss


def _graph(n=130, m=800, seed=7, P=4):
    src, dst = erdos_renyi(n, m, seed=seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    return build_dsss(el, P)


@pytest.fixture(scope="module")
def graph():
    return _graph()


@pytest.fixture(scope="module")
def dsss_path(graph, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("obs") / "g.dsss")
    write_dsss(graph, path)
    return path


def _session(graph, dsss_path, residency):
    budget = int(graph.total_edge_bytes(8) * 0.3)
    if residency == "disk":
        return GraphSession.open(
            dsss_path, memory_budget=budget, host_memory_budget=2 * budget
        )
    return GraphSession(graph, memory_budget=budget, residency=residency)


# ---------------------------------------------------------------------------
# registry plumbing
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_render_parse_roundtrip(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("t_bytes_total", "bytes", ("kind",))
        c.labels(kind="h2d").inc(7)
        c.labels(kind="disk").inc(3.5)
        reg.gauge("t_depth", "queue depth").set(4)
        parsed = parse_prometheus(reg.render())
        assert parsed[("t_bytes_total", (("kind", "h2d"),))] == 7
        assert parsed[("t_bytes_total", (("kind", "disk"),))] == 3.5
        assert parsed[("t_depth", ())] == 4

    def test_disabled_registry_records_nothing(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("t_total")
        c.inc(5)
        reg.gauge("t_g").set(9)
        reg.histogram("t_h").observe(0.1)
        assert reg.value("t_total") == 0.0
        assert reg.value("t_g") == 0.0
        assert reg.value("t_h") == 0.0
        reg.set_enabled(True)
        c.inc(5)
        assert reg.value("t_total") == 5.0

    def test_reregistration_idempotent_but_type_checked(self):
        reg = MetricsRegistry(enabled=True)
        a = reg.counter("t_total", "x", ("k",))
        assert reg.counter("t_total", "y", ("k",)) is a
        with pytest.raises(ValueError):
            reg.gauge("t_total")
        with pytest.raises(ValueError):
            reg.counter("t_total", labelnames=("other",))

    def test_histogram_quantiles_and_render(self):
        h = HistogramValue(buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 0.5, 0.5, 5.0):
            h.observe(v)
        assert 0.0 < h.quantile(0.5) <= 1.0
        assert h.quantile(0.99) <= 10.0
        assert h.count == 6
        reg = MetricsRegistry(enabled=True)
        reg.histogram("t_lat", buckets=(0.1, 1.0)).observe(0.5)
        text = reg.render()
        assert 't_lat_bucket{le="+Inf"} 1' in text
        assert "t_lat_count 1" in text

    def test_value_missing_series_is_zero(self):
        reg = MetricsRegistry(enabled=True)
        assert reg.value("never_registered") == 0.0
        reg.counter("t_total", "x", ("k",))
        assert reg.value("t_total", k="absent") == 0.0


# ---------------------------------------------------------------------------
# tracer plumbing
# ---------------------------------------------------------------------------
class TestTracer:
    def test_ring_bounded_and_since_mark(self):
        tr = Tracer(capacity=4)
        tr.enabled = True
        for i in range(10):
            tr.instant(f"e{i}")
        assert len(tr.spans()) == 4
        mark = tr.mark()
        tr.instant("after")
        assert [s.name for s in tr.spans(since=mark)] == ["after"]

    def test_span_ctx_gates_on_enabled(self):
        tr = Tracer()
        with tr.span("off"):
            pass
        assert tr.spans() == []
        tr.enabled = True
        with tr.span("on", cat="t", k=1):
            pass
        (s,) = tr.spans()
        assert s.name == "on" and s.args_dict() == {"k": 1}

    def test_chrome_export_shape(self, tmp_path):
        tr = Tracer()
        tr.enabled = True
        tr.record("work", 1.0, 1.5, cat="t", args={"bytes": 3})
        doc = tr.to_chrome()
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs[0]["ts"] == 1.0e6 and xs[0]["dur"] == 0.5e6
        assert any(e["ph"] == "M" for e in doc["traceEvents"])
        path = str(tmp_path / "t.json")
        tr.export(path)
        assert json.load(open(path))["traceEvents"]

    def test_cli_converts_jsonl_dump(self, tmp_path):
        tr = Tracer()
        tr.enabled = True
        tr.record("sweep", 0.0, 0.1, args={"bytes_h2d": 64})
        src = str(tmp_path / "spans.jsonl")
        tr.dump(src)
        out = str(tmp_path / "trace.json")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.obs", "export-trace", src, "-o", out],
            capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        events = json.load(open(out))["traceEvents"]
        xs = [e for e in events if e.get("ph") == "X"]
        assert xs[0]["name"] == "sweep"
        assert xs[0]["args"]["bytes_h2d"] == 64


# ---------------------------------------------------------------------------
# engine wiring: registry deltas == Result.meters over the matrix
# ---------------------------------------------------------------------------
_BYTE_KINDS = (
    ("h2d", "bytes_h2d"),
    ("disk_read", "bytes_disk_read"),
    ("read_edges", "bytes_read_edges"),
    ("read_intervals", "bytes_read_intervals"),
    ("read_hubs", "bytes_read_hubs"),
    ("written_hubs", "bytes_written_hubs"),
    ("written_intervals", "bytes_written_intervals"),
)


def _snap_bytes():
    return {
        kind: REGISTRY.value("repro_engine_bytes_total", kind=kind)
        for kind, _ in _BYTE_KINDS
    }


class TestEngineMetrics:
    @pytest.mark.parametrize("residency", ["device", "host", "disk"])
    @pytest.mark.parametrize("execution", ["per_block", "packed"])
    def test_registry_deltas_equal_meters(
        self, graph, dsss_path, residency, execution
    ):
        sess = _session(graph, dsss_path, residency)
        plan = ExecutionPlan(
            PageRank(), max_iters=3, tol=0.0, execution=execution
        )
        before = _snap_bytes()
        s_sweeps = REGISTRY.value("repro_engine_sweeps_total")
        res = sess.run(plan)
        after = _snap_bytes()
        for kind, field in _BYTE_KINDS:
            assert after[kind] - before[kind] == getattr(res.meters, field), (
                f"{residency}/{execution}: registry kind={kind} drifted "
                "from Meters"
            )
        assert (
            REGISTRY.value("repro_engine_sweeps_total") - s_sweeps
            == res.meters.iterations
        )
        assert (
            REGISTRY.value(
                "repro_engine_runs_total",
                program="pagerank",
                strategy=res.strategy.strategy,
                residency=sess.resolved_residency(),
                execution=sess.resolved_execution(
                    res.strategy.strategy, sess.resolved_residency(), execution
                ),
            )
            >= 1
        )

    def test_disabled_registry_freezes_engine_counters(self, graph):
        sess = GraphSession(graph)
        plan = ExecutionPlan(PageRank(), max_iters=2, tol=0.0)
        sess.run(plan)  # ensure series exist
        before = _snap_bytes()
        s_sweeps = REGISTRY.value("repro_engine_sweeps_total")
        REGISTRY.set_enabled(False)
        try:
            sess.run(plan)
        finally:
            REGISTRY.set_enabled(True)
        assert _snap_bytes() == before
        assert REGISTRY.value("repro_engine_sweeps_total") == s_sweeps

    def test_iomodel_drift_gauge_near_one(self, graph):
        budget = int(graph.total_edge_bytes(8) * 0.3)
        sess = GraphSession(graph, memory_budget=budget, residency="host")
        plan = ExecutionPlan(PageRank(), max_iters=4, tol=0.0)
        res = sess.run(plan)
        strat = res.strategy.strategy
        read, write = modelled_io(
            sess.params_for(plan.program), budget, strat
        )
        if read > 0:
            got = REGISTRY.value(
                "repro_iomodel_drift_ratio", direction="read", strategy=strat
            )
            want = res.meters.bytes_read / res.meters.iterations / read
            assert got == pytest.approx(want)
            assert 0.2 < got < 5.0  # full sweeps: same order as the model


# ---------------------------------------------------------------------------
# tracing wiring: per-sweep byte attrs sum exactly to meters
# ---------------------------------------------------------------------------
class TestEngineTracing:
    def test_traced_disk_run_sums_and_valid_chrome(
        self, graph, dsss_path, tmp_path
    ):
        sess = _session(graph, dsss_path, "disk")
        path = str(tmp_path / "run.json")
        plan = ExecutionPlan(
            PageRank(), max_iters=4, tol=0.0, trace=TraceSpec(path=path)
        )
        res = sess.run(plan)
        assert not TRACER.enabled  # plan-scoped enable was restored
        doc = json.load(open(path))
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        sweeps = [e for e in xs if e["name"] == "sweep"]
        assert len(sweeps) == res.meters.iterations
        assert (
            sum(e["args"]["bytes_h2d"] for e in sweeps)
            == res.meters.bytes_h2d
        )
        assert (
            sum(e["args"]["bytes_disk_read"] for e in sweeps)
            == res.meters.bytes_disk_read
        )
        assert res.meters.bytes_disk_read > 0
        (run_span,) = [e for e in xs if e["name"] == "run"]
        assert run_span["args"]["bytes_h2d"] == res.meters.bytes_h2d
        assert run_span["args"]["residency"] == "disk"

    def test_trace_summary_bytes_equal_meters_on_disk(self, tmp_path):
        """A disk-tier PageRank on an R-MAT graph (scale 9, P = 8) under a
        quarter of its edge bytes: the run summary that
        ``repro.runtime.trace_analysis`` reads back from the exported
        trace carries exactly the run's ``bytes_h2d`` and
        ``bytes_disk_read``."""
        from repro.graph.generators import rmat
        from repro.runtime.trace_analysis import load_events, run_summaries

        el = degree_and_densify(*rmat(9, 16, seed=0), drop_self_loops=True)
        g = build_dsss(el, 8)
        store = str(tmp_path / "g.dsss")
        write_dsss(g, store)
        budget = int(g.total_edge_bytes(8) * 0.25)
        sess = GraphSession.open(
            store, memory_budget=budget, host_memory_budget=2 * budget
        )
        assert sess.resolved_residency() == "disk"
        path = str(tmp_path / "run.json")
        res = sess.run(
            ExecutionPlan(
                PageRank(), max_iters=5, tol=0.0, trace=TraceSpec(path=path)
            )
        )
        (summary,) = run_summaries(load_events(path))
        assert summary["sweeps"] == res.meters.iterations
        assert summary["bytes_h2d"] == res.meters.bytes_h2d
        assert summary["bytes_disk_read"] == res.meters.bytes_disk_read
        assert res.meters.bytes_disk_read > 0

    def test_trace_records_staging_and_checkpoint(self, graph, tmp_path):
        # Fresh device session: the first fused run always stages, so a
        # cat="staging" span is guaranteed alongside the checkpoint ones.
        sess = GraphSession(graph)
        path = str(tmp_path / "ck.json")
        plan = ExecutionPlan(
            PageRank(),
            max_iters=4,
            tol=0.0,
            checkpoint=CheckpointSpec(directory=str(tmp_path / "snaps"),
                                      every=2),
            trace=TraceSpec(path=path),
        )
        sess.run(plan)
        xs = [
            e
            for e in json.load(open(path))["traceEvents"]
            if e.get("ph") == "X"
        ]
        names = {e["name"] for e in xs}
        assert "checkpoint" in names
        assert any(e["cat"] == "staging" for e in xs)

    def test_tracespec_sweeps_off_and_batch_key_exclusion(
        self, graph, tmp_path
    ):
        path = str(tmp_path / "nosweeps.json")
        spec = TraceSpec(path=path, sweeps=False)
        plan = ExecutionPlan(PageRank(), max_iters=2, tol=0.0, trace=spec)
        bare = ExecutionPlan(PageRank(), max_iters=2, tol=0.0)
        assert plan.batch_key() == bare.batch_key()  # traced requests fuse
        GraphSession(graph).run(plan)
        xs = [
            e
            for e in json.load(open(path))["traceEvents"]
            if e.get("ph") == "X"
        ]
        assert all(e["name"] != "sweep" for e in xs)
        assert any(e["name"] == "run" for e in xs)

    def test_trace_type_validated(self):
        with pytest.raises(TypeError):
            ExecutionPlan(PageRank(), trace="run.json")


# ---------------------------------------------------------------------------
# serving wiring: scrape == stats, split_meters re-sums, healthz
# ---------------------------------------------------------------------------
def _scrape(server, path="/metrics"):
    import urllib.request

    return urllib.request.urlopen(server.telemetry.url(path), timeout=10)


class TestServingTelemetry:
    def test_scrape_equals_stats_and_meter_shares_resum(self, graph):
        pool = SessionPool()
        pool.register("g", graph)
        server = GraphServer(pool, max_batch=8, telemetry_port=0)
        try:
            k = 6
            plans = [
                ExecutionPlan(
                    BFS(), strategy="spu", max_iters=graph.n + 1,
                    program_kwargs={"root": r},
                )
                for r in range(k)
            ]
            served = server.serve([QueryRequest("g", p) for p in plans])
            st = server.stats()
            parsed = parse_prometheus(
                _scrape(server).read().decode()
            )
            for f in st.COUNTER_FIELDS:
                assert parsed[(f"repro_serving_{f}_total", ())] == getattr(
                    st, f
                ), f
            for f in ("p50_total_s", "p95_total_s", "p99_total_s", "qps"):
                assert parsed[(f"repro_serving_{f}", ())] == pytest.approx(
                    getattr(st, f)
                )
            # fused-batch shares re-sum to the scraped serving meters
            assert any(q.fused and q.batch_size > 1 for q in served)
            from repro.core.session import Meters

            merged = Meters()
            for q in served:
                merged.merge(q.meters)
            for f in dataclasses.fields(Meters):
                scraped = parsed[
                    ("repro_serving_meters_total", (("field", f.name),))
                ]
                assert scraped == pytest.approx(
                    float(getattr(st.meters, f.name))
                )
                if f.name not in ("wall_seconds", "peak_device_graph_bytes"):
                    assert float(getattr(merged, f.name)) == pytest.approx(
                        scraped
                    ), f.name
            # pool stats came along
            assert parsed[("repro_pool_open_sessions", ())] == 1
        finally:
            server.shutdown_telemetry()

    def test_healthz_and_unknown_route(self, graph):
        pool = SessionPool()
        pool.register("g", graph)
        server = GraphServer(pool, telemetry_port=0)
        try:
            resp = _scrape(server, "/healthz")
            doc = json.loads(resp.read())
            assert resp.status == 200 and doc["status"] == "ok"
            assert doc["queue_depth"] == 0
            import urllib.error

            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _scrape(server, "/nope")
            assert exc_info.value.code == 404
        finally:
            server.shutdown_telemetry()

    def test_split_meters_percentiles_in_stats(self, graph):
        pool = SessionPool()
        pool.register("g", graph)
        server = GraphServer(pool, max_batch=4, telemetry_port=0)
        try:
            plan = ExecutionPlan(PageRank(), max_iters=2, tol=0.0)
            server.serve([QueryRequest("g", plan) for _ in range(4)])
            st = server.stats()
            assert st.p50_total_s > 0
            assert st.p50_total_s <= st.p95_total_s <= st.p99_total_s
            assert st.p99_total_s <= DEFAULT_LATENCY_BUCKETS[-1]
        finally:
            server.shutdown_telemetry()


# ---------------------------------------------------------------------------
# checkpoint counters
# ---------------------------------------------------------------------------
class TestCheckpointCounters:
    def test_save_snapshot_publishes_counters(self, graph, tmp_path):
        before_saves = REGISTRY.value("repro_checkpoint_saves_total")
        before_bytes = REGISTRY.value("repro_checkpoint_bytes_total")
        sess = GraphSession(graph)
        plan = ExecutionPlan(
            PageRank(),
            max_iters=4,
            tol=0.0,
            checkpoint=CheckpointSpec(directory=str(tmp_path), every=2),
        )
        sess.run(plan)
        assert REGISTRY.value("repro_checkpoint_saves_total") - before_saves == 2
        assert REGISTRY.value("repro_checkpoint_bytes_total") > before_bytes
