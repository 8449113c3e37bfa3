"""The disk tier, the stream layer's readers and the cells they apply to."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_harness import TINY, make_checkout, on_cpu, run  # noqa: F401
from repro.obs import MetricsRegistry

REPO = Path(__file__).resolve().parents[2]
DISK = dict(TINY, scale=11, edge_factor=16, tier="disk", pinned_tile_share=0.43,
            host_memory_budget=0)
STREAM = ("stream_bytes_per_sweep", "stream_fetch_ms_per_sweep", "chunks_per_sweep")
BOTH = ("build_s", "compile_s", "device_idle_pct", "sweep_device_ms", "sweep_roofline_pct",
        "tiles_per_sweep")
OLD, NEW = "graph500-s21.pagerank", "graph500-s21-disk.pagerank"


def test_the_tier_opens_a_written_store_and_resolves_disk(tmp_path):
    from repro.core import PageRank, build_dsss
    from repro.graph.preprocess import degree_and_densify

    src, dst, _ = harness.load_part(REPO, "generators", "graph500").edges(5, DISK)
    g = build_dsss(degree_and_densify(src, dst, drop_self_loops=True), DISK["P"])
    tier = harness.load_part(REPO, "tiers", "disk")
    sess = tier.open_session(g, DISK, tmp_path)
    assert (tmp_path / "graph.dsss").is_file()
    assert sess.resolved_residency() == "disk"
    assert sess.Be == 8 and PageRank.attr_bytes == 8
    assert sess.memory_budget == 2 * g.n_pad * 8 + round(0.43 * g.m * 8)
    assert sess.host_memory_budget == 0
    np.testing.assert_array_equal(sess.graph.edgelist.id_to_index, g.edgelist.id_to_index)


@pytest.mark.parametrize(
    "name, counter, labels, value, sweeps, want",
    [
        ("stream_bytes_per_sweep", "repro_engine_bytes_total", {"kind": "h2d"},
         12 * 1048580.0, 12, 1048580.0),
        ("stream_bytes_per_sweep", "repro_engine_bytes_total", {"kind": "h2d"}, 5.0, 0, None),
        ("stream_bytes_per_sweep", None, {}, 0.0, 12, None),
        ("stream_fetch_ms_per_sweep", "repro_engine_stream_fetch_seconds_total", {},
         0.6, 12, 50.0),
        ("stream_fetch_ms_per_sweep", None, {}, 0.0, 12, None),
        ("chunks_per_sweep", "repro_engine_stream_chunks_total", {}, 12 * 553.0, 12, 553.0),
        ("chunks_per_sweep", None, {}, 0.0, 12, None),
    ],
)
def test_stream_readers(monkeypatch, name, counter, labels, value, sweeps, want):
    reader = harness.load_part(REPO, "metrics", name)
    reg = MetricsRegistry(enabled=True)
    reg.counter("repro_engine_sweeps_total").inc(sweeps)
    if counter is not None:
        fam = reg.counter(counter, labelnames=tuple(labels))
        (fam.labels(**labels) if labels else fam).inc(value)
    monkeypatch.setattr(reader, "REGISTRY", reg)
    got = reader.read(object())
    assert got == pytest.approx(want) if want is not None else got is None


def test_each_metric_applies_to_its_cells():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    metrics = {m["name"]: m for m in spec["per_layer"]}
    for name in BOTH:
        assert harness._metric_applies(metrics[name], OLD)
        assert harness._metric_applies(metrics[name], NEW)
    for name in STREAM:
        assert metrics[name]["layer"] == "stream" and metrics[name]["moves"] == "teps"
        assert harness._metric_applies(metrics[name], NEW)
        assert not harness._metric_applies(metrics[name], OLD)


def test_a_disk_cell_runs_through_the_harness(on_cpu, tmp_path, capsys):  # noqa: F811
    root = make_checkout(tmp_path, config=DISK)
    # a seed of its own: the harness's work directory is named by cell and seed,
    # and test_bench_harness.py may run the same cell name in another worker
    traced, out = run(root, capsys, trace=1, seed=2**31 + 17)
    assert traced["correct"] is True
    assert "tier=disk" in out
    # the registry is the process's: earlier runs in this process dilute the ratios
    assert all(traced["metrics"][k]["value"] > 0 for k in STREAM + ("build_s", "tiles_per_sweep"))
