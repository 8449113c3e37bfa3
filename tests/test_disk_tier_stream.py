"""The disk tier's streamed sweep at a small Graph500-shaped graph.

A ``.dsss`` store opened under the benchmark's disk tier (``bench/tiers/
disk.py``): the budget pins the configured share of the tile stream, the
rest is sliced from the mmap'd file every sweep. The ranks are the device
tier's bit for bit, and the streaming counters charge exactly the streamed
chunks.
"""
import importlib.util
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core import ExecutionPlan, GraphSession, PageRank, build_dsss
from repro.core.iomodel import packed_h2d_bytes
from repro.graph.preprocess import degree_and_densify
from repro.obs import REGISTRY, TRACER, disable_tracing, enable_tracing

REPO = Path(__file__).resolve().parents[1]
CHUNKS = "repro_engine_stream_chunks_total"
TILES = "repro_engine_tiles_swept_total"
FETCH = "repro_engine_stream_fetch_seconds_total"
CONFIG = {"generator": "graph500", "scale": 11, "edge_factor": 16,
          "initiator": [0.57, 0.19, 0.19], "undirected": True, "P": 4,
          "tier": "disk", "pinned_tile_share": 0.43, "host_memory_budget": 0}
ITERS = 3


def _part(kind, name):
    path = REPO / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"part_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def graph():
    src, dst, _ = _part("generators", "graph500").edges(2**31 + 11, CONFIG)
    return build_dsss(degree_and_densify(src, dst, drop_self_loops=True), CONFIG["P"])


def _disk(graph, tmp_path, **config):
    return _part("tiers", "disk").open_session(graph, dict(CONFIG, **config), tmp_path)


def _plan():
    return ExecutionPlan(PageRank(), max_iters=ITERS, tol=0.0)


def _counters():
    return np.array([REGISTRY.value(CHUNKS), REGISTRY.value(TILES), REGISTRY.value(FETCH)])


@pytest.mark.parametrize("share", [0.0, 0.25, 0.43, 0.9])
def test_budget_pins_the_configured_share(graph, tmp_path, share):
    sess = _disk(graph, tmp_path, pinned_tile_share=share)
    compiled = sess.compile(_plan())
    assert (compiled.residency, compiled.choice.strategy) == ("disk", "spu")
    splan = sess.packed_stream_plan("spu", PageRank().attr_bytes)
    e_valid = sess._staged.packed_host(sess.packing).e_valid.astype(np.int64)
    pinned = int(e_valid[: splan.pin_tiles].sum())
    assert pinned <= share * graph.m
    # within one tile: the next tile would overrun the share
    assert splan.pin_tiles == len(e_valid) or pinned + e_valid[splan.pin_tiles] > share * graph.m
    assert 0 < splan.pin_tiles < splan.num_tiles or share == 0.0


def test_ranks_are_the_device_tiers_bit_for_bit(graph, tmp_path):
    want = GraphSession(graph).run(_plan())
    got = _disk(graph, tmp_path).run(_plan())
    assert got.iterations == want.iterations == ITERS
    np.testing.assert_array_equal(got.attrs, want.attrs)


def test_counters_charge_the_streamed_chunks(graph, tmp_path):
    sess = _disk(graph, tmp_path)
    splan = sess.packed_stream_plan("spu", PageRank().attr_bytes)
    streamed = splan.num_tiles - splan.pin_tiles
    assert streamed > 0 and splan.chunk_tiles > 1  # several chunks, one shorter
    before = _counters()
    res = sess.run(_plan())
    chunks, tiles, fetch_s = _counters() - before
    assert tiles - ITERS * splan.pin_tiles == ITERS * streamed  # the pinned slab, then the chunks
    assert chunks == ITERS * math.ceil(streamed / splan.chunk_tiles)
    assert fetch_s > 0
    raw = ITERS * packed_h2d_bytes(streamed, splan.tile_edges)
    assert res.meters.bytes_disk_read == raw  # host_memory_budget=0: every chunk from the file
    assert res.meters.bytes_h2d == raw  # the pinned prefix is not charged


def test_the_device_tier_charges_no_stream_counter(graph):
    before = _counters()
    GraphSession(graph).run(_plan())
    chunks, tiles, fetch_s = _counters() - before
    assert chunks == fetch_s == 0
    assert tiles == ITERS * graph.packed_sweep("adaptive").num_tiles  # swept, none streamed


def test_fetch_spans_nest_in_chunks_and_the_store_has_spans(graph, tmp_path):
    enable_tracing()
    mark = TRACER.mark()
    try:
        sess = _disk(graph, tmp_path)
        sess.run(_plan())
    finally:
        disable_tracing()
    spans = TRACER.spans(since=mark)
    names = [s.name for s in spans]
    assert names.count("store.write") == 1 and names.count("store.open") == 1
    chunks = [s for s in spans if s.name == "sweep.chunk"]
    fetches = [s for s in spans if s.name == "sweep.fetch"]
    assert len(fetches) == len(chunks) > 0
    for f in fetches:
        assert any(c.ts <= f.ts and f.ts + f.dur <= c.ts + c.dur for c in chunks)
