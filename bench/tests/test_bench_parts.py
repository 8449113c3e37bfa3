"""The benchmark's yardstick on the CPU: generator, references, bytes, trace."""
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import reference, roofline, trace
from bench.generators import graph500 as graphgen

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
ABC = (0.57, 0.19, 0.19)


def test_generator_is_fixed_by_the_whole_seed():
    a = graphgen.kronecker_edges(2**31 + 5, 10, 8, ABC)
    b = graphgen.kronecker_edges(2**31 + 5, 10, 8, ABC)
    c = graphgen.kronecker_edges(2**31 + 6, 10, 8, ABC)
    d = graphgen.kronecker_edges(2**31 + 5 + 2**32, 10, 8, ABC)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], d[0])  # the high word of a 64-bit seed counts
    assert a[0].dtype == np.int32 and a[0].shape == (8 << 10,)
    assert a[0].min() >= 0 and a[0].max() < 1 << 10


def _shape(src, dst, n):
    out = np.sort(np.bincount(src, minlength=n))[::-1]
    return {
        "isolated_out": float((out == 0).mean()),
        "top1pct_share": float(out[: n // 100].sum() / out.sum()),
        "self_loops": float((src == dst).mean()),
    }


def test_generator_is_shaped_like_rmat():
    """Label permutation leaves degree statistics alone: they match the
    numpy R-MAT with the same initiator, seed for seed within noise."""
    from repro.graph.generators import rmat

    scale = 14
    ours = _shape(*graphgen.kronecker_edges(3, scale, 8, ABC), 1 << scale)
    plain = _shape(*(x.astype(np.int32) for x in rmat(scale, 8, *ABC, seed=3)), 1 << scale)
    for k in ours:
        assert ours[k] == pytest.approx(plain[k], rel=0.1, abs=1e-3), k
    assert ours["top1pct_share"] > 0.15  # heavy skew: far above 1% for 1% of labels


def test_undirected_graphs_are_handed_on_as_arcs_both_ways():
    cfg = {"scale": 10, "edge_factor": 16, "initiator": list(ABC)}
    src, dst = graphgen.kronecker_edges(2**31 + 3, 10, 16, ABC)
    s1, d1, n1 = graphgen.edges(2**31 + 3, dict(cfg, undirected=False))
    s2, d2, n2 = graphgen.edges(2**31 + 3, dict(cfg, undirected=True))
    assert n1 == n2 == 1 << 10
    assert np.array_equal(s1, src) and np.array_equal(d1, dst)
    assert np.array_equal(s2, np.concatenate([src, dst]))
    assert np.array_equal(d2, np.concatenate([dst, src]))
    g = reference.clean_edges(s2, d2, n2)
    fwd = set(zip(g.src.tolist(), g.dst.tolist()))
    assert fwd == {(d, s) for s, d in fwd}  # every arc has its reverse


def test_label_permutation_is_a_bijection():
    import jax.numpy as jnp

    for bits in (1, 5, 12):
        x = jnp.arange(1 << bits, dtype=jnp.int32)
        y = np.asarray(graphgen._permute(graphgen.seed_key(9), x, bits))
        assert np.array_equal(np.sort(y), np.arange(1 << bits))


def test_labels_are_permuted():
    """Unpermuted R-MAT puts hubs at low labels; the permutation spreads them."""
    n = 1 << 12
    src, _ = graphgen.kronecker_edges(4, 12, 8, ABC)
    deg = np.bincount(src, minlength=n)
    hubs = np.argsort(deg)[-40:]
    assert np.median(hubs) > n / 8


def test_sweep_bytes_from_known_sizes():
    # 10 vertices, 100 edges, float32: 100*(4+4) + 10*3*4
    assert roofline.sweep_min_bytes(10, 100, attr_bytes=4) == 920
    assert roofline.sweep_min_bytes(10, 100, attr_bytes=4, weighted=True) == 1320
    assert roofline.sweep_min_bytes(10, 100, attr_bytes=4, queries=2) == 100 * 12 + 10 * 24
    # a graph of 66.2 M arcs and 3.85 M vertices -> ~0.58 GB, ~0.7 ms on v5e
    nbytes = roofline.sweep_min_bytes(3_852_142, 66_186_966, attr_bytes=4)
    assert nbytes == 66_186_966 * 8 + 3_852_142 * 12
    assert nbytes / roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == pytest.approx(7.03e-4, rel=0.01)


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def _ev(name, lo, hi):
    return trace.Event(name, float(lo), float(hi))


def test_trace_reduction_arithmetic():
    host = [_ev("bench.job", 100, 200), _ev("bench.job", 210, 300), _ev("PjitFunction(f)", 150, 190)]
    ops = [_ev("a", 90, 120), _ev("b", 110, 140), _ev("a", 160, 170), _ev("c", 250, 310)]
    r = trace.reduce_trace(trace.TraceEvents({"/device:TPU:0": ops}, host))
    # window [100, 300]; busy [100,140] + [160,170] + [250,300] = 100 ns
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["device_ops"][0] == ["c", pytest.approx(50e-9)]
    gaps = {tuple(g[:1]): g[1] for g in r["idle_gaps"]}
    assert gaps[("bench.job",)] == pytest.approx(80e-9)  # [170, 250] spans two jobs' gap
    assert r["idle_gaps"][1] == ["bench.job / PjitFunction(f)", pytest.approx(20e-9)]
    assert trace.reduce_trace(trace.TraceEvents({}, host)) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    """Two 2-sweep PageRank jobs traced on one v5e at scale 12."""
    ev = trace.read_trace(DATA / "v5e_pagerank_s12")
    r = trace.reduce_trace(ev)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert r["device_ops"][0][0] == "jit__packed_sweep_impl: %while.1"  # the scan over tiles
    assert all(label.startswith("bench.job") for label, _ in r["idle_gaps"])
    expected = json.loads((DATA / "v5e_pagerank_s12.json").read_text())
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)


def _engine_graph(src, dst, P=4):
    from repro.core import GraphSession, build_dsss
    from repro.graph.preprocess import degree_and_densify

    el = degree_and_densify(src, dst, drop_self_loops=True)
    return GraphSession(build_dsss(el, P)), el.id_to_index


def test_references_agree_with_the_engine():
    from repro.core import BFS, INF_DEPTH, ExecutionPlan, PageRank

    scale = 11
    src, dst = graphgen.kronecker_edges(5, scale, 8, ABC)
    sess, ids = _engine_graph(src, dst)
    g = reference.clean_edges(src, dst, 1 << scale)
    assert sess.graph.n == g.n and sess.graph.m == g.m
    ranks = sess.run(ExecutionPlan(PageRank(damping=0.85), max_iters=10, tol=0.0)).attrs
    got = np.zeros(g.num_labels)
    got[ids] = ranks
    errs = reference.rank_errors(got, reference.pagerank_ref(g, 0.85, 10), g.present)
    assert errs["rank_max_rel_err"] < 1e-5 and errs["rank_l1_err"] < 1e-6
    root = int(np.flatnonzero(sess.graph.out_degree[: g.n] > 0)[7])
    depth = sess.run(ExecutionPlan(BFS(), max_iters=g.n + 1, program_kwargs={"root": root})).attrs
    got = np.full(g.num_labels, reference.UNREACHED)
    got[ids] = np.where(depth == INF_DEPTH, reference.UNREACHED, depth)
    want = reference.bfs_ref(g, int(ids[root]))
    assert (want >= 1).sum() > 10
    np.testing.assert_array_equal(got, want)


def test_clean_edges_drops_loops_and_duplicates():
    g = reference.clean_edges(np.array([1, 1, 2, 3, 3]), np.array([2, 2, 2, 1, 4]), 6)
    assert g.src.tolist() == [1, 3, 3] and g.dst.tolist() == [2, 1, 4]
    assert g.present.tolist() == [False, True, True, True, True, False]
    assert g.n == 4 and g.m == 3


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keeps_to_its_contract():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file() and len(c["source"]) <= 200
        assert len(c["why"]) <= 200
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert (REPO / "bench/traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert (REPO / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
