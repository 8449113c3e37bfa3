"""Property tests for frontier-aware selective execution (the tentpole).

Contract under test (see ``core/session.py`` and ``core/iomodel.py``):

1. **Bit-identity** — for monotone programs (BFS / SSSP / WCC),
   ``activity="auto"`` produces bit-identical attributes, outputs and
   iteration counts to ``activity="off"`` across strategy ∈
   {spu, dpu, mpu} × execution ∈ {per_block, packed} × residency ∈
   {device, host}: skipped work contributes exact ⊕-identities, never a
   different fold order.
2. **Meter exactness** — the physical byte meters of a selective run are
   reconstructed *exactly* by the iomodel activity closed forms applied
   to the run's per-sweep ``activity_log``:
   ``packed_h2d_bytes(selective_streamed_tiles(...))`` for packed host
   streaming, ``streamed_block_bytes(..., active_rows)`` for per-block
   host streaming, ``selective_edge_bytes`` for the modelled slow-tier
   edge traffic. Model meters additionally agree across execution modes
   at the same activity setting (packed charges from metadata, per-block
   from the blocks it actually walks).
3. **Strict shrink** — once the frontier narrows below a full sweep,
   physical transfers are strictly smaller than the ``activity="off"``
   baseline (given the layout is skippable at all: more than one
   streamed chunk and per-tile spans narrower than the whole range).

The deterministic companions live in tests/test_selective_and_bugfixes.py
(tier-1) and the disk tier is exercised below on a concrete ``.dsss``
store (disk chunk skipping uses the ``pin+host_tiles`` boundary).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BFS,
    ExecutionPlan,
    GraphSession,
    SSSP,
    WCC,
    build_dsss,
)
from repro.core.iomodel import (
    packed_h2d_bytes,
    selective_edge_bytes,
    selective_streamed_tiles,
    streamed_block_bytes,
)
from repro.core.session import MODEL_METER_FIELDS
from repro.core.session import _host_block_nbytes
from repro.graph.generators import erdos_renyi, ring
from repro.graph.preprocess import degree_and_densify

PROGRAMS = {
    "bfs": lambda: (BFS(), {"root": 0}),
    "sssp": lambda: (SSSP(), {"root": 0}),
    "wcc": lambda: (WCC(), {}),
}


def _graph(seed, P, n=100, m=450):
    src, dst = erdos_renyi(n, m, seed=seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    return build_dsss(el, P)


def _path_graph(n, P):
    src, dst = ring(n)
    el = degree_and_densify(src[:-1], dst[:-1])  # directed path
    return build_dsss(el, P)


def _budget(g, frac):
    return int((2 * g.n_pad * 8 + g.m * 8) * frac)


def _run_pair(g, prog, kw, *, strategy, execution, residency, budget):
    """(selective result, off result) on independent sessions."""
    results = []
    for activity in ("auto", "off"):
        sess = GraphSession(g, memory_budget=budget, residency=residency)
        results.append(
            sess.run(
                ExecutionPlan(
                    prog,
                    strategy=strategy,
                    max_iters=g.n + 1,
                    execution=execution,
                    activity=activity,
                    program_kwargs=kw,
                )
            )
        )
    return results


class TestSelectiveBitIdentity:
    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(1, 5),
        strategy=st.sampled_from(["spu", "dpu", "mpu"]),
        execution=st.sampled_from(["per_block", "packed"]),
        residency=st.sampled_from(["device", "host"]),
        prog_name=st.sampled_from(["bfs", "sssp", "wcc"]),
        frac=st.sampled_from([0.0, 0.4, 1.5]),
    )
    def test_selective_equals_off(
        self, seed, P, strategy, execution, residency, prog_name, frac
    ):
        g = _graph(seed, P)
        prog, kw = PROGRAMS[prog_name]()
        on, off = _run_pair(
            g, prog, kw,
            strategy=strategy, execution=execution, residency=residency,
            budget=_budget(g, frac) if residency == "host" else None,
        )
        np.testing.assert_array_equal(on.attrs, off.attrs)
        np.testing.assert_array_equal(on.output, off.output)
        assert on.iterations == off.iterations
        assert on.converged == off.converged
        # The selective run never streams *more* than the baseline.
        assert on.meters.bytes_h2d <= off.meters.bytes_h2d


class TestMeterExactness:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(2, 5),
        strategy=st.sampled_from(["spu", "dpu", "mpu"]),
        prog_name=st.sampled_from(["bfs", "sssp", "wcc"]),
        frac=st.sampled_from([0.0, 0.4]),
    )
    def test_packed_h2d_matches_closed_form(
        self, seed, P, strategy, prog_name, frac
    ):
        g = _graph(seed, P)
        prog, kw = PROGRAMS[prog_name]()
        budget = _budget(g, frac)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        plan = ExecutionPlan(
            prog, strategy=strategy, max_iters=g.n + 1,
            execution="packed", program_kwargs=kw,
        )
        res = sess.run(plan)
        compiled = sess.compile(plan)
        assert compiled.activity == "selective"
        splan = sess.packed_stream_plan(compiled.choice.strategy, prog.attr_bytes)
        expected = sum(
            packed_h2d_bytes(
                selective_streamed_tiles(
                    sess._packed_tile_activity(log_s),
                    splan.pin_tiles,
                    splan.chunk_tiles,
                ),
                splan.tile_edges,
                run_slots=splan.run_slots,
            )
            for log_s in res.activity_log
        )
        assert res.meters.bytes_h2d == expected

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(2, 5),
        strategy=st.sampled_from(["spu", "dpu", "mpu"]),
        prog_name=st.sampled_from(["bfs", "sssp", "wcc"]),
        frac=st.sampled_from([0.0, 0.4]),
    )
    def test_per_block_h2d_and_edges_match_closed_forms(
        self, seed, P, strategy, prog_name, frac
    ):
        g = _graph(seed, P)
        prog, kw = PROGRAMS[prog_name]()
        budget = _budget(g, frac)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        plan = ExecutionPlan(
            prog, strategy=strategy, max_iters=g.n + 1,
            execution="per_block", program_kwargs=kw,
        )
        res = sess.run(plan)
        compiled = sess.compile(plan)
        assert compiled.activity == "selective"
        nbytes = {k: _host_block_nbytes(h) for k, h in sess.host_blocks.items()}
        edges = {k: h["e"] for k, h in sess.host_blocks.items()}
        expected_h2d = sum(
            streamed_block_bytes(nbytes, compiled.resident, log_s)
            for log_s in res.activity_log
        )
        expected_edges = sum(
            selective_edge_bytes(edges, compiled.resident, log_s, sess.Be)
            for log_s in res.activity_log
        )
        assert res.meters.bytes_h2d == expected_h2d
        assert res.meters.bytes_read_edges == expected_edges

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(1, 5),
        strategy=st.sampled_from(["spu", "dpu", "mpu"]),
        prog_name=st.sampled_from(["bfs", "sssp", "wcc"]),
        frac=st.sampled_from([0.0, 0.4, 1.5]),
    )
    def test_model_meters_agree_across_execution_modes(
        self, seed, P, strategy, prog_name, frac
    ):
        g = _graph(seed, P)
        prog, kw = PROGRAMS[prog_name]()
        budget = _budget(g, frac)
        runs = {}
        for execution in ("per_block", "packed"):
            sess = GraphSession(g, memory_budget=budget, residency="host")
            runs[execution] = sess.run(
                ExecutionPlan(
                    prog, strategy=strategy, max_iters=g.n + 1,
                    execution=execution, program_kwargs=kw,
                )
            )
        for field in MODEL_METER_FIELDS:
            assert getattr(runs["per_block"].meters, field) == getattr(
                runs["packed"].meters, field
            ), field


class TestStrictShrink:
    @pytest.mark.parametrize("execution", ["per_block", "packed"])
    def test_narrow_frontier_strictly_shrinks_stream(self, execution):
        # Directed path: the BFS frontier is a single interval almost
        # every sweep, and at n=1024 / P=8 each packed tile spans one
        # interval — every layout grain is skippable.
        g = _path_graph(1024, 8)
        on, off = _run_pair(
            g, BFS(), {"root": 0},
            strategy="spu", execution=execution, residency="host", budget=0,
        )
        np.testing.assert_array_equal(on.attrs, off.attrs)
        assert 0 < on.meters.bytes_h2d < off.meters.bytes_h2d
        # ≥5× on late-iteration-dominated runs is the acceptance bar for
        # this shape: 1022 of 1023 sweeps have a one-interval frontier.
        assert off.meters.bytes_h2d / on.meters.bytes_h2d >= 5.0

    def test_disk_tier_skips_chunk_reads(self, tmp_path):
        from repro.storage import write_dsss

        g = _path_graph(1024, 8)
        path = str(tmp_path / "g.dsss")
        write_dsss(g, path)
        runs = {}
        for activity in ("auto", "off"):
            sess = GraphSession.open(
                path, memory_budget=0, host_memory_budget=0
            )
            assert sess.resolved_residency() == "disk"
            runs[activity] = sess.run(
                ExecutionPlan(
                    BFS(), strategy="spu", max_iters=g.n + 1,
                    execution="packed", activity=activity,
                    program_kwargs={"root": 0},
                )
            )
        on, off = runs["auto"], runs["off"]
        np.testing.assert_array_equal(on.attrs, off.attrs)
        assert 0 < on.meters.bytes_disk_read < off.meters.bytes_disk_read
        assert 0 < on.meters.bytes_h2d < off.meters.bytes_h2d
