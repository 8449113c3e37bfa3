"""Property tests for out-of-core host-streamed execution (the tentpole).

Contract under test (see ``core/session.py``):

1. **Bit-identity** — host-streamed execution produces bit-identical
   attributes to device-resident execution, for PageRank / BFS / WCC,
   across strategies and budgets forcing 0%, partial and 100% edge
   residency. The modelled byte meters are also identical: under "host"
   the edge charges coincide with real transfers instead of being
   simulated.
2. **Budget enforcement** — with ``memory_budget`` below the total staged
   bytes, the persistently device-pinned topology plus both attribute
   copies stays ≤ budget (staged accounting), and the transient
   streaming ring adds at most two *stream units* on top of the pinned
   set — two sub-shard blocks for per-block execution, two tile chunks
   (``PackedStreamPlan.max_chunk_model_bytes``) for the packed compiled
   path, which since adaptive tiling no longer downgrades under host
   residency and is what these sessions run by default.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BFS,
    ExecutionPlan,
    GraphSession,
    PageRank,
    WCC,
    build_dsss,
)
from repro.graph.generators import erdos_renyi
from repro.graph.preprocess import degree_and_densify

from repro.core.session import MODEL_METER_FIELDS as MODELLED_FIELDS

PROGRAMS = {
    "pagerank": lambda: (PageRank(), {}, 6, 0.0),
    "bfs": lambda: (BFS(), {"root": 0}, 200, 1e-10),
    "wcc": lambda: (WCC(), {}, 200, 1e-10),
}


def _graph(seed, P, n=100, m=450):
    src, dst = erdos_renyi(n, m, seed=seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    return build_dsss(el, P)


def _budget(g, frac):
    """frac of (both attribute copies + all edge bytes): 0.0 → nothing
    fits, ≥1.0 → 100% residency."""
    return int((2 * g.n_pad * 8 + g.m * 8) * frac)


class TestHostDeviceBitIdentity:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(1, 5),
        strategy=st.sampled_from(["spu", "dpu", "mpu"]),
        prog_name=st.sampled_from(["pagerank", "bfs", "wcc"]),
        frac=st.sampled_from([0.0, 0.3, 0.6, 1.5]),
    )
    def test_host_streamed_equals_device_resident(
        self, seed, P, strategy, prog_name, frac
    ):
        g = _graph(seed, P)
        prog, kw, iters, tol = PROGRAMS[prog_name]()
        budget = _budget(g, frac)
        plan = ExecutionPlan(
            prog, strategy=strategy, max_iters=iters, tol=tol, program_kwargs=kw
        )
        dev = GraphSession(g, memory_budget=budget, residency="device").run(plan)
        host = GraphSession(g, memory_budget=budget, residency="host").run(plan)
        # Bit-identical, not approximately equal: the streamed blocks are
        # the same padded buffers, so every reduction runs in the same
        # order on the same values.
        np.testing.assert_array_equal(host.attrs, dev.attrs)
        assert host.iterations == dev.iterations
        assert host.converged == dev.converged
        for field in MODELLED_FIELDS:
            assert getattr(host.meters, field) == getattr(dev.meters, field), field
        # Device mode simulates the slow tier; host mode performs it. The
        # default (packed) host path streams the active chunks of its
        # non-pinned tile suffix every sweep: the exact physical volume is
        # the frontier-aware closed form over the run's activity_log
        # (all-ones for non-monotone PageRank, so the oracle degenerates
        # to the full-stream form there).
        from repro.core.iomodel import packed_h2d_bytes, selective_streamed_tiles

        assert dev.meters.bytes_h2d == 0.0
        host_sess = GraphSession(g, memory_budget=budget, residency="host")
        compiled = host_sess.compile(plan)
        assert compiled.execution == "packed"
        splan = host_sess.packed_stream_plan(
            compiled.choice.strategy, prog.attr_bytes
        )
        expected_h2d = sum(
            packed_h2d_bytes(
                selective_streamed_tiles(
                    host_sess._packed_tile_activity(log_s),
                    splan.pin_tiles,
                    splan.chunk_tiles,
                ),
                splan.tile_edges,
                run_slots=splan.run_slots,
            )
            for log_s in host.activity_log
        )
        assert host.meters.bytes_h2d == expected_h2d

    def test_unlimited_budget_bit_identical_to_budgeted_host(self):
        """The acceptance identity: budget below staged bytes, results equal
        the unlimited-budget run bit for bit."""
        g = _graph(seed=3, P=4)
        plan = ExecutionPlan(PageRank(), strategy="spu", max_iters=8, tol=0.0)
        unlimited = GraphSession(g).run(plan)
        tight = GraphSession(
            g, memory_budget=_budget(g, 0.4), residency="host"
        ).run(plan)
        np.testing.assert_array_equal(tight.attrs, unlimited.attrs)

    def test_shim_accepts_equivalent_residency_on_shared_session(self):
        """'auto' with a budget resolves to 'host'; passing the resolved
        name to the shim over that session must not be rejected."""
        from repro.core import NXGraphEngine

        g = _graph(seed=2, P=3)
        sess = GraphSession(g, memory_budget=_budget(g, 0.5))  # auto → host
        assert sess.resolved_residency() == "host"
        eng = NXGraphEngine(g, PageRank(), residency="host", session=sess)
        assert eng.session is sess
        with pytest.raises(ValueError, match="residency"):
            NXGraphEngine(g, PageRank(), residency="device", session=sess)

    def test_plan_level_residency_override(self):
        g = _graph(seed=4, P=3)
        sess = GraphSession(g, memory_budget=_budget(g, 0.3), residency="device")
        base = ExecutionPlan(PageRank(), strategy="dpu", max_iters=4, tol=0.0)
        dev = sess.run(base)
        host = sess.run(
            ExecutionPlan(
                PageRank(), strategy="dpu", max_iters=4, tol=0.0, residency="host"
            )
        )
        np.testing.assert_array_equal(host.attrs, dev.attrs)
        assert dev.meters.bytes_h2d == 0.0 and host.meters.bytes_h2d > 0


class TestBudgetEnforcement:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 30),
        P=st.integers(1, 5),
        frac=st.floats(0.0, 1.2),
    )
    def test_pinned_set_plus_attrs_within_budget(self, seed, P, frac):
        g = _graph(seed, P)
        prog = PageRank()
        Ba = prog.attr_bytes
        budget = _budget(g, frac)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        res = sess.run(ExecutionPlan(prog, strategy="spu", max_iters=2, tol=0.0))
        pinned_model, pinned_actual = sess.pinned_device_bytes()
        if pinned_model > 0:
            # Staged accounting: persistent residency honors B_M.
            assert pinned_model + 2 * g.n_pad * Ba <= budget
        # Transient streaming ring: at most current + prefetched stream
        # units (tile chunks for the default packed path) on top.
        splan = sess.packed_stream_plan("spu", Ba)
        assert (
            res.meters.peak_device_graph_bytes
            <= pinned_model + 2 * splan.max_chunk_model_bytes
        )

    def test_zero_budget_streams_everything_every_sweep(self):
        g = _graph(seed=5, P=4)
        sess = GraphSession(g, memory_budget=0, residency="host")
        res = sess.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=3, tol=0.0))
        assert sess.pinned_device_bytes() == (0.0, 0.0)
        total_model = sum(h["e"] for h in sess.host_blocks.values()) * sess.Be
        assert res.meters.bytes_read_edges == res.iterations * total_model

    def test_full_budget_streams_nothing(self):
        g = _graph(seed=6, P=4)
        sess = GraphSession(g, memory_budget=_budget(g, 2.0), residency="host")
        res = sess.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=3, tol=0.0))
        assert res.meters.bytes_h2d == 0.0
        assert res.meters.bytes_read_edges == 0.0
        pinned_model, _ = sess.pinned_device_bytes()
        assert pinned_model == sum(h["e"] for h in sess.host_blocks.values()) * sess.Be

    def test_device_peak_below_budget_with_headroom(self):
        """The acceptance inequality end-to-end: peak device graph bytes +
        both attribute copies ≤ budget + the documented two-stream-unit
        slack, on a genuinely out-of-core budget."""
        g = _graph(seed=7, P=4, n=200, m=1200)
        prog = PageRank()
        Ba = prog.attr_bytes
        total = 2 * g.n_pad * Ba + g.m * 8
        budget = int(total * 0.6)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        res = sess.run(ExecutionPlan(prog, strategy="spu", max_iters=3, tol=0.0))
        splan = sess.packed_stream_plan("spu", Ba)
        assert budget < total  # genuinely out-of-core
        assert (
            res.meters.peak_device_graph_bytes + 2 * g.n_pad * Ba
            <= budget + 2 * splan.max_chunk_model_bytes
        )

    def test_per_block_ring_still_bounded(self):
        """The legacy per-block streaming path keeps its two-block ring."""
        g = _graph(seed=9, P=4, n=200, m=1200)
        prog = PageRank()
        budget = _budget(g, 0.5)
        sess = GraphSession(g, memory_budget=budget, residency="host")
        res = sess.run(
            ExecutionPlan(
                prog, strategy="spu", max_iters=2, tol=0.0,
                execution="per_block",
            )
        )
        pinned_model, _ = sess.pinned_device_bytes()
        max_block = max(h["e"] for h in sess.host_blocks.values()) * sess.Be
        assert res.meters.bytes_h2d > 0
        assert (
            res.meters.peak_device_graph_bytes <= pinned_model + 2 * max_block
        )

    def test_pinned_blocks_released_when_strategy_changes(self):
        """SPU pins the leftover set; a following DPU plan must not keep
        those device copies alive (budget would silently be exceeded)."""
        g = _graph(seed=8, P=4)
        sess = GraphSession(g, memory_budget=_budget(g, 0.8), residency="host")
        sess.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=2, tol=0.0))
        assert sess.pinned_device_bytes()[0] > 0
        sess.run(ExecutionPlan(PageRank(), strategy="dpu", max_iters=2, tol=0.0))
        assert sess.pinned_device_bytes() == (0.0, 0.0)


class TestBatchedHostStreaming:
    def test_batched_queries_stream_edges_once(self):
        """K BFS sources over a host-streamed session still pay the edge
        transfers once per sweep, not K× — the semi-external-memory win."""
        g = _graph(seed=9, P=1, n=80, m=500)
        sess = GraphSession(g, memory_budget=0, residency="host")
        roots = [0, 3, 7, 11]
        plans = [
            ExecutionPlan(BFS(), strategy="dpu", max_iters=200, program_kwargs={"root": r})
            for r in roots
        ]
        batch = sess.run_batch(plans)
        assert batch.fused
        single = sess.run(plans[0])
        per_batch = batch.meters.per_iteration()
        per_single = single.meters.per_iteration()
        assert per_batch.bytes_read_edges == per_single.bytes_read_edges > 0
        assert per_batch.bytes_h2d == per_single.bytes_h2d > 0
        for res, root in zip(batch, roots):
            ref = GraphSession(g).run(
                ExecutionPlan(BFS(), strategy="dpu", max_iters=200, program_kwargs={"root": root})
            )
            np.testing.assert_array_equal(res.attrs, ref.attrs)
