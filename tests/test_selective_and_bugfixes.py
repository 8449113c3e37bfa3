"""Frontier-aware selective execution + the rode-along bugfix regressions.

Deterministic (tier-1) lane for this PR's contract:

* **Batched aux** — ``GraphSession._execute`` used to build the batch's
  aux arrays from query 0's kwargs alone (``make_aux(g, **kwargs_list[0])``),
  silently applying them to every query: a batch of ``MaxLabelForward``
  plans with different masks returned wrong labels for queries 1..K-1.
  Differing-but-stackable aux now runs vmapped with a leading query axis
  (and ``run_batch`` fuses such plans instead of falling back).
* **Kwarg validation** — unknown ``program_kwargs`` names used to be
  swallowed by the lifecycle methods' ``**kw`` catch-alls (a typo'd
  ``"rot"`` ran BFS from vertex 0); :class:`ExecutionPlan` now validates
  names against ``program.accepted_kwargs()`` at construction.
* **wcc driver** — the driver silently accepted an asymmetric
  :class:`DSSSGraph` (returning per-direction pseudo-components) and
  dropped the ``residency``/``execution`` axes every other driver plumbs.
* **Selective execution** — ``activity="auto"`` (default) must be
  bit-identical to ``activity="off"`` while strictly shrinking physical
  ``bytes_h2d`` once the frontier is narrower than the layout
  (the hypothesis lane, tests/test_selective_property.py, generalises
  this across the strategy × execution × residency grid).
"""
import numpy as np
import pytest

from repro.core import (
    BFS,
    ExecutionPlan,
    GraphSession,
    PageRank,
    build_dsss,
    wcc,
)
from repro.core.vertex_programs import MaxLabelForward
from repro.graph.generators import erdos_renyi, ring, rmat
from repro.graph.preprocess import degree_and_densify


def _graph(n=96, m=500, seed=0, P=4):
    src, dst = erdos_renyi(n, m, seed=seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    return build_dsss(el, P)


# ---------------------------------------------------------------------------
# Bugfix 1: per-query aux in fused batches.
# ---------------------------------------------------------------------------
class TestBatchedAux:
    def _masks(self, g):
        full = np.ones(g.n_pad, np.int32)
        half = np.ones(g.n_pad, np.int32)
        half[g.n // 2 : g.n] = 0  # second half are spectators
        return full, half

    def test_execute_with_differing_aux_matches_individual_runs(self):
        # Regression: the old _execute applied query 0's mask to every
        # query, so query 1's labels leaked across its mask boundary.
        g = _graph()
        sess = GraphSession(g)
        full, half = self._masks(g)
        plan = ExecutionPlan(MaxLabelForward(), strategy="spu", max_iters=g.n + 1)
        batch = sess._execute(
            plan, [{"mask": full}, {"mask": half}]
        )
        assert batch.fused
        for mask, res in zip((full, half), batch.results):
            ref = sess.run(plan.with_kwargs(mask=mask))
            np.testing.assert_array_equal(res.attrs, ref.attrs)

    def test_run_batch_fuses_per_query_masks(self):
        # Stackable-but-differing aux now *fuses* (one streamed pass)
        # instead of silently downgrading to sequential runs.
        g = _graph(seed=1)
        sess = GraphSession(g)
        full, half = self._masks(g)
        plans = [
            ExecutionPlan(
                MaxLabelForward(),
                strategy="dpu",
                max_iters=g.n + 1,
                program_kwargs={"mask": m},
            )
            for m in (full, half)
        ]
        batch = sess.run_batch(plans)
        assert batch.fused
        for plan, res in zip(plans, batch.results):
            ref = sess.run(plan)
            np.testing.assert_array_equal(res.attrs, ref.attrs)

    def test_identical_aux_still_shared(self):
        g = _graph(seed=2)
        sess = GraphSession(g)
        plans = [
            ExecutionPlan(
                BFS(), strategy="spu", max_iters=g.n + 1,
                program_kwargs={"root": r},
            )
            for r in (0, 5)
        ]
        batch = sess.run_batch(plans)
        assert batch.fused
        for plan, res in zip(plans, batch.results):
            ref = sess.run(plan)
            np.testing.assert_array_equal(res.attrs, ref.attrs)


# ---------------------------------------------------------------------------
# Bugfix 2: unknown program_kwargs raise at plan construction.
# ---------------------------------------------------------------------------
class TestKwargValidation:
    def test_kwargless_program_rejects_any_kwarg(self):
        # WCC is the remaining kwargless program (PageRank grew
        # personalize/reset_dist); unknown names on a program with
        # accepted kwargs get the name-listing error instead.
        from repro.core.vertex_programs import WCC

        with pytest.raises(TypeError, match="accepts no program_kwargs"):
            ExecutionPlan(WCC(), program_kwargs={"root": 3})
        with pytest.raises(TypeError, match="accepted kwargs"):
            ExecutionPlan(PageRank(), program_kwargs={"root": 3})

    def test_typo_rejected_with_accepted_names(self):
        # Pre-fix this ran BFS silently from vertex 0.
        with pytest.raises(TypeError, match=r"rot.*root"):
            ExecutionPlan(BFS(), program_kwargs={"rot": 3})

    def test_known_kwargs_accepted(self):
        ExecutionPlan(BFS(), program_kwargs={"root": 3})
        ExecutionPlan(
            MaxLabelForward(),
            program_kwargs={"mask": np.ones(8, np.int32)},
        )

    def test_accepted_kwargs_harvest(self):
        assert PageRank().accepted_kwargs() == {"personalize", "reset_dist"}
        assert BFS().accepted_kwargs() == {"root"}
        assert MaxLabelForward().accepted_kwargs() == {"labels", "mask"}


# ---------------------------------------------------------------------------
# Bugfix 3: the wcc driver's symmetry contract and session axes.
# ---------------------------------------------------------------------------
class TestWCCDriver:
    def test_asymmetric_dsss_rejected(self):
        # Drop the ring's wrap edge → directed path 0→1→…→31, which has
        # in_degree != out_degree at the endpoints.
        src, dst = ring(32)
        el = degree_and_densify(src[:-1], dst[:-1])
        g = build_dsss(el, 4)
        with pytest.raises(ValueError, match="symmetrized"):
            wcc(g)

    def test_symmetrized_dsss_matches_edgelist_across_axes(self):
        src, dst = erdos_renyi(80, 200, seed=3)
        el = degree_and_densify(src, dst, drop_self_loops=True)
        ref = wcc(el, P=4)
        g_sym = build_dsss(el.symmetrized(), 4)
        for kw in (
            {},
            {"residency": "host", "memory_budget": 0},
            {"execution": "per_block"},
        ):
            res = wcc(g_sym, **kw)
            np.testing.assert_array_equal(res.attrs, ref.attrs)


# ---------------------------------------------------------------------------
# Tentpole smoke: selective ≡ off, with strictly fewer physical bytes.
# ---------------------------------------------------------------------------
class TestSelectiveExecution:
    def test_activity_axis_validated(self):
        with pytest.raises(ValueError, match="activity"):
            ExecutionPlan(BFS(), activity="sometimes")

    def test_selective_bit_identical_and_strictly_fewer_bytes(self):
        # A long directed path: the BFS frontier is one vertex per sweep,
        # so late sweeps touch exactly one interval out of P — streaming
        # must skip the rest.
        src, dst = ring(512)
        el = degree_and_densify(src[:-1], dst[:-1])  # path, no wrap
        g = build_dsss(el, 8)
        plan_kw = dict(max_iters=el.n + 1, program_kwargs={"root": 0})
        on_s = GraphSession(g, memory_budget=0, residency="host")
        off_s = GraphSession(g, memory_budget=0, residency="host")
        on = on_s.run(ExecutionPlan(BFS(), **plan_kw))
        off = off_s.run(ExecutionPlan(BFS(), activity="off", **plan_kw))
        np.testing.assert_array_equal(on.attrs, off.attrs)
        assert on.iterations == off.iterations
        assert 0 < on.meters.bytes_h2d < off.meters.bytes_h2d
        # The log shows a genuinely narrow frontier...
        assert any(log.sum() == 1 for log in on.activity_log)
        # ...and activity="off" records full sweeps.
        assert all(log.all() for log in off.activity_log)

    def test_late_iteration_skip_ratio_on_rmat(self):
        """BFS on an R-MAT graph streamed under a tight budget: each
        sweep's physical ``bytes_h2d`` is the closed form over its logged
        frontier, and the trailing sweeps, whose frontier has collapsed
        to at most P/2 intervals, stream at least 5x fewer bytes than full
        sweeps would."""
        from repro.core.iomodel import (
            packed_h2d_bytes,
            selective_streamed_tiles,
        )

        P = 16
        el = degree_and_densify(*rmat(13, 4, seed=0), drop_self_loops=True)
        g = build_dsss(el, P)
        # Nothing pins and chunks are fine-grained: the regime where
        # skipping inactive streamed chunks can bite.
        budget = int((2 * g.n_pad * 8 + g.total_edge_bytes(8)) * 0.05)
        runs = {}
        for activity in ("auto", "off"):
            sess = GraphSession(g, memory_budget=budget, residency="host")
            runs[activity] = sess, sess.run(
                ExecutionPlan(
                    BFS(), strategy="spu", max_iters=g.n + 1,
                    execution="packed", activity=activity,
                    program_kwargs={"root": 0},
                )
            )
        sess, on = runs["auto"]
        off = runs["off"][1]
        np.testing.assert_array_equal(on.attrs, off.attrs)
        assert on.iterations == off.iterations
        splan = sess.packed_stream_plan("spu", BFS().attr_bytes)
        full_sweep = packed_h2d_bytes(
            splan.num_tiles - splan.pin_tiles, splan.tile_edges,
            run_slots=splan.run_slots,
        )
        per_sweep = [
            packed_h2d_bytes(
                selective_streamed_tiles(
                    sess._packed_tile_activity(log),
                    splan.pin_tiles,
                    splan.chunk_tiles,
                ),
                splan.tile_edges,
                run_slots=splan.run_slots,
            )
            for log in on.activity_log
        ]
        assert sum(per_sweep) == on.meters.bytes_h2d
        assert off.meters.bytes_h2d == full_sweep * off.iterations
        frontier = [int(log.sum()) for log in on.activity_log]
        k = len(frontier)
        while k > 0 and frontier[k - 1] <= P // 2:
            k -= 1
        late = range(k, len(frontier))
        assert len(late) > 0, frontier
        late_bytes = sum(per_sweep[i] for i in late)
        assert full_sweep * len(late) >= 5 * late_bytes, (frontier, per_sweep)

    def test_non_monotone_programs_ignore_activity(self):
        g = _graph(seed=4)
        sess = GraphSession(g, memory_budget=0, residency="host")
        plan = ExecutionPlan(PageRank(), max_iters=3, tol=0.0)
        assert sess.compile(plan).activity == "off"
        res = sess.run(plan)
        assert all(log.all() for log in res.activity_log)

    def test_estimate_parts_sum_to_estimate(self):
        from repro.serving.server import (
            estimate_inflight_bytes,
            estimate_inflight_parts,
        )

        g = _graph(seed=5)
        sess = GraphSession(g, memory_budget=int(g.m * 12 * 0.5), residency="host")
        plan = ExecutionPlan(BFS(), max_iters=g.n + 1, program_kwargs={"root": 0})
        topo, attr = estimate_inflight_parts(sess, plan, 3)
        assert topo > 0 and attr > 0
        assert topo + attr == estimate_inflight_bytes(sess, plan, 3)
