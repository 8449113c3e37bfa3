"""The fused Pallas kernel backend (``execution="packed_kernel"``) parity suite.

The kernel path's acceptance contract is the one the packed scan passes
in tests/test_packed_sweep.py, three-way: for every native schedule
(SPU/DPU/MPU), every program family (float-sum / int-min / weighted
float-min), every residency (device / host / disk) and both activity
modes, interpret-mode kernel results must equal ``per_block`` and
``packed`` — while actually dispatching the fused ``pallas_call`` (never
the scan, never the per-block primitives). The model ``Meters``,
``iterations`` and ``converged`` must be identical.

Min and max results must be bit-identical. Float sums (PageRank, PPR)
must agree within ``rtol=1e-5``, ``atol=1e-8``
(``tests/_fold_contract.py``): the kernel folds a destination's run
partials (ascending-edge-order windowed sum fold, ascending-run-order
hub scatter; see ``kernels/packed_sweep.py``) while the scan folds each
edge straight into its destination, a float32 reassociation of the
destination's in-edges.
"""
import dataclasses

import numpy as np
import pytest
from _fold_contract import assert_attrs_match

from repro.core import (
    BFS,
    ExecutionPlan,
    GraphSession,
    PageRank,
    SSSP,
    build_dsss,
)
from repro.core import session as session_mod
from repro.core.vertex_programs import MaxLabelForward
from repro.graph.generators import erdos_renyi
from repro.graph.preprocess import degree_and_densify
from repro.storage import write_dsss

STRATEGIES = ["spu", "dpu", "mpu"]

# (label, program factory, plan kwargs, weighted) — PageRank exercises the
# float-sum semiring (where the kernel's fold order must match the scan's
# association exactly), BFS the monotone int-min path with activity
# skipping, SSSP the weighted float-min path.
PROGRAMS = [
    ("pagerank", PageRank, dict(max_iters=6, tol=0.0), True),
    ("bfs", BFS, dict(max_iters=100, program_kwargs={"root": 0}), False),
    ("sssp", SSSP, dict(max_iters=100, program_kwargs={"root": 0}), True),
]

MODEL_FIELDS = session_mod.MODEL_METER_FIELDS

BUDGET = 720  # forces streaming + a strict 0 < Q < P MPU split
HOST_BUDGET = 3000  # partial host cache: some tile chunks hit disk


def _graph(n=150, m=900, seed=0, P=5, weighted=False):
    src, dst = erdos_renyi(n, m, seed=seed)
    w = None
    if weighted:
        rng = np.random.default_rng(seed)
        w = rng.uniform(0.1, 2.0, size=len(src)).astype(np.float32)
    el = degree_and_densify(src, dst, weights=w, drop_self_loops=True)
    return build_dsss(el, P)


def _meters_dict(meters, model_only=False):
    d = dataclasses.asdict(meters)
    d.pop("wall_seconds")
    if model_only:
        d = {k: v for k, v in d.items() if k in MODEL_FIELDS}
    return d


def _assert_equivalent(ref, kern, reduce, model_only=False):
    assert_attrs_match(ref.attrs, kern.attrs, reduce)
    assert ref.iterations == kern.iterations
    assert ref.converged == kern.converged
    assert _meters_dict(ref.meters, model_only) == _meters_dict(
        kern.meters, model_only
    )


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    """One weighted + one unweighted graph, each with a .dsss store."""
    out = {}
    for weighted in (False, True):
        g = _graph(seed=3, weighted=weighted)
        path = str(
            tmp_path_factory.mktemp("kstore") / f"g{int(weighted)}.dsss"
        )
        write_dsss(g, path)
        out[weighted] = (g, path)
    return out


def _session(staged, weighted, residency):
    g, path = staged[weighted]
    if residency == "disk":
        return GraphSession.open(
            path, memory_budget=BUDGET, host_memory_budget=HOST_BUDGET
        )
    return GraphSession(g, memory_budget=BUDGET, residency=residency)


@pytest.mark.parametrize("label,prog_cls,kwargs,weighted", PROGRAMS)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("residency", ["device", "host", "disk"])
@pytest.mark.parametrize("activity", ["auto", "off"])
def test_three_way_parity(
    staged, label, prog_cls, kwargs, weighted, strategy, residency, activity
):
    sess = _session(staged, weighted, residency)
    if strategy == "mpu":
        choice = sess.compile(ExecutionPlan(prog_cls(), strategy="mpu")).choice
        assert 0 < choice.Q < sess.graph.P, "budget must exercise the hub split"

    def run(execution):
        return sess.run(
            ExecutionPlan(
                prog_cls(), strategy=strategy, execution=execution,
                activity=activity, **kwargs,
            )
        )

    pb, pk, kn = run("per_block"), run("packed"), run("packed_kernel")
    # vs per_block: model meters always agree; physical fields describe
    # different data paths (per-block streams blocks, packed streams tile
    # chunks), so they are compared model-only off-device.
    _assert_equivalent(
        pb, kn, prog_cls.reduce, model_only=residency != "device"
    )
    # vs packed: same tile streaming/selective machinery drives both, so
    # under every residency even the physical fields must coincide.
    _assert_equivalent(pk, kn, prog_cls.reduce)


def test_kernel_path_actually_runs(monkeypatch):
    """``packed_kernel`` must dispatch the fused kernel executable — never
    the scan sweep, never the per-block primitives — once per update sweep
    on device."""
    g = _graph(seed=5)
    sess = GraphSession(g)

    def boom(*a, **kw):
        raise AssertionError("wrong executable dispatched in kernel mode")

    monkeypatch.setattr(session_mod, "_block_gather_reduce", boom)
    monkeypatch.setattr(session_mod, "_block_to_hub", boom)
    monkeypatch.setattr(session_mod, "_block_from_hub", boom)
    monkeypatch.setattr(session_mod, "_apply_interval", boom)
    # The scan sweep must not run either: the apply executable is shared,
    # so poison only the sweep half of _packed_jits.
    real_packed = session_mod._packed_jits

    def scan_poisoned(donate):
        _, apply_all = real_packed(donate)
        return boom, apply_all

    monkeypatch.setattr(session_mod, "_packed_jits", scan_poisoned)

    calls = []
    real_kernel = session_mod._packed_kernel_jits

    def counting(donate):
        sweep = real_kernel(donate)

        def counted(*a, **kw):
            calls.append(1)
            return sweep(*a, **kw)

        return counted

    monkeypatch.setattr(session_mod, "_packed_kernel_jits", counting)
    res = sess.run(
        ExecutionPlan(
            PageRank(), strategy="spu", max_iters=3, tol=0.0,
            execution="packed_kernel",
        )
    )
    assert res.iterations == 3
    assert len(calls) == 3  # one fused-kernel dispatch per update sweep


def test_auto_resolution_tracks_backend(monkeypatch):
    """auto → the XLA scan on every backend; explicit "packed_kernel" is
    honored on CPU (interpreted) and refused on TPU, where the kernel does
    not lower; fused/custom downgrade."""
    import jax

    g = _graph(seed=1)
    sess = GraphSession(g)
    assert jax.default_backend() == "cpu"
    assert sess.resolved_execution("spu", "device") == "packed"
    assert sess.resolved_execution("spu", "device", "packed_kernel") == (
        "packed_kernel"
    )
    assert sess.resolved_execution("fused", "device", "packed_kernel") == (
        "per_block"
    )
    compiled = sess.compile(
        ExecutionPlan(PageRank(), strategy="dpu", execution="packed_kernel")
    )
    assert compiled.execution == "packed_kernel"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sess.resolved_execution("spu", "host") == "packed"
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        sess.resolved_execution("spu", "device", "packed_kernel")


def test_src_sorted_subshard_tiles_parity():
    """src_sorted graphs force subshard packing; the kernel's windowed
    fold has no slot-ordering assumption (unlike dsss_spmv's one-hot
    window), so parity must hold on their scrambled-run tiles too."""
    el = degree_and_densify(*erdos_renyi(80, 400, seed=1), drop_self_loops=True)
    g = build_dsss(el, 4, src_sorted=True)
    sess = GraphSession(g)
    assert sess.packing == "subshard"
    plan = dict(strategy="spu", max_iters=4, tol=0.0)
    pk = sess.run(ExecutionPlan(PageRank(), execution="packed", **plan))
    kn = sess.run(ExecutionPlan(PageRank(), execution="packed_kernel", **plan))
    _assert_equivalent(pk, kn, PageRank.reduce)


def test_batched_queries_and_stacked_aux():
    """K>1 fused batches run the kernel vmap-free (the query axis is a
    grid dimension): differing BFS roots (per-query attrs) and differing
    MaxLabelForward masks (vmap-stacked per-query aux) both stay
    bit-identical to the scan backend."""
    g = _graph(seed=7)
    sess = GraphSession(g)

    def batch(prog_factory, kwargs_list, **plan_kw):
        out = {}
        for exe in ("packed", "packed_kernel"):
            out[exe] = sess.run_batch(
                [
                    ExecutionPlan(
                        prog_factory(), execution=exe,
                        program_kwargs=kw, **plan_kw,
                    )
                    for kw in kwargs_list
                ]
            )
        assert out["packed"].fused and out["packed_kernel"].fused
        for a, b in zip(out["packed"].results, out["packed_kernel"].results):
            _assert_equivalent(a, b, prog_factory.reduce)

    batch(BFS, [{"root": r} for r in (0, 7, 33)], strategy="dpu")
    rng = np.random.default_rng(0)
    batch(
        MaxLabelForward,
        [{"mask": rng.random(g.n) < 0.5} for _ in range(3)],
        strategy="mpu",
        max_iters=30,
    )


def test_ppr_batch_kernel_parity():
    """Personalized PageRank point queries (differing reset vectors →
    vmap-stacked aux) fuse and match the scan backend within the sum
    tolerance."""
    g = _graph(seed=9)
    sess = GraphSession(g)
    seeds = (0, 5, 41)

    def plans(exe):
        return [
            ExecutionPlan(
                PageRank(), strategy="dpu", execution=exe, max_iters=15,
                tol=0.0, program_kwargs={"personalize": s},
            )
            for s in seeds
        ]

    bp = sess.run_batch(plans("packed"))
    bk = sess.run_batch(plans("packed_kernel"))
    assert bp.fused and bk.fused
    for a, b in zip(bp.results, bk.results):
        _assert_equivalent(a, b, PageRank.reduce)


def test_invalid_execution_values_still_rejected():
    g = _graph(seed=1)
    with pytest.raises(ValueError, match="packed_kernel"):
        GraphSession(g, execution="kernel")
    with pytest.raises(ValueError, match="packed_kernel"):
        ExecutionPlan(PageRank(), execution="kernel")
    # and the new literal is accepted by both axes
    GraphSession(g, execution="packed_kernel")
    ExecutionPlan(PageRank(), execution="packed_kernel")
