"""Tile-packed compiled sweeps vs. the per-block executor.

The packed path's contract: for every native schedule (SPU/DPU/MPU),
every program family (float sum, int min, weighted float min, max), every
residency (device-staged, host-streamed, disk-streamed), both activity
modes and batched K > 1 runs, it must produce

  * bit-identical attributes and outputs for min and max programs, and
    for float sums (PageRank) attributes within ``rtol=1e-5``,
    ``atol=1e-8``: the scan folds each edge straight into its
    destination while the per-block executor folds run partials, a
    float32 reassociation of a destination's in-edges
    (``tests/_fold_contract.py``), and
  * field-for-field identical *model* ``Meters`` (edges, blocks, every
    modelled byte counter) — the physical fields (``wall_seconds``,
    ``bytes_h2d``, ``peak_device_graph_bytes``) describe whichever data
    path actually ran and are compared only where the paths coincide,

while actually running the compiled scan (one ``lax.scan`` + one batched
apply per sweep on device; one scan per streamed tile chunk under host
residency) instead of the per-sub-shard dispatch loop. Since the adaptive
destination-aligned tiling, host residency no longer downgrades packed
execution — also covered here, along with the layout invariants of
:class:`repro.core.dsss.PackedSweep` and the padding bound on power-law
graphs. The per-vertex message form of the tile gather (one gather per
edge for unweighted programs without destination aux) is held bitwise to
the per-edge form, tile by tile, and the scan's step is held to one
scatter. The run fold (one partial per destination run) is held to the
per-edge fold — bitwise for min and max, within the fold contract for
sums — and engages only where the packing admits it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _fold_contract import assert_attrs_match

from repro.core import (
    BFS,
    INF_DEPTH,
    WCC,
    ExecutionPlan,
    GraphSession,
    NXGraphEngine,
    PageRank,
    SSSP,
    build_dsss,
)
from repro.core import session as session_mod
from repro.core.vertex_programs import MaxLabelForward
from repro.graph.generators import erdos_renyi, ring, rmat, zipf
from repro.graph.preprocess import degree_and_densify
from repro.obs import REGISTRY
from repro.storage import open_dsss, write_dsss

STRATEGIES = ["spu", "dpu", "mpu"]
RESIDENCIES = ["device", "host"]

# memory_budget chosen so MPU resolves to a strict 0 < Q < P split for
# both attribute widths (Ba=4 min-programs and Ba=8 PageRank), so the
# mixed direct+hub two-phase path really runs. Under "host" and "disk"
# the same budget also forces real streaming (it is far below the graph
# bytes); the disk tier's host cache holds only some of the tile chunks.
BUDGET = 720
HOST_BUDGET = 3000

# label -> (graph kind, program factory, plan kwargs). PageRank exercises
# the float-sum semiring (where re-association would show), BFS the
# monotone int-min path with activity skipping, SSSP the weighted
# float-min path, WCC int-min label propagation on a symmetric graph and
# max-label the max semiring with per-vertex aux.
PROGRAMS = {
    "pagerank": ("weighted", PageRank, dict(max_iters=6, tol=0.0)),
    "bfs": ("plain", BFS, dict(program_kwargs={"root": 0})),
    "sssp": ("weighted", SSSP, dict(program_kwargs={"root": 0})),
    "wcc": ("symmetric", WCC, {}),
    "max-label": ("plain", MaxLabelForward, {}),
}

# Modelled meter fields — must be identical across execution modes AND
# residencies. The remaining fields (bytes_h2d, peak_device_graph_bytes,
# wall_seconds) are physical: they report what the chosen data path did.
MODEL_FIELDS = session_mod.MODEL_METER_FIELDS


def _graph(n=150, m=900, seed=0, P=5, weighted=False):
    src, dst = erdos_renyi(n, m, seed=seed)
    w = None
    if weighted:
        rng = np.random.default_rng(seed)
        w = rng.uniform(0.1, 2.0, size=len(src)).astype(np.float32)
    el = degree_and_densify(src, dst, weights=w, drop_self_loops=True)
    return build_dsss(el, P)


def _run_graph(P=2, src_sorted=False):
    """An R-MAT graph whose tiles take the run fold: 21 tiles of 1,024
    slots (most padded), runs of ~21 edges, the longest 275, R = 256."""
    el = degree_and_densify(
        *rmat(10, edge_factor=32, seed=0), drop_self_loops=True
    )
    return build_dsss(el, P, src_sorted=src_sorted)


def _meters_dict(meters, model_only=False):
    d = dataclasses.asdict(meters)
    d.pop("wall_seconds")
    if model_only:
        d = {k: v for k, v in d.items() if k in MODEL_FIELDS}
    return d


def _assert_equivalent(res_pb, res_pk, reduce, model_only=False):
    assert_attrs_match(res_pb.attrs, res_pk.attrs, reduce)
    assert res_pb.iterations == res_pk.iterations
    assert res_pb.converged == res_pk.converged
    assert _meters_dict(res_pb.meters, model_only) == _meters_dict(
        res_pk.meters, model_only
    )


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    """Each graph kind of :data:`PROGRAMS`, in memory and as a .dsss store."""
    weighted = _graph(seed=3, weighted=True)
    plain = _graph(seed=3)
    symmetric = build_dsss(
        degree_and_densify(
            *erdos_renyi(150, 900, seed=3), drop_self_loops=True
        ).symmetrized(),
        5,
    )
    out = {}
    for kind, g in (
        ("weighted", weighted), ("plain", plain), ("symmetric", symmetric)
    ):
        path = str(tmp_path_factory.mktemp("stores") / f"{kind}.dsss")
        write_dsss(g, path)
        out[kind] = (g, path)
    return out


def _session(g, residency, path=None):
    if residency == "disk":
        return GraphSession.open(
            path, memory_budget=BUDGET, host_memory_budget=HOST_BUDGET
        )
    return GraphSession(g, memory_budget=BUDGET, residency=residency)


@pytest.mark.parametrize("label", list(PROGRAMS))
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("residency", ["device", "host", "disk"])
@pytest.mark.parametrize("activity", ["auto", "off"])
def test_backend_parity(staged, label, strategy, residency, activity):
    """``packed`` against ``per_block``: min/max results bit for bit, sums
    within the fold contract, the same sweeps and convergence, identical
    model meters everywhere and identical physical meters on device."""
    kind, prog_cls, kwargs = PROGRAMS[label]
    g, path = staged[kind]
    sess = _session(g, residency, path)
    assert sess.resolved_residency() == residency
    if label == "max-label":
        mask = np.random.default_rng(2).random(g.n) < 0.5
        kwargs = dict(program_kwargs={"mask": mask})
    kwargs = {"max_iters": g.n + 1, **kwargs}
    if strategy == "mpu":
        choice = sess.compile(ExecutionPlan(prog_cls(), strategy="mpu")).choice
        assert 0 < choice.Q < g.P, "budget must exercise the hub split"

    def run(execution):
        return sess.run(
            ExecutionPlan(
                prog_cls(), strategy=strategy, execution=execution,
                activity=activity, **kwargs,
            )
        )

    pb, pk = run("per_block"), run("packed")
    # Model meters agree always; the physical fields additionally agree
    # under device residency (neither path streams: h2d 0, peak = total).
    _assert_equivalent(
        pb, pk, prog_cls.reduce, model_only=(residency != "device")
    )
    assert pk.meters.edges_processed > 0
    if residency != "device":
        assert pb.meters.bytes_h2d > 0 and pk.meters.bytes_h2d > 0
    if residency == "disk":
        assert pb.meters.bytes_disk_read > 0 and pk.meters.bytes_disk_read > 0
    if label == "pagerank":
        # Non-monotone: every sweep touches every sub-shard.
        assert pk.meters.blocks_processed == pk.iterations * len(sess.block_keys)
    else:
        assert pb.converged and pk.converged


@pytest.mark.parametrize("residency", RESIDENCIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize(
    "label,prog_cls,weighted",
    [("bfs", BFS, False), ("sssp", SSSP, True)],
)
def test_batched_k_gt_1(label, prog_cls, weighted, strategy, residency):
    """K>1 fused batches: one packed scan serves all queries."""
    g = _graph(seed=7, weighted=weighted)
    budget = g.total_edge_bytes(8) // 3 if residency == "host" else None
    sess = GraphSession(g, memory_budget=budget, residency=residency)
    roots = [0, 11, 29, 63]

    def plans(execution):
        return [
            ExecutionPlan(
                prog_cls(),
                strategy=strategy,
                max_iters=100,
                execution=execution,
                program_kwargs={"root": r},
            )
            for r in roots
        ]

    b_pb = sess.run_batch(plans("per_block"))
    b_pk = sess.run_batch(plans("packed"))
    assert b_pb.fused and b_pk.fused
    assert b_pb.iterations == b_pk.iterations
    for r_pb, r_pk in zip(b_pb, b_pk):
        np.testing.assert_array_equal(r_pb.attrs, r_pk.attrs)
        np.testing.assert_array_equal(r_pb.output, r_pk.output)
        assert r_pb.iterations == r_pk.iterations
    assert _meters_dict(b_pb.meters, model_only=True) == _meters_dict(
        b_pk.meters, model_only=True
    )


def test_batched_pagerank_shares_edge_stream():
    """Edge bytes are charged once per sweep under batching, K× for
    interval/hub state — identically in both execution modes."""
    g = _graph(seed=9)
    sess = GraphSession(g, residency="device")
    plan = ExecutionPlan(
        PageRank(), strategy="dpu", max_iters=4, tol=0.0, execution="packed"
    )
    single = sess.run(plan)
    batch = sess.run_batch([plan] * 6)
    assert batch.fused
    assert batch.meters.bytes_read_edges == single.meters.bytes_read_edges > 0
    assert batch.meters.bytes_read_hubs == 6 * single.meters.bytes_read_hubs


@pytest.mark.parametrize("residency", RESIDENCIES)
def test_packed_path_actually_runs(monkeypatch, residency):
    """The packed run must never enter the per-block primitives; on device
    it calls the compiled sweep exactly once per update sweep, streaming
    calls it once per tile chunk."""
    g = _graph(seed=5)
    budget = g.total_edge_bytes(8) // 2 if residency == "host" else None
    sess = GraphSession(g, memory_budget=budget, residency=residency)

    def boom(*a, **kw):
        raise AssertionError("per-block primitive dispatched in packed mode")

    monkeypatch.setattr(session_mod, "_block_gather_reduce", boom)
    monkeypatch.setattr(session_mod, "_block_to_hub", boom)
    monkeypatch.setattr(session_mod, "_block_from_hub", boom)
    monkeypatch.setattr(session_mod, "_apply_interval", boom)

    sweeps = []
    real_jits = session_mod._packed_jits

    def counting_jits(donate):
        sweep, apply_all = real_jits(donate)

        def counted(*a, **kw):
            sweeps.append(1)
            return sweep(*a, **kw)

        return counted, apply_all

    monkeypatch.setattr(session_mod, "_packed_jits", counting_jits)
    res = sess.run(
        ExecutionPlan(
            PageRank(), strategy="spu", max_iters=3, tol=0.0, execution="packed"
        )
    )
    assert res.iterations == 3
    if residency == "device":
        assert len(sweeps) == 3  # one compiled sweep dispatch per update sweep
    else:
        assert len(sweeps) >= 3  # ≥ one chunk per sweep, no per-block entry


def test_activity_skipping_matches_per_block():
    """Monotone activity tracking: packed masks inactive rows to exact
    identities; block/edge meters must track the per-block skip counts."""
    el = degree_and_densify(*ring(36))
    g = build_dsss(el, 6)
    sess = GraphSession(g, residency="device")
    for strategy in STRATEGIES:
        pb = sess.run(
            ExecutionPlan(
                BFS(), strategy=strategy, max_iters=50, execution="per_block",
                program_kwargs={"root": 0},
            )
        )
        pk = sess.run(
            ExecutionPlan(
                BFS(), strategy=strategy, max_iters=50, execution="packed",
                program_kwargs={"root": 0},
            )
        )
        _assert_equivalent(pb, pk, BFS.reduce)
        assert pk.meters.blocks_skipped > 0  # the ring really does skip rows


def test_host_residency_runs_packed():
    """Since adaptive tiling, packed execution streams out-of-core instead
    of downgrading: auto resolves to packed under host residency, results
    are bit-identical to device residency, and the budget pins a tile
    prefix within the leftover while chunks stream on top."""
    g = _graph(seed=6)
    budget = 2 * g.n_pad * 8 + g.total_edge_bytes(8) // 2
    host = GraphSession(g, memory_budget=budget, residency="host")
    compiled = host.compile(ExecutionPlan(PageRank(), strategy="spu"))
    assert compiled.residency == "host" and compiled.execution == "packed"
    dev = GraphSession(g, residency="device")
    r_host = host.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=4, tol=0.0))
    r_dev = dev.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=4, tol=0.0))
    np.testing.assert_array_equal(r_host.attrs, r_dev.attrs)
    assert r_host.meters.bytes_h2d > 0  # host mode really streamed
    assert r_dev.meters.bytes_h2d == 0
    # Budget accounting: pinned tile prefix fits the leftover, and the
    # peak adds at most the two-chunk streaming ring on top.
    splan = host.packed_stream_plan("spu", PageRank().attr_bytes)
    pinned_model, _ = host.pinned_device_bytes()
    assert pinned_model == splan.pin_model_bytes
    assert pinned_model + 2 * g.n_pad * 8 <= budget
    assert (
        r_host.meters.peak_device_graph_bytes
        <= pinned_model + 2 * splan.max_chunk_model_bytes
    )
    # Physical stream volume is a closed form of the layout: every
    # non-pinned tile ships its dense leaves once per sweep.
    from repro.core import packed_h2d_bytes

    assert r_host.meters.bytes_h2d == r_host.iterations * packed_h2d_bytes(
        splan.num_tiles - splan.pin_tiles, splan.tile_edges,
        run_slots=splan.run_slots,
    )


def test_full_budget_host_packed_streams_nothing():
    g = _graph(seed=2)
    total = 2 * g.n_pad * 8 + g.total_edge_bytes(8)
    sess = GraphSession(g, memory_budget=2 * total, residency="host")
    res = sess.run(ExecutionPlan(PageRank(), strategy="spu", max_iters=3, tol=0.0))
    assert res.meters.bytes_h2d == 0.0
    assert res.meters.bytes_read_edges == 0.0
    assert sess.pinned_device_bytes()[0] == g.m * sess.Be


def test_custom_and_fused_strategies_stay_per_block():
    import repro.core.baselines  # noqa: F401  (registers turbograph-like)

    g = _graph(seed=8)
    sess = GraphSession(g, residency="device", execution="packed")
    assert (
        sess.compile(ExecutionPlan(PageRank(), strategy="fused")).execution
        == "per_block"
    )
    assert (
        sess.compile(
            ExecutionPlan(PageRank(), strategy="turbograph-like")
        ).execution
        == "per_block"
    )
    # And they still run correctly under a packed-preferring session.
    ref = sess.run(
        ExecutionPlan(PageRank(), strategy="spu", max_iters=5, tol=0.0)
    )
    fused = sess.run(
        ExecutionPlan(PageRank(), strategy="fused", max_iters=5, tol=0.0)
    )
    np.testing.assert_allclose(fused.attrs, ref.attrs, rtol=1e-6, atol=1e-9)


def test_engine_shim_execution_knob():
    g = _graph(seed=4, weighted=True)
    sess = GraphSession(g, residency="device")
    pb = NXGraphEngine(
        g, PageRank(), strategy="spu", execution="per_block", session=sess
    )
    pk = NXGraphEngine(g, PageRank(), strategy="spu", execution="packed", session=sess)
    assert pb.execution == "per_block" and pk.execution == "packed"
    r_pb = pb.run(max_iters=5, tol=0.0)
    r_pk = pk.run(max_iters=5, tol=0.0)
    _assert_equivalent(r_pb, r_pk, PageRank.reduce)
    with pytest.raises(ValueError, match="packing"):
        NXGraphEngine(g, PageRank(), packing="subshard", session=sess)


def test_packed_layout_invariants_adaptive_and_subshard():
    from _layout_checks import check_layout

    g = _graph(seed=2, weighted=True)
    for mode in ("adaptive", "subshard"):
        packed = g.packed_sweep(mode)
        check_layout(g, packed)
    # Subshard mode reproduces the per-block bookkeeping exactly.
    old = g.packed_sweep("subshard")
    host = g.host_blocks()
    assert old.num_tiles == len(host)
    for t, key in enumerate(sorted(host)):
        blk = host[key]
        assert old.e_valid[t] == blk["e"]
        assert old.u[t] == blk["u"]
        assert (old.src_interval[t], old.dst_interval[t]) == key
        assert old.base_slot[t] == g.hub_offsets[key]


def test_adaptive_padding_bounded_on_power_law():
    """The acceptance bound: on a Zipf-degree graph at P=32 the adaptive
    packing pads ≤ 1.25× while the legacy sub-shard tiles are hub-bound."""
    el = degree_and_densify(*zipf(6000, 40000, alpha=1.9, seed=0), drop_self_loops=True)
    g = build_dsss(el, 32)
    from _layout_checks import check_layout

    adaptive = g.packed_sweep("adaptive")
    legacy = g.packed_sweep("subshard")
    assert adaptive.padding_ratio <= 1.25, adaptive.padding_ratio
    assert legacy.padding_ratio > adaptive.padding_ratio
    check_layout(g, adaptive)


def test_src_sorted_requires_subshard_packing():
    el = degree_and_densify(*erdos_renyi(80, 400, seed=1), drop_self_loops=True)
    g = build_dsss(el, 4, src_sorted=True)
    with pytest.raises(ValueError, match="src_sorted"):
        g.packed_sweep("adaptive")
    with pytest.raises(ValueError, match="adaptive"):
        GraphSession(g, packing="adaptive")
    sess = GraphSession(g)  # auto → subshard
    assert sess.packing == "subshard"
    pb = sess.run(
        ExecutionPlan(PageRank(), strategy="spu", max_iters=4, tol=0.0,
                      execution="per_block")
    )
    pk = sess.run(
        ExecutionPlan(PageRank(), strategy="spu", max_iters=4, tol=0.0,
                      execution="packed")
    )
    _assert_equivalent(pb, pk, PageRank.reduce)


def test_kernel_operands_from_packed_tile():
    """Tiles are valid Pallas kernel streams: staging one through
    ops.prepare_from_packed_tile and running the windowed sub-shard update
    reproduces the reference per-slot reduction over global hub slots."""
    import jax.numpy as jnp

    from repro.kernels.ops import prepare_from_packed_tile, subshard_update

    g = _graph(n=80, m=400, seed=11, P=3, weighted=True)
    packed = g.packed_sweep("adaptive")
    gslot = g.global_hub_slots()
    num_slots = int(g.hub_offsets[-1, -1])
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.1, 1.0, size=g.n_pad).astype(np.float32)
    for t in range(packed.num_tiles):
        operands = prepare_from_packed_tile(
            packed, t, jnp.float32, gather_op="mul", reduce="sum"
        )
        hub = subshard_update(
            jnp.asarray(vals), *operands, num_slots=num_slots,
            gather_op="mul", reduce="sum",
        )
        lo = int(packed.row_offset[t])
        hi = lo + int(packed.e_valid[t])
        ref = np.zeros(num_slots, np.float32)
        np.add.at(
            ref, gslot[lo:hi], vals[g.src[lo:hi]] * g.weights[lo:hi]
        )
        sl = slice(int(packed.base_slot[t]), int(packed.base_slot[t] + packed.u[t]))
        np.testing.assert_allclose(np.asarray(hub)[sl], ref[sl], rtol=1e-5)
    # src_sorted blocks scramble the slot stream — staging must refuse
    # rather than silently compute wrong windowed partials.
    el = degree_and_densify(*erdos_renyi(80, 400, seed=11), drop_self_loops=True)
    gs = build_dsss(el, 3, src_sorted=True)
    ps = gs.packed_sweep("subshard")
    raised = 0
    for t in range(ps.num_tiles):
        try:
            prepare_from_packed_tile(ps, t, jnp.float32, gather_op="mul", reduce="sum")
        except ValueError:
            raised += 1
    assert raised > 0


def test_invalid_execution_values_rejected():
    g = _graph(seed=1)
    accepted = r"'per_block', 'packed' or 'auto'"
    for value in ("warp", "packed_kernel"):
        with pytest.raises(ValueError, match=accepted):
            GraphSession(g, execution=value)
        with pytest.raises(ValueError, match=accepted):
            ExecutionPlan(PageRank(), execution=value)
    with pytest.raises(ValueError):
        GraphSession(g, packing="diagonal")


def _message_case(case, g, rng):
    """(program, attrs (K, n_pad), aux, aux_batched, row_active) per case."""
    P, n_pad = g.P, g.n_pad
    full = np.ones(P, bool)
    partial = np.arange(P) % 2 == 0
    ranks = lambda k: rng.random((k, n_pad), dtype=np.float32)  # noqa: E731
    if case == "pagerank-k1":
        return PageRank(), ranks(1), PageRank().make_aux(g), False, full
    if case == "pagerank-k3":
        return PageRank(), ranks(3), PageRank().make_aux(g), False, full
    if case == "ppr-reset":
        auxes = [PageRank().make_aux(g, personalize=v) for v in (0, 7, 19)]
        aux = {k: np.stack([np.asarray(a[k]) for a in auxes]) for k in auxes[0]}
        return PageRank(), ranks(3), aux, True, full
    if case == "bfs":
        attrs = rng.integers(0, 6, (2, n_pad)).astype(np.int32)
        attrs[rng.random((2, n_pad)) < 0.4] = INF_DEPTH
        return BFS(), attrs, {}, False, partial
    if case == "wcc":
        attrs = rng.integers(0, g.n, (1, n_pad)).astype(np.int32)
        return WCC(), attrs, {}, False, full
    mask = (rng.random(n_pad) < 0.6).astype(np.int32)
    attrs = rng.integers(-5, 50, (1, n_pad)).astype(np.int32)
    return (
        MaxLabelForward(), attrs, MaxLabelForward().make_aux(g, mask=mask),
        False, partial,
    )


@pytest.mark.parametrize(
    "case", ["pagerank-k1", "pagerank-k3", "ppr-reset", "bfs", "wcc", "max-label"]
)
def test_vertex_message_matches_per_edge_gather(case):
    """Gathering one precomputed per-vertex message per edge builds the
    same contributions, and folds the same accumulators, bit for bit, as
    gathering attribute, aux and activity mask per edge — on every tile,
    padded ones included, and through the whole scan."""
    import jax.numpy as jnp

    g = _graph(n=300, m=2400, seed=4, P=6)
    prog, attrs, aux, aux_batched, row_active = _message_case(
        case, g, np.random.default_rng(1)
    )
    assert session_mod._vertex_message_applies(prog, has_weights=False)
    packed = g.packed_sweep("adaptive")
    T = packed.src.shape[-1]
    assert (packed.e_valid < T).any()  # some tile carries padding
    tiles = {
        k: jnp.asarray(v)
        for k, v in session_mod._packed_host_chunk(
            packed, 0, packed.num_tiles, False
        ).items()
    }
    attrs = jnp.asarray(attrs, prog.dtype)
    aux = {k: jnp.asarray(v) for k, v in aux.items()}
    vert_active = jnp.asarray(np.repeat(row_active, g.n_pad // g.P))
    msgs = session_mod._vertex_messages(
        prog, attrs, aux, vert_active, aux_batched
    )
    ident = session_mod.reduce_identity(prog.reduce, prog.dtype)
    acc_msg = acc_edge = jnp.full(attrs.shape, ident, prog.dtype)
    for t in range(packed.num_tiles):
        tile = {k: v[t] for k, v in tiles.items()}
        by_msg = session_mod._message_contributions(prog, msgs, tile)
        by_edge = session_mod._edge_contributions(
            prog, attrs, aux, vert_active, False, aux_batched, tile
        )
        np.testing.assert_array_equal(np.asarray(by_msg), np.asarray(by_edge))
        acc_msg = session_mod._fold_tile(prog, by_msg, tile, acc_msg)
        acc_edge = session_mod._fold_tile(prog, by_edge, tile, acc_edge)
        np.testing.assert_array_equal(np.asarray(acc_msg), np.asarray(acc_edge))
    swept = session_mod._packed_sweep_impl(
        prog, attrs, jnp.full(attrs.shape, ident, prog.dtype), aux, tiles,
        jnp.asarray(row_active), has_weights=False, aux_batched=aux_batched,
    )
    np.testing.assert_array_equal(np.asarray(swept), np.asarray(acc_edge))


def _scatters(jaxpr) -> int:
    """Scatter equations in ``jaxpr``, nested calls included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name.startswith("scatter")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scatters(sub)
    return n


def _scatter_widths(jaxpr) -> list:
    """Update widths of the scatter equations in ``jaxpr``, nested calls
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            out.append(eqn.invars[2].aval.shape[-1])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scatter_widths(sub)
    return out


@pytest.mark.parametrize("prog_cls", [PageRank, BFS])
def test_scan_step_folds_with_one_scatter(prog_cls):
    """Each scan step folds its tile with one scatter into the
    accumulator, and the lowered sweep holds no other scatter. The
    per-edge fold scatters T updates (one per slot); where the run fold
    engages, it scatters R (one per run slot, 2·R ≤ T)."""
    prog = prog_cls()
    for g, fold in ((_graph(seed=3), "per-edge"), (_run_graph(), "run")):
        packed = g.packed_sweep("adaptive")
        assert (packed.run_end is None) == (fold == "per-edge")
        tiles = {
            k: jnp.asarray(v)
            for k, v in session_mod._packed_host_chunk(
                packed, 0, packed.num_tiles, False
            ).items()
        }
        aux = {k: jnp.asarray(v) for k, v in prog.make_aux(g).items()}
        attrs = jnp.zeros((1, g.n_pad), prog.dtype)
        args = (prog, attrs, attrs, aux, tiles, jnp.ones(g.P, bool), False)
        closed = jax.make_jaxpr(
            session_mod._packed_sweep_impl, static_argnums=(0, 6)
        )(*args)
        (scan,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
        assert _scatters(scan.params["jaxpr"].jaxpr) == 1
        assert _scatters(closed.jaxpr) == 1
        width = packed.tile_edges if fold == "per-edge" else packed.run_slots
        assert _scatter_widths(scan.params["jaxpr"].jaxpr) == [width]
        assert fold == "per-edge" or 2 * width <= packed.tile_edges
        lowered = jax.jit(
            session_mod._packed_sweep_impl,
            static_argnames=("program", "has_weights"),
        ).lower(*args[:6], has_weights=False)
        assert lowered.as_text().count('"stablehlo.scatter"(') == 1


MESSAGE_CASES = [
    "pagerank-k1", "pagerank-k3", "ppr-reset", "bfs", "wcc", "max-label"
]


def _run_fold_case(case):
    """The run graph's packing, device tiles with and without ``run_end``,
    and one message case's operands."""
    g = _run_graph()
    prog, attrs, aux, aux_batched, row_active = _message_case(
        case, g, np.random.default_rng(1)
    )
    packed = g.packed_sweep("adaptive")
    runs = {
        k: jnp.asarray(v)
        for k, v in session_mod._packed_host_chunk(
            packed, 0, packed.num_tiles, False
        ).items()
    }
    edges = {k: v for k, v in runs.items() if k != "run_end"}
    ops = dict(
        prog=prog,
        attrs=jnp.asarray(attrs, prog.dtype),
        aux={k: jnp.asarray(v) for k, v in aux.items()},
        aux_batched=aux_batched,
        row_active=jnp.asarray(row_active),
    )
    return g, packed, runs, edges, ops


def _identities(ops):
    prog = ops["prog"]
    ident = session_mod.reduce_identity(prog.reduce, prog.dtype)
    return jnp.full(ops["attrs"].shape, ident, prog.dtype)


def _sweep(ops, tiles, acc=None):
    return session_mod._packed_sweep_impl(
        ops["prog"], ops["attrs"], _identities(ops) if acc is None else acc,
        ops["aux"], tiles, ops["row_active"],
        has_weights=False, aux_batched=ops["aux_batched"],
    )


@pytest.mark.parametrize("case", MESSAGE_CASES)
def test_run_fold_matches_per_edge_fold(case):
    """The run fold (segmented scan, run totals at ``run_end``, one scatter
    per run) folds the same accumulators as one scatter per edge: bitwise
    for min and max, within the fold contract for sums — on every tile,
    padded ones and one with a run longer than 128 edges included, and
    through the whole scan."""
    g, packed, runs, edges, ops = _run_fold_case(case)
    prog = ops["prog"]
    T = packed.tile_edges
    assert (packed.e_valid < T).any()  # padded tiles
    assert np.bincount(g.global_hub_slots()).max() > 128  # a long run
    vert_active = jnp.asarray(
        np.repeat(np.asarray(ops["row_active"]), g.n_pad // g.P)
    )
    msgs = session_mod._vertex_messages(
        prog, ops["attrs"], ops["aux"], vert_active, ops["aux_batched"]
    )
    contributions = jax.jit(session_mod._message_contributions,
                            static_argnums=0)
    fold = jax.jit(session_mod._fold_tile, static_argnums=0)
    acc_run = acc_edge = _identities(ops)
    for t in range(packed.num_tiles):
        run_tile = {k: v[t] for k, v in runs.items()}
        edge_tile = {k: v[t] for k, v in edges.items()}
        contrib = contributions(prog, msgs, run_tile)
        acc_run = fold(prog, contrib, run_tile, acc_run)
        acc_edge = fold(prog, contrib, edge_tile, acc_edge)
        assert_attrs_match(
            np.asarray(acc_edge), np.asarray(acc_run), prog.reduce
        )
    swept_run, swept_edge = _sweep(ops, runs), _sweep(ops, edges)
    np.testing.assert_array_equal(np.asarray(swept_run), np.asarray(acc_run))
    np.testing.assert_array_equal(np.asarray(swept_edge), np.asarray(acc_edge))
    assert_attrs_match(
        np.asarray(swept_edge), np.asarray(swept_run), prog.reduce
    )


@pytest.mark.parametrize("case", MESSAGE_CASES)
def test_run_fold_scans_stay_bitwise(case):
    """Under the run fold, a selective scan over the active tiles (padded
    to a larger bucket with emptied tiles) and the scan cut into chunks of
    tiles equal the full scan bit for bit: a tile's fold depends only on
    the tile."""
    from repro.core.dsss import active_tile_mask, next_bucket, tile_source_spans

    g, packed, runs, edges, ops = _run_fold_case(case)
    full = np.asarray(_sweep(ops, runs))
    first, last = tile_source_spans(packed, g.interval_size)
    active = np.flatnonzero(
        active_tile_mask(np.asarray(ops["row_active"]), first, last)
    )
    bucket = next_bucket(active.size + 1)
    idx = np.zeros(bucket, np.int32)
    idx[: active.size] = active
    selected = session_mod._packed_sweep_select_impl(
        ops["prog"], ops["attrs"], _identities(ops), ops["aux"], runs, jnp.asarray(idx), jnp.asarray(np.int32(active.size)),
        ops["row_active"], has_weights=False, aux_batched=ops["aux_batched"],
    )
    np.testing.assert_array_equal(np.asarray(selected), full)
    acc = None
    for lo in range(0, packed.num_tiles, 4):
        acc = _sweep(ops, {k: v[lo : lo + 4] for k, v in runs.items()}, acc)
    np.testing.assert_array_equal(np.asarray(acc), full)


def _run_fold_tiles(sess, plan):
    """(result, tiles swept, run-fold tiles) of one run."""
    tiles = REGISTRY.value("repro_engine_tiles_swept_total")
    run_fold = REGISTRY.value("repro_engine_run_fold_tiles_total")
    res = sess.run(plan)
    return (
        res,
        REGISTRY.value("repro_engine_tiles_swept_total") - tiles,
        REGISTRY.value("repro_engine_run_fold_tiles_total") - run_fold,
    )


@pytest.mark.parametrize("residency", ["device", "host", "disk"])
def test_run_fold_session_parity(tmp_path, residency):
    """Through a session, the run fold's PageRank matches the per-block
    executor within the fold contract, its BFS bit for bit, every tier
    equals the device tier bitwise, and every swept tile is counted as a
    run-fold tile."""
    g = _run_graph()
    budget = g.total_edge_bytes(8) // 3
    if residency == "disk":
        path = str(tmp_path / "runs.dsss")
        write_dsss(g, path)
        sess = GraphSession.open(path, memory_budget=budget,
                                 host_memory_budget=budget)
    else:
        sess = GraphSession(
            g, memory_budget=None if residency == "device" else budget,
            residency=residency,
        )
    assert sess.resolved_residency() == residency
    device = GraphSession(g)
    for prog, kwargs in (
        (PageRank(), dict(max_iters=6, tol=0.0)),
        (BFS(), dict(max_iters=g.n + 1, program_kwargs={"root": 3})),
    ):
        plan = ExecutionPlan(prog, strategy="spu", execution="packed", **kwargs)
        res, tiles, run_fold = _run_fold_tiles(sess, plan)
        assert tiles > 0 and run_fold == tiles
        np.testing.assert_array_equal(res.attrs, device.run(plan).attrs)
        pb = device.run(dataclasses.replace(plan, execution="per_block"))
        assert_attrs_match(pb.attrs, res.attrs, prog.reduce)
    if residency != "device":
        assert res.meters.bytes_h2d > 0  # streamed, run_end with each chunk


@pytest.mark.parametrize("layout", ["one-edge-runs", "src-sorted"])
def test_per_edge_fold_where_runs_do_not_pay(layout):
    """The run fold stays off where it would not pay or not hold: a graph
    whose runs are mostly one edge (2·R > T), and a ``src_sorted`` layout's
    sub-shard tiles (runs not contiguous). Those sweep with the per-edge
    fold, count no run-fold tile, and still match the per-block executor."""
    if layout == "one-edge-runs":
        g = _graph(n=300, m=2400, seed=4, P=6)
        packed = g.packed_sweep("adaptive")
        R = -(-int(packed.u.max()) // 128) * 128
        assert 2 * R > packed.tile_edges
    else:
        g = _run_graph(src_sorted=True)
        packed = g.packed_sweep("subshard")
        R = -(-int(packed.u.max()) // 128) * 128
        assert 2 * R <= packed.tile_edges  # only the order rules it out
    assert packed.run_end is None and packed.run_slots == 0
    sess = GraphSession(g)
    plan = ExecutionPlan(PageRank(), max_iters=4, tol=0.0, execution="packed")
    res, tiles, run_fold = _run_fold_tiles(sess, plan)
    assert tiles > 0 and run_fold == 0
    pb = sess.run(dataclasses.replace(plan, execution="per_block"))
    assert_attrs_match(pb.attrs, res.attrs, PageRank.reduce)


@pytest.mark.parametrize("P", [2, 4])
def test_run_end_derivation(tmp_path, P):
    """``run_end`` is (NT, R): each run's last slot, increasing over the
    tile's runs, 0 past them; a ``.dsss`` store opened from disk derives
    the same array from its stored ``run_local``."""
    from _layout_checks import check_run_end

    g = _run_graph(P)
    packed = g.packed_sweep("adaptive")
    assert packed.run_end is not None
    assert packed.run_end.shape == (packed.num_tiles, packed.run_slots)
    check_run_end(packed)
    path = str(tmp_path / "g.dsss")
    write_dsss(g, path)
    stored = open_dsss(path).packed()
    np.testing.assert_array_equal(stored.run_end, packed.run_end)
