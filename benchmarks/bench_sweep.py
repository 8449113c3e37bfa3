"""Per-sweep wall time, dispatch count and padding: per_block vs. packed.

The paper's headline claim is raw per-iteration speed; the per-block
executor pays O(P²) host→XLA round-trips per update sweep, so at realistic
P the run is dispatch-bound. This benchmark measures:

* **Uniform section** (Erdős–Rényi, P ∈ {8, 16, 32}, device residency,
  PageRank): per-sweep wall seconds and jitted-primitive dispatches per
  sweep for both execution modes (counted by wrapping the session's jit
  entry points), with attribute agreement (within :data:`SUM_RTOL` /
  :data:`SUM_ATOL`) and meter equality asserted per row.
* **Power-law section** (Zipf + R-MAT, P ∈ {16, 32} — the skew regime
  NXgraph §V targets): padded-edge ratio and per-sweep wall of the legacy
  one-tile-per-sub-shard packing vs. adaptive destination-aligned tiles,
  and out-of-core (`residency="host"`, budget ≈ half the edge bytes)
  per-sweep wall + raw h2d volume of packed streaming vs. the per-block
  fetcher — the downgrade adaptive tiling removed.

* **Frontier section** (BFS on R-MAT, ``residency="host"``, tight
  budget): physical per-sweep ``bytes_h2d`` of frontier-aware selective
  execution (``activity="auto"``) vs the full-sweep ``activity="off"``
  baseline, with the closed-form/meter exactness asserted and the
  late-iteration (collapsed-frontier) skip ratio reported.

* **Kernel section** (``execution="packed_kernel"`` vs ``"packed"`` on
  the same tiles): per-sweep wall + dispatch counts of the fused Pallas
  sweep against the XLA scan, asserting attrs within the sum tolerance,
  identical meters, and exactly one fused ``pallas_call`` dispatch per
  sweep.
  Off-TPU the kernel runs in interpret mode, so its wall number is a
  correctness-path cost, not a speed claim — the claim is the dispatch
  shape and the bits.

Writes ``BENCH_sweep.json`` (repo root by default); CI runs the
``--smoke`` variant per PR with ``--assert-padding-ratio 1.25``,
``--assert-skip-ratio 5.0`` and ``--assert-kernel-parity`` so
dispatch-count, padding, frontier-skip *and* kernel-parity regressions
fail the build.

Usage::

    PYTHONPATH=src python benchmarks/bench_sweep.py            # full, writes BENCH_sweep.json
    PYTHONPATH=src python benchmarks/bench_sweep.py --smoke    # tiny graphs, CI artifact
"""
import argparse
import dataclasses
import json
import pathlib
import sys

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))  # so `benchmarks._util` resolves as a script
sys.path.insert(0, str(_ROOT / "src"))

import jax  # noqa: E402

from benchmarks._util import stamp  # noqa: E402

from repro.core import BFS, ExecutionPlan, GraphSession, PageRank, build_dsss  # noqa: E402
from repro.core import session as session_mod  # noqa: E402
from repro.core.iomodel import packed_h2d_bytes, selective_streamed_tiles  # noqa: E402
from repro.graph.generators import erdos_renyi, rmat, zipf  # noqa: E402
from repro.graph.preprocess import degree_and_densify  # noqa: E402

# The session's jit entry points — one call == one host-scheduled XLA
# dispatch in the update loop.
_PER_BLOCK_PRIMITIVES = [
    "_block_gather_reduce",
    "_block_to_hub",
    "_block_from_hub",
    "_apply_interval",
    "_pre_iteration",
]


class DispatchCounter:
    """Counts calls to the session's jitted primitives while active.

    ``count`` is every host-scheduled dispatch; ``kernel_count`` is the
    subset that went through the fused Pallas sweep executables
    (``execution="packed_kernel"``).
    """

    def __init__(self):
        self.count = 0
        self.kernel_count = 0
        self._saved = {}

    def _wrap(self, fn, kernel=False):
        def counted(*a, **kw):
            self.count += 1
            if kernel:
                self.kernel_count += 1
            return fn(*a, **kw)

        return counted

    def __enter__(self):
        for name in _PER_BLOCK_PRIMITIVES:
            fn = getattr(session_mod, name)
            self._saved[name] = fn
            setattr(session_mod, name, self._wrap(fn))
        real_jits = session_mod._packed_jits
        self._saved["_packed_jits"] = real_jits

        def counting_jits(donate):
            sweep, apply_all = real_jits(donate)
            return self._wrap(sweep), self._wrap(apply_all)

        session_mod._packed_jits = counting_jits
        real_select = session_mod._packed_select_jits
        self._saved["_packed_select_jits"] = real_select

        def counting_select(donate):
            return self._wrap(real_select(donate))

        session_mod._packed_select_jits = counting_select
        real_kernel = session_mod._packed_kernel_jits
        self._saved["_packed_kernel_jits"] = real_kernel

        def counting_kernel(donate):
            return self._wrap(real_kernel(donate), kernel=True)

        session_mod._packed_kernel_jits = counting_kernel
        real_kernel_select = session_mod._packed_kernel_select_jits
        self._saved["_packed_kernel_select_jits"] = real_kernel_select

        def counting_kernel_select(donate):
            return self._wrap(real_kernel_select(donate), kernel=True)

        session_mod._packed_kernel_select_jits = counting_kernel_select
        return self

    def __exit__(self, *exc):
        for name, fn in self._saved.items():
            setattr(session_mod, name, fn)
        return False


# PageRank across backends: the scan folds each edge straight into its
# destination, per_block and packed_kernel fold run partials, so float32
# sums agree to rounding, not bitwise. Same-backend rows stay bitwise.
SUM_RTOL = 1e-5
SUM_ATOL = 1e-8


def assert_sums_match(expected, actual):
    np.testing.assert_allclose(actual, expected, rtol=SUM_RTOL, atol=SUM_ATOL)


def bench_one(session, strategy, execution, iters):
    plan = ExecutionPlan(
        PageRank(), strategy=strategy, max_iters=iters, tol=0.0, execution=execution
    )
    session.run(plan)  # warmup: staging + jit compilation
    with DispatchCounter() as counter:
        res = session.run(plan)
    assert res.iterations == iters
    return {
        "strategy": strategy,
        "mode": execution,
        "per_sweep_seconds": res.meters.wall_seconds / res.iterations,
        "dispatches_per_sweep": counter.count / res.iterations,
        "fused_dispatches_per_sweep": counter.kernel_count / res.iterations,
        "h2d_per_sweep": res.meters.bytes_h2d / res.iterations,
        "attrs": res.attrs,
        "meters": res.meters,
    }


def uniform_section(report, args):
    src, dst = erdos_renyi(args.n, args.m, seed=args.seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    report["graph"] = {
        "generator": "erdos_renyi", "n": el.n, "m": el.m, "seed": args.seed,
    }
    for P in args.p_values:
        g = build_dsss(el, P)
        sess = GraphSession(g, residency="device")
        packed = g.packed_sweep()
        print(
            f"P={P}: {len(sess.block_keys)} sub-shards, tile_edges="
            f"{packed.tile_edges}, padded_slots={packed.padded_edge_slots} "
            f"({packed.padding_ratio:.2f}x edges)"
        )
        for strategy in args.strategies:
            rows = {}
            for execution in ("per_block", "packed"):
                r = bench_one(sess, strategy, execution, args.iters)
                rows[execution] = r
                print(
                    f"  {strategy:>4} {execution:>9}: "
                    f"{r['per_sweep_seconds'] * 1e3:8.2f} ms/sweep, "
                    f"{r['dispatches_per_sweep']:7.1f} dispatches/sweep"
                )
            assert_sums_match(
                rows["per_block"].pop("attrs"), rows["packed"].pop("attrs")
            )
            m_pb = dataclasses.asdict(rows["per_block"].pop("meters"))
            m_pk = dataclasses.asdict(rows["packed"].pop("meters"))
            m_pb.pop("wall_seconds"), m_pk.pop("wall_seconds")
            assert m_pb == m_pk, "execution modes must meter identically"
            speedup = (
                rows["per_block"]["per_sweep_seconds"]
                / rows["packed"]["per_sweep_seconds"]
            )
            dispatch_ratio = (
                rows["per_block"]["dispatches_per_sweep"]
                / rows["packed"]["dispatches_per_sweep"]
            )
            print(
                f"  {strategy:>4}   speedup: {speedup:5.1f}x wall, "
                f"{dispatch_ratio:5.1f}x fewer dispatches "
                f"(attrs match, meters identical)"
            )
            for execution in ("per_block", "packed"):
                report["results"].append({"P": P, **rows[execution]})
            report["speedups"].append(
                {
                    "P": P,
                    "strategy": strategy,
                    "wall_speedup": speedup,
                    "dispatch_ratio": dispatch_ratio,
                }
            )


def powerlaw_section(report, args):
    """Skewed graphs: old vs adaptive packing, packed-host vs per-block-host."""
    graphs = []
    if args.smoke:
        graphs.append(("zipf", zipf(2000, 14000, alpha=1.9, seed=args.seed)))
    else:
        graphs.append(("zipf", zipf(args.n, args.m, alpha=1.9, seed=args.seed)))
        graphs.append(("rmat", rmat(14, 8, seed=args.seed)))
    for gen_name, (src, dst) in graphs:
        el = degree_and_densify(src, dst, drop_self_loops=True)
        for P in args.pl_p_values:
            g = build_dsss(el, P)
            adaptive = g.packed_sweep("adaptive")
            legacy = g.packed_sweep("subshard")
            print(
                f"{gen_name} P={P} (n={el.n}, m={el.m}): padding "
                f"adaptive={adaptive.padding_ratio:.3f}x "
                f"(T={adaptive.tile_edges}, NT={adaptive.num_tiles}) vs "
                f"subshard={legacy.padding_ratio:.3f}x "
                f"(T={legacy.tile_edges}, NT={legacy.num_tiles})"
            )
            row = {
                "generator": gen_name,
                "P": P,
                "n": el.n,
                "m": el.m,
                "padding_ratio_adaptive": adaptive.padding_ratio,
                "padding_ratio_subshard": legacy.padding_ratio,
                "tile_edges_adaptive": adaptive.tile_edges,
                "tile_edges_subshard": legacy.tile_edges,
            }
            # Device residency: the packing ablation (same compiled path).
            dev_rows = {}
            for packing in ("subshard", "adaptive"):
                sess = GraphSession(g, residency="device", packing=packing)
                r = bench_one(sess, "spu", "packed", args.iters)
                dev_rows[packing] = r
                row[f"device_packed_{packing}_per_sweep_seconds"] = r[
                    "per_sweep_seconds"
                ]
                print(
                    f"  device packed/{packing:>8}: "
                    f"{r['per_sweep_seconds'] * 1e3:8.2f} ms/sweep"
                )
            np.testing.assert_array_equal(
                dev_rows["subshard"]["attrs"], dev_rows["adaptive"]["attrs"]
            )
            # Out-of-core: budget ≈ attrs + half the edge bytes, SPU.
            budget = 2 * g.n_pad * 8 + g.total_edge_bytes(8) // 2
            sess_h = GraphSession(g, memory_budget=budget, residency="host")
            host_rows = {}
            for execution in ("per_block", "packed"):
                r = bench_one(sess_h, "spu", execution, args.host_iters)
                host_rows[execution] = r
                row[f"host_{execution}_per_sweep_seconds"] = r["per_sweep_seconds"]
                row[f"host_{execution}_h2d_per_sweep"] = r["h2d_per_sweep"]
                print(
                    f"  host   {execution:>9}: "
                    f"{r['per_sweep_seconds'] * 1e3:8.2f} ms/sweep, "
                    f"h2d {r['h2d_per_sweep'] / 1e6:6.2f} MB/sweep, "
                    f"{r['dispatches_per_sweep']:6.1f} dispatches/sweep"
                )
            assert_sums_match(
                host_rows["per_block"]["attrs"], host_rows["packed"]["attrs"]
            )
            # Host ≡ device bit-identity, at matching sweep counts (the
            # device ablation rows above may use a different iters).
            dev_ref = GraphSession(g, residency="device").run(
                ExecutionPlan(
                    PageRank(), strategy="spu", max_iters=args.host_iters,
                    tol=0.0, execution="packed",
                )
            )
            np.testing.assert_array_equal(
                host_rows["packed"]["attrs"], dev_ref.attrs
            )
            assert (
                host_rows["per_block"]["meters"].model_dict()
                == host_rows["packed"]["meters"].model_dict()
            ), "host execution modes must model-meter identically"
            row["host_wall_speedup"] = (
                row["host_per_block_per_sweep_seconds"]
                / row["host_packed_per_sweep_seconds"]
            )
            row["device_packing_wall_speedup"] = (
                row["device_packed_subshard_per_sweep_seconds"]
                / row["device_packed_adaptive_per_sweep_seconds"]
            )
            print(
                f"  adaptive vs subshard: {row['device_packing_wall_speedup']:.2f}x; "
                f"packed-host vs per-block-host: {row['host_wall_speedup']:.2f}x "
                "(attrs match, model meters identical)"
            )
            report["powerlaw"].append(row)


def frontier_section(report, args):
    """Frontier-aware selective execution: BFS on R-MAT, host residency.

    Selective (``activity="auto"``, the default for monotone programs) vs
    the full-sweep ``activity="off"`` baseline, out-of-core. The physical
    per-sweep ``bytes_h2d`` is reconstructed from the run's
    ``activity_log`` via the iomodel closed form and asserted to match
    the measured meter exactly; the gated headline is the *late-iteration*
    skip — the trailing sweeps whose frontier has collapsed to ≤ P/2
    intervals, where NXgraph-style activity tracking pays off most.
    """
    scale = 13 if args.smoke else 15
    P = 16 if args.smoke else 32
    src, dst = rmat(scale, 4, seed=args.seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    g = build_dsss(el, P)
    # A tight budget: nothing pins, chunks are fine-grained — the regime
    # where skipping inactive streamed chunks can actually bite.
    budget = int((2 * g.n_pad * 8 + g.total_edge_bytes(8)) * 0.05)
    plan_kw = dict(
        strategy="spu", max_iters=g.n + 1, execution="packed",
        program_kwargs={"root": 0},
    )
    runs = {}
    for activity in ("auto", "off"):
        sess = GraphSession(g, memory_budget=budget, residency="host")
        plan = ExecutionPlan(BFS(), activity=activity, **plan_kw)
        sess.run(plan)  # warmup: staging + jit compilation
        with DispatchCounter() as counter:
            res = sess.run(plan)
        runs[activity] = (sess, res, counter.count / res.iterations)
    sess, on, on_disp = runs["auto"]
    _, off, off_disp = runs["off"]
    np.testing.assert_array_equal(on.attrs, off.attrs)
    assert on.iterations == off.iterations
    # Measured-vs-modelled exactness: the per-sweep closed form over the
    # activity log reproduces the physical meter byte for byte.
    compiled = sess.compile(ExecutionPlan(BFS(), **plan_kw))
    splan = sess.packed_stream_plan(compiled.choice.strategy, 4)
    full_sweep = packed_h2d_bytes(
        splan.num_tiles - splan.pin_tiles, splan.tile_edges
    )
    per_sweep = [
        packed_h2d_bytes(
            selective_streamed_tiles(
                sess._packed_tile_activity(log),
                splan.pin_tiles,
                splan.chunk_tiles,
            ),
            splan.tile_edges,
        )
        for log in on.activity_log
    ]
    assert sum(per_sweep) == on.meters.bytes_h2d
    assert off.meters.bytes_h2d == full_sweep * off.iterations
    frontier = [int(log.sum()) for log in on.activity_log]
    # Late iterations: the trailing sweeps with a collapsed (≤ P/2) frontier.
    k = len(frontier)
    while k > 0 and frontier[k - 1] <= P // 2:
        k -= 1
    late = list(range(k, len(frontier))) or [len(frontier) - 1]
    late_on = sum(per_sweep[i] for i in late)
    late_skip_ratio = (full_sweep * len(late)) / max(late_on, 1.0)
    row = {
        "generator": "rmat",
        "scale": scale,
        "P": P,
        "n": el.n,
        "m": el.m,
        "sweeps": on.iterations,
        "frontier_intervals": frontier,
        "h2d_selective": on.meters.bytes_h2d,
        "h2d_off": off.meters.bytes_h2d,
        "h2d_ratio": off.meters.bytes_h2d / on.meters.bytes_h2d,
        "late_sweeps": late,
        "late_skip_ratio": late_skip_ratio,
        "dispatches_per_sweep_selective": on_disp,
        "dispatches_per_sweep_off": off_disp,
        "per_sweep_seconds_selective": on.meters.wall_seconds / on.iterations,
        "per_sweep_seconds_off": off.meters.wall_seconds / off.iterations,
    }
    print(
        f"frontier rmat scale={scale} P={P} (n={el.n}, m={el.m}): "
        f"{on.iterations} sweeps, frontier {frontier}; h2d "
        f"{on.meters.bytes_h2d / 1e6:.2f} MB selective vs "
        f"{off.meters.bytes_h2d / 1e6:.2f} MB off "
        f"({row['h2d_ratio']:.2f}x), late sweeps {late}: "
        f"{late_skip_ratio:.1f}x skip (bit-identical, meters exact)"
    )
    report["frontier"].append(row)


def kernel_section(report, args):
    """Fused Pallas sweep (``packed_kernel``) vs the XLA scan (``packed``).

    Both executables are driven through the identical session machinery
    (same staging, same streaming, same apply), so every row asserts
    attrs within the sum tolerance and fully identical meters — including
    physical fields — and that the kernel mode dispatched exactly one fused
    ``pallas_call`` per update sweep with the same total dispatch count
    as the scan. Off-TPU the kernel runs under the Pallas interpreter,
    so wall seconds compare a debugging path against compiled XLA; on
    TPU (``backend == "compiled"``) they compare like against like.
    """
    from repro.kernels.dsss_spmv import default_interpret

    n, m, P, iters = (400, 2_400, 4, 2) if args.smoke else (3_000, 18_000, 8, 3)
    src, dst = erdos_renyi(n, m, seed=args.seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    g = build_dsss(el, P)
    sess = GraphSession(g, residency="device")
    kernel_backend = "interpret" if default_interpret() else "compiled"
    for strategy in ("spu", "dpu"):
        rows = {}
        for execution in ("packed", "packed_kernel"):
            r = bench_one(sess, strategy, execution, iters)
            rows[execution] = r
            print(
                f"kernel {strategy:>4} {execution:>13}: "
                f"{r['per_sweep_seconds'] * 1e3:8.2f} ms/sweep, "
                f"{r['dispatches_per_sweep']:5.1f} dispatches/sweep "
                f"({r['fused_dispatches_per_sweep']:.1f} fused)"
            )
        assert_sums_match(
            rows["packed"].pop("attrs"), rows["packed_kernel"].pop("attrs")
        )
        m_scan = dataclasses.asdict(rows["packed"].pop("meters"))
        m_kern = dataclasses.asdict(rows["packed_kernel"].pop("meters"))
        m_scan.pop("wall_seconds"), m_kern.pop("wall_seconds")
        assert m_scan == m_kern, "kernel and scan must meter identically"
        row = {
            "P": P,
            "n": el.n,
            "m": el.m,
            "strategy": strategy,
            "kernel_backend": kernel_backend,
            "scan_per_sweep_seconds": rows["packed"]["per_sweep_seconds"],
            "kernel_per_sweep_seconds": rows["packed_kernel"][
                "per_sweep_seconds"
            ],
            "scan_dispatches_per_sweep": rows["packed"]["dispatches_per_sweep"],
            "kernel_dispatches_per_sweep": rows["packed_kernel"][
                "dispatches_per_sweep"
            ],
            "fused_dispatches_per_sweep": rows["packed_kernel"][
                "fused_dispatches_per_sweep"
            ],
            "attrs_match": True,
            "meters_identical": True,
        }
        print(
            f"kernel {strategy:>4}   parity: attrs match, meters identical, "
            f"{row['fused_dispatches_per_sweep']:.1f} fused dispatch/sweep "
            f"({kernel_backend})"
        )
        report["kernel"].append(row)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--p-values", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--pl-p-values", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--m", type=int, default=120_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--host-iters", type=int, default=3)
    ap.add_argument(
        "--strategies", nargs="+", default=["spu", "dpu"],
        choices=["spu", "dpu", "mpu"],
    )
    ap.add_argument(
        "--assert-padding-ratio", type=float, default=None,
        help="fail (exit 1) if any power-law adaptive padding ratio exceeds this",
    )
    ap.add_argument(
        "--assert-skip-ratio", type=float, default=None,
        help="fail (exit 1) if the frontier section's late-iteration h2d "
        "skip ratio (selective vs activity='off') falls below this",
    )
    ap.add_argument(
        "--assert-kernel-parity", action="store_true",
        help="fail (exit 1) unless every kernel-section row matches the "
        "scan (attrs within the sum tolerance, meters identical) with "
        "exactly one fused dispatch per sweep",
    )
    ap.add_argument(
        "--out",
        default=str(pathlib.Path(__file__).resolve().parent.parent / "BENCH_sweep.json"),
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny graphs, P=[4]/[16], 2 sweeps — the CI artifact variant",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.p_values, args.n, args.m, args.iters = [4], 400, 2_400, 2
        args.pl_p_values, args.host_iters = [16], 2

    report = {
        "benchmark": "bench_sweep",
        "backend": jax.default_backend(),
        "iters_per_run": args.iters,
        "results": [],
        "speedups": [],
        "powerlaw": [],
        "frontier": [],
        "kernel": [],
    }
    uniform_section(report, args)
    powerlaw_section(report, args)
    frontier_section(report, args)
    kernel_section(report, args)
    if args.assert_kernel_parity:
        for row in report["kernel"]:
            assert row["attrs_match"] and row["meters_identical"], (
                f"kernel {row['strategy']} P={row['P']}: parity broken"
            )
            assert row["fused_dispatches_per_sweep"] == 1.0, (
                f"kernel {row['strategy']} P={row['P']}: expected exactly "
                f"one fused dispatch per sweep, got "
                f"{row['fused_dispatches_per_sweep']}"
            )
            assert (
                row["kernel_dispatches_per_sweep"]
                == row["scan_dispatches_per_sweep"]
            ), (
                f"kernel {row['strategy']} P={row['P']}: dispatch shape "
                f"diverged ({row['kernel_dispatches_per_sweep']} vs "
                f"{row['scan_dispatches_per_sweep']})"
            )
        print(
            f"kernel-parity gate holds on all {len(report['kernel'])} "
            "kernel configurations"
        )
    if args.assert_skip_ratio is not None:
        for row in report["frontier"]:
            assert row["late_skip_ratio"] >= args.assert_skip_ratio, (
                f"frontier {row['generator']} scale={row['scale']} "
                f"P={row['P']}: late-iteration skip ratio "
                f"{row['late_skip_ratio']:.2f} below the "
                f"{args.assert_skip_ratio} bound"
            )
        print(
            f"late-iteration skip-ratio bound {args.assert_skip_ratio} holds "
            f"on all {len(report['frontier'])} frontier configurations"
        )
    if args.assert_padding_ratio is not None:
        for row in report["powerlaw"]:
            assert row["padding_ratio_adaptive"] <= args.assert_padding_ratio, (
                f"{row['generator']} P={row['P']}: adaptive padding "
                f"{row['padding_ratio_adaptive']:.3f} exceeds the "
                f"{args.assert_padding_ratio} bound"
            )
        print(
            f"padding-ratio bound {args.assert_padding_ratio} holds on all "
            f"{len(report['powerlaw'])} power-law configurations"
        )
    out = pathlib.Path(args.out)
    stamp(report, bench="sweep", smoke=args.smoke)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {out}")
    return report


if __name__ == "__main__":
    main()
