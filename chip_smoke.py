#!/usr/bin/env python3
"""Bring-up smoke run of the graph engine on one TPU (or one 2x2 TPU host).

Builds an R-MAT graph at the scale of the paper's smallest real graph,
LiveJournal (paper Table III: n = 4.85 M, m = 69 M): scale 23, edge factor
8, about 67 M edges before de-duplication, all made from ``--seed``. The
graph goes through ``degree_and_densify`` -> ``build_dsss`` (P = 16) ->
``write_dsss`` into a fresh directory under ``--out``, then drives the
engine's main path through the entry points a user calls:

  A  device residency: ``GraphSession`` PageRank, 10 iterations, tol 0,
     ``execution="auto"``; checked against a numpy float64 PageRank.
  B  disk tier: ``GraphSession.open`` on the ``.dsss`` with a memory budget
     of both attribute copies plus a quarter of the edge bytes, so tiles
     stream disk -> host -> device; attributes must be bit-identical to A,
     ``bytes_h2d`` > 0, ``bytes_disk_read`` equal to its closed form.
  C  serving: a ``GraphServer`` answers 16 BFS point queries from 16 seeded
     roots as one fused batch; every depth vector must equal a numpy
     level-synchronous BFS exactly.

``--chips 4`` runs only ``repro.core.distributed.distributed_pagerank`` on a
2x2 mesh of ``jax.devices()`` and compares it with phase A's single-chip
PageRank, within phase A's tolerance.

The run fails, with no result line, unless JAX's first device is a TPU; any
failed check or exception exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python3 chip_smoke.py [--seed 0] [--scale 23] [--chips 1|4] [--out DIR]
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import BFS, ExecutionPlan, GraphSession, PageRank, build_dsss  # noqa: E402
from repro.core.identities import INF_DEPTH  # noqa: E402
from repro.core.iomodel import packed_disk_bytes  # noqa: E402
from repro.graph.generators import rmat  # noqa: E402
from repro.graph.preprocess import degree_and_densify  # noqa: E402
from repro.serving import GraphServer, QueryRequest, SessionPool  # noqa: E402
from repro.storage import write_dsss  # noqa: E402

ITERS = 10
DAMPING = 0.85
N_QUERIES = 16
# float32 engine vs float64 reference: each rank is a float32 sum over up
# to ~1e5 in-edges per iteration, so per-vertex relative error can reach
# ~k·eps32 for hubs. Ranks sum to 1; the L1 bound is ~100x the error seen
# at scale 18 on CPU, the per-vertex bound covers the k·eps worst case.
PR_L1_TOL = 1e-4
PR_REL_TOL = 1e-3


class CompileClock:
    """Sums JAX backend-compile time (cache retrievals included)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, snap) -> str:
        s, c, h = snap
        return (
            f"compile_s={self.seconds - s:.3f} compiles={self.compiles - c} "
            f"cache_hits={self.cache_hits - h}"
        )


def require_tpu():
    """The first device must be a TPU; anything else is a failed run."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU — JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); this run never falls back to it"
        )
    return dev


def log(msg: str) -> None:
    print(msg, flush=True)


# -- plain references (numpy, independent of the engine) ---------------------
def pagerank_ref(src, dst, out_degree, n, iters):
    """Float64 PageRank with uniform teleport and dangling redistribution."""
    deg = out_degree[:n].astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    dangling = deg == 0
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        y = np.bincount(dst, weights=(r * inv)[src], minlength=n)
        r = (1.0 - DAMPING) / n + DAMPING * (y + r[dangling].sum() / n)
    return r


def bfs_ref(src, dst, n, roots):
    """Level-synchronous BFS from up to 16 roots at once (one bit each)."""
    order = np.argsort(dst, kind="stable")
    s = src[order]
    ud, starts = np.unique(dst[order], return_index=True)
    depth = np.full((len(roots), n), INF_DEPTH, np.int32)
    visited = np.zeros(n, np.uint16)
    frontier = np.zeros(n, np.uint16)
    for k, r in enumerate(roots):
        visited[r] |= np.uint16(1 << k)
        frontier[r] |= np.uint16(1 << k)
        depth[k, r] = 0
    level = 0
    while frontier.any():
        level += 1
        new = np.bitwise_or.reduceat(frontier[s], starts) & ~visited[ud]
        frontier[:] = 0
        frontier[ud] = new
        visited[ud] |= new
        for k in range(len(roots)):
            depth[k, ud[((new >> k) & 1).astype(bool)]] = level
    return depth


def check_pagerank(name, got, ref):
    l1 = float(np.abs(got - ref).sum())
    rel = float((np.abs(got - ref) / ref).max())
    log(f"{name}: l1_err={l1:.3e} (tol {PR_L1_TOL}) "
        f"max_rel_err={rel:.3e} (tol {PR_REL_TOL})")
    if not (np.all(np.isfinite(got)) and l1 <= PR_L1_TOL and rel <= PR_REL_TOL):
        raise AssertionError(f"{name}: PageRank outside tolerance")


# -- phases -------------------------------------------------------------------
def build_graph(args, graph_dir: Path):
    t0 = time.perf_counter()
    src, dst = rmat(args.scale, edge_factor=args.edge_factor, seed=args.seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    del src, dst
    g = build_dsss(el, args.P)
    path = graph_dir / f"rmat{args.scale}_seed{args.seed}.dsss"
    store = write_dsss(g, str(path))
    tile_bytes = sum(
        seg.nbytes for name, seg in store.segments.items()
        if name.startswith("p_")
    )
    log(f"graph: n={g.n} m={g.m} P={g.P} n_pad={g.n_pad} "
        f"tile_edges={store.meta['tile_edges']} "
        f"num_tiles={store.meta['num_tiles']} "
        f"packed_tile_bytes={tile_bytes} file_bytes={path.stat().st_size} "
        f"build_s={time.perf_counter() - t0:.3f}")
    return el, g, path


def pagerank_plan(iters):
    return ExecutionPlan(PageRank(damping=DAMPING), max_iters=iters, tol=0.0)


def phase_a(g, el, clock):
    snap = clock.snapshot()
    sess = GraphSession(g)
    t0 = time.perf_counter()
    sess.run(pagerank_plan(1))  # stages the tiles and compiles
    warm_s = time.perf_counter() - t0
    compiled = sess.compile(pagerank_plan(ITERS))
    t0 = time.perf_counter()
    res = sess.run(pagerank_plan(ITERS))
    attrs = np.asarray(res.attrs)
    run_s = time.perf_counter() - t0
    if res.iterations != ITERS:
        raise AssertionError(f"phase A ran {res.iterations} iterations")
    log(f"phase A: residency={compiled.residency} "
        f"execution={compiled.execution} strategy={res.strategy.strategy} "
        f"first_run_s={warm_s:.3f} s_per_iter={run_s / ITERS:.6f} "
        f"{clock.since(snap)}")
    ref = pagerank_ref(el.src, el.dst, g.out_degree, g.n, ITERS)
    check_pagerank("phase A", attrs[: g.n].astype(np.float64), ref)
    return attrs


def phase_b(g, path, attrs_a, clock):
    snap = clock.snapshot()
    edge_bytes = g.total_edge_bytes(8)
    sess = GraphSession.open(
        str(path),
        memory_budget=2 * g.n_pad * 4 + edge_bytes // 4,
        host_memory_budget=edge_bytes // 4,
    )
    plan = pagerank_plan(ITERS)
    compiled = sess.compile(plan)
    t0 = time.perf_counter()
    res = sess.run(plan)
    run_s = time.perf_counter() - t0
    splan = sess.packed_stream_plan(compiled.choice.strategy, compiled.params.Ba)
    disk_expect = ITERS * packed_disk_bytes(
        splan.num_tiles - splan.pin_tiles - splan.host_tiles,
        splan.tile_edges,
        weighted=sess.has_weights,
    )
    m = res.meters
    log(f"phase B: residency={compiled.residency} "
        f"execution={compiled.execution} strategy={res.strategy.strategy} "
        f"pin_tiles={splan.pin_tiles} host_tiles={splan.host_tiles} "
        f"num_tiles={splan.num_tiles} chunk_tiles={splan.chunk_tiles} "
        f"bytes_h2d={m.bytes_h2d:.0f} bytes_disk_read={m.bytes_disk_read:.0f} "
        f"disk_closed_form={disk_expect:.0f} s_per_iter={run_s / ITERS:.6f} "
        f"{clock.since(snap)}")
    if compiled.residency != "disk":
        raise AssertionError("phase B did not run from the disk tier")
    if not np.array_equal(np.asarray(res.attrs), attrs_a):
        raise AssertionError("phase B attrs differ from phase A (residency)")
    if not m.bytes_h2d > 0:
        raise AssertionError("phase B streamed nothing host->device")
    if m.bytes_disk_read != disk_expect:
        raise AssertionError("phase B disk bytes differ from the closed form")


def phase_c(g, el, seed, clock):
    snap = clock.snapshot()
    rng = np.random.default_rng(seed)
    roots = rng.choice(
        np.flatnonzero(g.out_degree[: g.n] > 0), N_QUERIES, replace=False
    )
    pool = SessionPool()
    pool.register("lj", g)
    server = GraphServer(pool, max_batch=N_QUERIES, max_wait_ms=50.0)
    plans = [
        ExecutionPlan(BFS(), max_iters=g.n + 1, program_kwargs={"root": int(r)})
        for r in roots
    ]
    t0 = time.perf_counter()
    out = server.serve([QueryRequest("lj", p) for p in plans])
    serve_s = time.perf_counter() - t0
    stats = server.stats()
    compiled = pool.session("lj").compile(plans[0])
    log(f"phase C: residency={compiled.residency} "
        f"execution={compiled.execution} queries={len(out)} "
        f"fused_batches={stats.fused_batches} "
        f"mean_occupancy={stats.mean_occupancy} "
        f"sweeps={out[0].result.iterations} serve_s={serve_s:.3f} "
        f"{clock.since(snap)}")
    if len(out) != N_QUERIES or stats.fused_batches < 1:
        raise AssertionError("phase C did not serve one fused batch")
    ref = bfs_ref(el.src, el.dst, g.n, roots)
    for k, q in enumerate(out):
        got = np.asarray(q.result.attrs)[: g.n]
        if not np.array_equal(got, ref[k]):
            bad = int((got != ref[k]).sum())
            raise AssertionError(f"phase C root {roots[k]}: {bad} depths differ")
    reached = int((ref < INF_DEPTH).sum())
    log(f"phase C: all {N_QUERIES} depth vectors equal the numpy BFS "
        f"(reached {reached} vertex-root pairs, max depth {int(ref[ref < INF_DEPTH].max())})")


def phase_four_chips(g, el, attrs_a, clock):
    from jax.sharding import Mesh

    from repro.core.distributed import distributed_pagerank

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devices)}")
    snap = clock.snapshot()
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    t0 = time.perf_counter()
    ranks, iters = distributed_pagerank(el, mesh, iters=ITERS, damping=DAMPING)
    run_s = time.perf_counter() - t0
    log(f"4-chip: mesh={dict(mesh.shape)} iters={iters} run_s={run_s:.3f} "
        f"(includes block layout + compile) {clock.since(snap)}")
    check_pagerank(
        "4-chip vs phase A", np.asarray(ranks, np.float64),
        attrs_a[: g.n].astype(np.float64),
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=23)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--P", type=int, default=16)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=str(HERE / "chip_smoke_out"))
    args = ap.parse_args(argv)

    dev = require_tpu()
    cache_dir = compile_cache.enable()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    clock = CompileClock()
    graph_dir = Path(args.out) / "graph"
    shutil.rmtree(graph_dir, ignore_errors=True)
    graph_dir.mkdir(parents=True)
    t_start = time.perf_counter()
    try:
        el, g, path = build_graph(args, graph_dir)
        attrs_a = phase_a(g, el, clock)
        if args.chips == 4:
            phase_four_chips(g, el, attrs_a, clock)
        else:
            phase_b(g, path, attrs_a, clock)
            phase_c(g, el, args.seed, clock)
    finally:
        shutil.rmtree(graph_dir, ignore_errors=True)
    log(f"total: wall_s={time.perf_counter() - t_start:.3f} "
        f"compile_s={clock.seconds:.3f} compiles={clock.compiles} "
        f"cache_hits={clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
