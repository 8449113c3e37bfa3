"""Quickstart: stage a graph once, run many programs, batch many queries.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import ExecutionPlan, GraphSession, BFS, PageRank, build_dsss
from repro.graph.generators import rmat
from repro.graph.preprocess import degree_and_densify


def main():
    compile_cache.enable()
    # 1. raw edges -> degreeing (dense ids) -> DSSS sharding
    src, dst = rmat(12, edge_factor=8, seed=0)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    graph = build_dsss(el, P=8)
    print(f"graph: n={graph.n} m={graph.m} P={graph.P} "
          f"hub-factor d={graph.mean_hub_in_degree():.1f}")

    # 2. stage the graph ONCE: the session owns the device-resident
    #    sub-shard blocks; every plan below re-uses them.
    session = GraphSession(graph, memory_budget=graph.n_pad * 8)  # force MPU to mix

    # 3. run PageRank under each strategy — identical results, different
    #    slow-tier traffic (paper Table II). Same staged blocks every time.
    for strategy in ["spu", "dpu", "mpu", "fused"]:
        plan = ExecutionPlan(PageRank(), strategy=strategy, max_iters=20, tol=1e-9)
        res = session.run(plan)
        per = res.meters.per_iteration()
        top = np.argsort(res.output)[-3:][::-1]
        print(
            f"{strategy:6s} iters={res.iterations:2d} "
            f"read/iter={per.bytes_read:9.0f}B write/iter={per.bytes_written:8.0f}B "
            f"top vertices={top.tolist()}"
        )

    # 4. batch 32 BFS sources into ONE streamed pass over the edge blocks:
    #    the edge traffic is paid once per sweep, not 32 times.
    roots = np.linspace(0, graph.n - 1, 32).astype(int).tolist()
    batch = session.run_batch(
        [
            ExecutionPlan(BFS(), max_iters=graph.n + 1, program_kwargs={"root": r})
            for r in roots
        ]
    )
    depths = [res.output for res in batch]
    print(
        f"bfs×{len(roots)}: fused={batch.fused} sweeps={batch.iterations} "
        f"edge-bytes={batch.meters.bytes_read_edges:.0f} "
        f"(single pass, not {len(roots)}×) max-depths={sorted(set(depths))}"
    )


if __name__ == "__main__":
    main()
