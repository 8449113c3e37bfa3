"""Targeted queries with interval-activity skipping: BFS / WCC / SCC,
plus a batched 16-source BFS sharing one edge-stream pass.

    PYTHONPATH=src python examples/bfs_wcc.py
"""
import numpy as np

from repro import compile_cache
from repro.core import bfs, multi_bfs, scc, wcc
from repro.graph.generators import paper_dataset
from repro.graph.preprocess import degree_and_densify


def main():
    compile_cache.enable()
    src, dst = paper_dataset("live-journal")
    el = degree_and_densify(src, dst, drop_self_loops=True)
    print(f"graph: n={el.n} m={el.m}")

    res = bfs(el, root=0, P=8)
    m = res.meters
    print(
        f"BFS : depth={res.output} iters={res.iterations} "
        f"blocks processed={m.blocks_processed} skipped={m.blocks_skipped} "
        f"(activity tracking, paper §II-B)"
    )

    # Multi-source BFS: 16 roots, one batched pass per sweep. The driver
    # re-uses the session (and staged blocks) from the single-source run.
    roots = np.linspace(0, el.n - 1, 16).astype(int).tolist()
    batch = multi_bfs(el, roots, P=8)
    print(
        f"BFS×{len(roots)}: fused={batch.fused} sweeps={batch.iterations} "
        f"mean depth={np.mean([r.output for r in batch]):.1f} "
        f"(one edge stream for all sources)"
    )

    res = wcc(el, P=8)
    n_comp = len(np.unique(res.attrs))
    print(f"WCC : {n_comp} components, iters={res.iterations}")
    labels = scc(el, P=8)
    print(f"SCC : {len(set(labels.tolist()))} components")


if __name__ == "__main__":
    main()
