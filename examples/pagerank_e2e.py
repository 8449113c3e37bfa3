"""End-to-end driver (the paper's flagship task): PageRank on the
twitter-scale stand-in, run the way a deployment would — from an on-disk
``.dsss`` container through the disk residency tier.

The graph is preprocessed + sharded once and serialized to a ``.dsss``
store (cached next to this script; delete it to rebuild); every later run
just ``GraphSession.open()``s the file — the sub-shard blocks and packed
tiles are mmap views, streamed disk→device under the three-level
``memory_budget`` / ``host_memory_budget`` hierarchy with adaptive
strategy selection and MTEPS reporting.

    PYTHONPATH=src python examples/pagerank_e2e.py [--iters 10]
"""
import argparse
import os
import time

from repro import compile_cache
from repro.core import ExecutionPlan, GraphSession, PageRank, build_dsss
from repro.graph.generators import paper_dataset
from repro.graph.preprocess import degree_and_densify
from repro.storage import write_dsss


def ensure_store(path: str, P: int) -> None:
    if os.path.exists(path):
        return
    t0 = time.time()
    src, dst = paper_dataset("twitter")
    el = degree_and_densify(src, dst, drop_self_loops=True)
    g = build_dsss(el, P)
    write_dsss(g, path)
    print(
        f"built {path}: n={g.n} m={g.m} P={g.P} "
        f"({os.path.getsize(path)/1e6:.1f}MB, {time.time()-t0:.1f}s)"
    )
    # For graphs that don't fit in RAM, the same container comes out of
    # the bounded-memory pipeline instead:
    #   python -m repro.storage build edges.txt twitter.dsss --P 12


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--P", type=int, default=12)
    ap.add_argument("--budget-frac", type=float, default=None,
                    help="device memory budget as a fraction of full working set")
    ap.add_argument("--store", default=None,
                    help=".dsss path (default: cached next to this script)")
    args = ap.parse_args()

    path = args.store or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), f"twitter_P{args.P}.dsss"
    )
    ensure_store(path, args.P)

    budget = None
    if args.budget_frac is not None:
        # Size the budget from the store metadata alone — no need to
        # assemble the graph twice.
        from repro.storage import open_dsss

        meta = open_dsss(path).meta
        n_pad = meta["P"] * meta["interval_size"]
        budget = int((2 * n_pad * 8 + meta["m"] * 8) * args.budget_frac)

    t0 = time.time()
    session = GraphSession.open(
        path,
        memory_budget=budget,
        # mid tier: 4x the device budget (None = unlimited RAM cache)
        host_memory_budget=None if budget is None else budget * 4,
        verify=False,
    )
    g = session.graph
    print(f"opened {path}: n={g.n} m={g.m} P={g.P} ({time.time()-t0:.2f}s, mmap)")

    plan = ExecutionPlan(PageRank(), strategy="auto",
                         max_iters=args.iters, tol=0.0)
    compiled = session.compile(plan)
    print(
        f"strategy: {compiled.choice.strategy} (Q={compiled.choice.Q}) "
        f"residency={compiled.residency} execution={compiled.execution}"
    )
    res = session.run(plan)
    m = res.meters
    print(
        f"{res.iterations} iterations in {m.wall_seconds:.2f}s "
        f"({m.wall_seconds/res.iterations:.3f}s/iter)"
    )
    print(
        f"slow-tier: read {m.bytes_read/1e6:.1f}MB write {m.bytes_written/1e6:.1f}MB"
        f" | disk tier: {m.bytes_disk_read/1e6:.1f}MB mmap-streamed"
    )
    print("paper reference: 2.05s/iter on real Twitter (1.47B edges), 1 PC")


if __name__ == "__main__":
    main()
