#!/usr/bin/env python3
"""The control: the PageRank reference in bfloat16, put in the engine's place.

    python3 bench/tests/control.py --workload <cell> --seed <n> --seconds <s> --trace 0

A benchmark run in every step, except that each job's ranks are replaced by
``reference.pagerank_bf16`` over the run's own raw edges, mapped to the
engine's dense ids: the precision step below the engine's float32 ranks.
The harness judges them as it judges the engine's, so ``correct`` must read
false and the result line carries the control's readings beside the
limits. ``test_bench_harness.py`` runs it at a small size on the CPU; run on
the chip at the cell's size, it gives the upper readings in ``PERF.md``.
The benchmark's own runs never load this file.
"""
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness, reference  # noqa: E402


def install(setattr) -> None:
    """Patch ``harness`` (with ``setattr(obj, name, value)``) to run the control."""
    raw = {}
    real_generate, real_make_traffic = harness.generate, harness.make_traffic

    def generate(root, config, seed):
        src, dst, num_labels = real_generate(root, config, seed)
        raw.update(src=src, dst=dst, num_labels=num_labels)
        return src, dst, num_labels

    def make_traffic(root, params, graph, seed):
        traffic = real_make_traffic(root, params, graph, seed)
        ref = reference.clean_edges(raw["src"], raw["dst"], raw["num_labels"])
        ranks = reference.pagerank_bf16(ref, traffic.damping, traffic.iterations)
        dense = ranks[np.asarray(graph.edgelist.id_to_index)].astype(np.float32)
        real_run = traffic.run

        def run(session, i):
            done = real_run(session, i)
            done.output = dense.copy()
            return done

        traffic.run = run
        return traffic

    setattr(harness, "generate", generate)
    setattr(harness, "make_traffic", make_traffic)


if __name__ == "__main__":
    install(setattr)
    sys.exit(harness.main(root=ROOT, t_start=T_START))
