"""Job kind ``pagerank``: LDBC Graphalytics PageRank, fixed iterations, tolerance 0.

A traffic file that names ``"job": "pagerank"`` gives ``damping``,
``iterations``, ``tol`` and ``limits``. Every job computes the same ranks;
the last job of the window is checked against the float64 reference.

Each job kind is one file ``bench/jobs/<kind>.py`` with a class ``Jobs``:

- ``Jobs(params, graph, seed)``: the traffic file's parameters, the
  engine's graph and the run's seed;
- ``warmup(session)``: run every shape the window will use, once;
- ``run(session, i)``: job ``i`` of the window, ending in a host sync;
  returns a :class:`bench.harness.Done`;
- ``attr_bytes``: bytes of one vertex attribute, for the sweep's least bytes;
- ``check(ref, id_to_index, done)``: after the window, against the plain
  reference, the work of all jobs (edges traversed, counted from the
  benchmark's own graph) and each compared number with its limit.
"""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.harness import Done

__all__ = ["Jobs"]


class Jobs:
    attr_bytes = 4  # float32 ranks

    def __init__(self, params: dict, graph, seed: int):
        from repro.core import ExecutionPlan, PageRank

        self.damping = float(params["damping"])
        self.iterations = int(params["iterations"])
        self.limits = {k: float(v) for k, v in params["limits"].items()}
        prog = PageRank(damping=self.damping)
        self.plan = ExecutionPlan(prog, max_iters=self.iterations, tol=float(params["tol"]))
        self.warm_plan = ExecutionPlan(prog, max_iters=min(2, self.iterations), tol=0.0)

    def warmup(self, session) -> None:
        session.run(self.warm_plan)

    def run(self, session, i: int) -> Done:
        res = session.run(self.plan)
        return Done(res.iterations, res.attrs)

    def check(self, ref: reference.RefGraph, id_to_index, done: list[Done]):
        work = sum(d.sweeps for d in done) * ref.m
        want = reference.pagerank_ref(ref, self.damping, self.iterations)
        last = done[-1]
        got = np.full(ref.num_labels, np.nan)
        if last.output.shape == id_to_index.shape and last.sweeps == self.iterations:
            got[:] = 0.0
            got[id_to_index] = last.output
        values = reference.rank_errors(got, want, ref.present)
        return work, {k: (values[k], self.limits[k]) for k in self.limits}
