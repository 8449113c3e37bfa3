"""Plain references for the benchmark's jobs, in raw vertex labels.

They read the edges exactly as the generator emitted them and do their own
cleaning (self loops dropped, duplicates merged, isolated labels left out),
so a comparison against them covers the engine's preprocessing as well as
its sweeps. Nothing here imports the engine: answers are mapped into label
space by the caller, through the engine's dense-id -> label table.

``pagerank_ref`` is float64. ``pagerank_bf16`` is the control: the same
iteration with every stored value and product rounded to bfloat16 (sums
are taken wider, as a bfloat16 path with float32 accumulation would), the
precision step below the engine's float32 ranks.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

__all__ = [
    "RefGraph",
    "clean_edges",
    "pagerank_ref",
    "pagerank_bf16",
    "rank_errors",
    "bfs_ref",
]

UNREACHED = -1


@dataclasses.dataclass(frozen=True)
class RefGraph:
    """A cleaned directed graph over labels ``[0, num_labels)``."""

    src: np.ndarray  # int32 (m,), sorted by (src, dst)
    dst: np.ndarray  # int32 (m,)
    num_labels: int
    present: np.ndarray  # bool (num_labels,): label has an incident edge
    out_degree: np.ndarray  # int64 (num_labels,)

    @property
    def n(self) -> int:
        return int(self.present.sum())

    @property
    def m(self) -> int:
        return int(self.src.shape[0])


def clean_edges(src: np.ndarray, dst: np.ndarray, num_labels: int) -> RefGraph:
    """Drop self loops and duplicate edges; find the non-isolated labels."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    keep = src != dst
    key = np.unique(src[keep] * num_labels + dst[keep])
    s = (key // num_labels).astype(np.int32)
    d = (key % num_labels).astype(np.int32)
    present = np.zeros(num_labels, bool)
    present[s] = True
    present[d] = True
    out_degree = np.bincount(s, minlength=num_labels).astype(np.int64)
    return RefGraph(s, d, num_labels, present, out_degree)


def _pagerank(g: RefGraph, damping: float, iters: int, q) -> np.ndarray:
    n = g.n
    deg = g.out_degree.astype(np.float64)
    inv = q(np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0))
    dangling = g.present & (deg == 0)
    r = q(np.where(g.present, 1.0 / n, 0.0))
    for _ in range(iters):
        y = q(np.bincount(g.dst, weights=q(r * inv)[g.src], minlength=g.num_labels))
        mass = q(r[dangling].sum())
        r = q((1.0 - damping) / n + damping * (y + mass / n))
        r = np.where(g.present, r, 0.0)
    return r


def pagerank_ref(g: RefGraph, damping: float, iters: int) -> np.ndarray:
    """Float64 PageRank, uniform teleport, dangling mass spread uniformly."""
    return _pagerank(g, damping, iters, lambda x: x)


def pagerank_bf16(g: RefGraph, damping: float, iters: int) -> np.ndarray:
    """The control: the same PageRank with values rounded to bfloat16."""
    bf16 = ml_dtypes.bfloat16
    return _pagerank(
        g, damping, iters, lambda x: np.asarray(x).astype(bf16).astype(np.float64)
    )


def rank_errors(got: np.ndarray, ref: np.ndarray, present: np.ndarray) -> dict:
    """The numbers a PageRank answer is judged by, against ``ref``.

    ``rank_max_rel_err``: the largest relative error over present labels.
    ``rank_l1_err``: the L1 distance over all labels, so a rank put on a
    label the reference leaves out counts too. A non-finite answer reads
    as infinitely far.
    """
    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return {"rank_max_rel_err": float("inf"), "rank_l1_err": float("inf")}
    diff = np.abs(got - ref)
    return {
        "rank_max_rel_err": float((diff[present] / ref[present]).max()),
        "rank_l1_err": float(diff.sum()),
    }


def bfs_ref(g: RefGraph, root: int) -> np.ndarray:
    """Directed level-synchronous BFS; ``UNREACHED`` where no path exists."""
    starts = np.zeros(g.num_labels + 1, np.int64)
    np.cumsum(g.out_degree, out=starts[1:])
    depth = np.full(g.num_labels, UNREACHED, np.int32)
    depth[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size:
        level += 1
        lo, cnt = starts[frontier], g.out_degree[frontier]
        idx = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        nbrs = np.unique(g.dst[idx])
        frontier = nbrs[depth[nbrs] == UNREACHED]
        depth[frontier] = level
    return depth
