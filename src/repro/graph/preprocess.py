"""Degreeing: the first preprocessing step of NXgraph (paper §III-A).

Maps raw, possibly sparse vertex *indices* to dense, contiguous *ids*
(so interval storage needs only an offset + attribute array — constant-time
access), removes duplicate edges and optionally self loops, and computes
in/out degrees. Produces the mapping and reverse mapping the paper's
"degreer" emits, plus the pre-shard (id-space edge list) consumed by the
sharder in :mod:`repro.core.dsss`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.trace import NO_SPAN, TRACER

__all__ = [
    "EdgeList",
    "degree_and_densify",
    "merge_unique_ids",
    "map_to_dense",
]


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """Pre-shard: dense-id edge list plus degree metadata.

    Attributes:
      src, dst:   int32 dense vertex ids, deduplicated.
      n:          number of (non-isolated) vertices. Ids are ``[0, n)``.
      out_degree: int32 ``(n,)`` out-degree per id.
      in_degree:  int32 ``(n,)`` in-degree per id.
      id_to_index: int64 ``(n,)`` reverse mapping (dense id -> raw index).
      weights:    optional float32 per-edge weights (aligned with src/dst).
    """

    src: np.ndarray
    dst: np.ndarray
    n: int
    out_degree: np.ndarray
    in_degree: np.ndarray
    id_to_index: np.ndarray
    weights: np.ndarray | None = None

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    def index_to_id(self, indices: np.ndarray) -> np.ndarray:
        """Raw index -> dense id (vectorised binary search on the mapping)."""
        pos = np.searchsorted(self.id_to_index, indices)
        pos = np.clip(pos, 0, len(self.id_to_index) - 1)
        ok = self.id_to_index[pos] == indices
        if not np.all(ok):
            raise KeyError("index not present in graph (isolated or unknown)")
        return pos.astype(np.int32)

    def reversed(self) -> "EdgeList":
        """Transpose graph (used by SCC's backward phase)."""
        return EdgeList(
            src=self.dst,
            dst=self.src,
            n=self.n,
            out_degree=self.in_degree,
            in_degree=self.out_degree,
            id_to_index=self.id_to_index,
            weights=self.weights,
        )

    def symmetrized(self) -> "EdgeList":
        """Undirected view: both edge directions (used by WCC).

        One fused dedup + degree pass: the sorted unique ``src·n + dst``
        keys *are* the deduplicated edge list (key // n, key % n), so the
        endpoints are decoded straight from them instead of re-gathering
        the doubled edge buffers, and — because the deduplicated
        symmetrized set is closed under transposition — a single bincount
        yields both degrees (out ≡ in). The old code paid two O(2m)
        fancy-indexed gathers plus two bincounts after already computing
        the keep set.
        """
        src = np.concatenate([self.src, self.dst])
        dst = np.concatenate([self.dst, self.src])
        key = src.astype(np.int64) * self.n + dst
        if self.weights is None:
            uniq = np.unique(key)
            w2 = None
        else:
            w = np.concatenate([self.weights] * 2)
            uniq, keep = np.unique(key, return_index=True)
            w2 = w[keep]
        src2 = (uniq // self.n).astype(np.int32)
        dst2 = (uniq % self.n).astype(np.int32)
        deg = np.bincount(src2, minlength=self.n).astype(np.int32)
        return EdgeList(
            src=src2,
            dst=dst2,
            n=self.n,
            out_degree=deg,
            in_degree=deg,  # symmetric set: in-degree == out-degree exactly
            id_to_index=self.id_to_index,
            weights=w2,
        )


def merge_unique_ids(acc: np.ndarray, *chunks: np.ndarray) -> np.ndarray:
    """Fold edge-chunk endpoints into a sorted unique id array.

    The chunked (external-memory) counterpart of ``np.unique`` over all
    endpoints in :func:`degree_and_densify`: calling this per streamed
    chunk accumulates exactly the dense-id mapping the one-shot pass
    computes, with peak memory O(vertices + chunk), never O(edges).
    """
    parts = [acc] + [np.asarray(c, dtype=np.int64).reshape(-1) for c in chunks]
    return np.unique(np.concatenate(parts))


def map_to_dense(id_to_index: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Raw indices -> dense ids against a sorted mapping (validated).

    Same contract as :meth:`EdgeList.index_to_id` but as a free function
    over an explicit mapping array, so the streaming build pipeline can
    map chunks before the :class:`EdgeList` exists.
    """
    values = np.asarray(values, dtype=np.int64)
    pos = np.searchsorted(id_to_index, values)
    pos = np.clip(pos, 0, max(len(id_to_index) - 1, 0))
    if len(id_to_index) == 0 or not np.all(id_to_index[pos] == values):
        raise KeyError("index not present in the accumulated id mapping")
    return pos.astype(np.int32)


def degree_and_densify(
    src: np.ndarray,
    dst: np.ndarray,
    weights: np.ndarray | None = None,
    *,
    drop_self_loops: bool = False,
    dedup: bool = True,
) -> EdgeList:
    """The degreeing pass: raw sparse indices -> dense contiguous ids.

    Vertices with no incident edge are eliminated (the paper's vertex counts
    exclude isolated vertices — Table III footnote).

    Traced as ``preprocess.densify`` with the sub-spans
    ``densify.unique_ids``, ``densify.dedup`` and ``densify.degrees``.
    """
    tracing = TRACER.enabled
    with TRACER.span("preprocess.densify") if tracing else NO_SPAN:
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if src.shape != dst.shape:
            raise ValueError(
                f"src/dst shape mismatch: {src.shape} vs {dst.shape}"
            )
        if drop_self_loops:
            keep = src != dst
            src, dst = src[keep], dst[keep]
            if weights is not None:
                weights = weights[keep]
        # Dense id assignment over the union of endpoints, sorted by raw
        # index so that the mapping is monotone (searchsorted-able reverse
        # mapping).
        with TRACER.span("densify.unique_ids") if tracing else NO_SPAN:
            id_to_index, inverse = np.unique(
                np.concatenate([src, dst]), return_inverse=True
            )
        m = src.shape[0]
        src_id = inverse[:m].astype(np.int32)
        dst_id = inverse[m:].astype(np.int32)
        n = int(id_to_index.shape[0])
        if dedup:
            with TRACER.span("densify.dedup") if tracing else NO_SPAN:
                key = src_id.astype(np.int64) * n + dst_id
                _, keep_idx = np.unique(key, return_index=True)
                src_id, dst_id = src_id[keep_idx], dst_id[keep_idx]
                if weights is not None:
                    weights = weights[keep_idx]
        with TRACER.span("densify.degrees") if tracing else NO_SPAN:
            out_deg = np.bincount(src_id, minlength=n).astype(np.int32)
            in_deg = np.bincount(dst_id, minlength=n).astype(np.int32)
        return EdgeList(
            src=src_id,
            dst=dst_id,
            n=n,
            out_degree=out_deg,
            in_degree=in_deg,
            id_to_index=id_to_index,
            weights=None if weights is None else weights.astype(np.float32),
        )
