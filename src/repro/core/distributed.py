"""Multi-pod NXgraph: the DSSS grid partitioned over a 2-D device mesh.

Mapping (DESIGN.md §2): the sub-shard grid becomes a (source-axis ×
destination-axis) device grid. Device (r, c) owns the edges with source in
row-chunk r and destination in column-chunk c — a device-granular
sub-shard, destination-sorted within. One iteration is:

  ToHub    — local gather + segment-reduce into a column-chunk partial
             (the *hub* is exactly the pre-reduce partial aggregate);
  FromHub  — ``psum`` of hubs over the source axis (this IS the paper's
             column-major hub fold, expressed as a collective);
  Exchange — ``all_gather`` of the new attributes over the destination
             axis, re-sliced to each device's source chunk (the paper's
             interval ping-pong crossing the mesh).

Single-pod: source axis = ("data",); multi-pod: ("pod", "data") — the pod
axis simply extends the source dimension of the grid, so hubs reduce
across pods too (this is what the multi-pod dry-run proves shards).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.graph.preprocess import EdgeList

__all__ = [
    "DeviceBlocks",
    "build_device_blocks",
    "make_pagerank_step",
    "distributed_pagerank",
    "graph_input_specs",
    "GRAPH_SCALES",
]


@dataclasses.dataclass
class DeviceBlocks:
    """Edge blocks stacked per device: (R, C, E_max) arrays."""

    n: int
    n_pad: int
    R: int
    C: int
    src_local: np.ndarray  # (R, C, E) int32, row-chunk-local source ids
    dst_local: np.ndarray  # (R, C, E) int32, column-chunk-local dst ids
    weight: np.ndarray  # (R, C, E) f32: 1/outdeg(src), 0 for padding
    row_chunk: int
    col_chunk: int


def build_device_blocks(el: EdgeList, R: int, C: int) -> DeviceBlocks:
    """Partition (degreed) edges into the R×C device grid, DSSS-sorted."""
    n = el.n
    n_pad = int(np.lcm(R, C) * -(-n // np.lcm(R, C)))
    row_chunk, col_chunk = n_pad // R, n_pad // C
    src, dst = el.src.astype(np.int64), el.dst.astype(np.int64)
    r = src // row_chunk
    c = dst // col_chunk
    order = np.lexsort((src, dst, c, r))  # destination-sorted within block
    src, dst = src[order], dst[order]
    r, c = r[order], c[order]
    block = r * C + c
    counts = np.bincount(block, minlength=R * C)
    e_max = max(int(counts.max()), 1)
    src_l = np.zeros((R * C, e_max), np.int32)
    dst_l = np.zeros((R * C, e_max), np.int32)
    w = np.zeros((R * C, e_max), np.float32)
    deg = el.out_degree.astype(np.float32)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    starts = np.zeros(R * C + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    for b in range(R * C):
        lo, hi = int(starts[b]), int(starts[b + 1])
        e = hi - lo
        src_l[b, :e] = (src[lo:hi] - (b // C) * row_chunk).astype(np.int32)
        dst_l[b, :e] = (dst[lo:hi] - (b % C) * col_chunk).astype(np.int32)
        w[b, :e] = inv[src[lo:hi]]
    return DeviceBlocks(
        n=n,
        n_pad=n_pad,
        R=R,
        C=C,
        src_local=src_l.reshape(R, C, e_max),
        dst_local=dst_l.reshape(R, C, e_max),
        weight=w.reshape(R, C, e_max),
        row_chunk=row_chunk,
        col_chunk=col_chunk,
    )


def make_pagerank_step(
    mesh,
    n: int,
    n_pad: int,
    *,
    src_axes: tuple[str, ...] = ("data",),
    dst_axis: str = "model",
    damping: float = 0.85,
):
    """Jitted one-iteration PageRank on the device grid.

    x, dangling_mask are sharded over the source axes; edge blocks over
    (source axes..., dst axis). Returns (step_fn, in_specs) for reuse by
    both the real runner and the dry-run."""
    R = int(np.prod([mesh.shape[a] for a in src_axes]))
    C = mesh.shape[dst_axis]
    row_chunk, col_chunk = n_pad // R, n_pad // C
    src_spec = P(src_axes if len(src_axes) > 1 else src_axes[0])
    blk_spec = P(src_axes if len(src_axes) > 1 else src_axes[0], dst_axis, None)

    def body(x_blk, dang_blk, src_l, dst_l, w):
        # x_blk: (row_chunk,) local source attributes
        # src_l/dst_l/w: (1, .., 1, E) local edge block
        e = src_l.shape[-1]
        src_ids = src_l.reshape(e)
        dst_ids = dst_l.reshape(e)
        wv = w.reshape(e)
        # -- ToHub: local contributions into the column-chunk partial
        contrib = x_blk[src_ids] * wv
        hub = jax.ops.segment_sum(contrib, dst_ids, num_segments=col_chunk)
        # -- FromHub: fold hubs across the source axis
        y_c = jax.lax.psum(hub, src_axes)  # (col_chunk,), complete
        # -- dangling mass (global scalar)
        dm = jax.lax.psum(jnp.sum(x_blk * dang_blk), src_axes)
        # -- exchange: new attributes back to source-axis sharding
        y_full = jax.lax.all_gather(
            y_c, dst_axis, tiled=True
        )  # (n_pad,) — chunk order == column order
        base = (1.0 - damping) / n
        new_full = base + damping * (y_full + dm / n)
        # padding rows stay zero so they never contribute mass
        valid = jnp.arange(n_pad) < n
        new_full = jnp.where(valid, new_full, 0.0)
        idx = jax.lax.axis_index(src_axes[0])
        for a in src_axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        my = jax.lax.dynamic_slice(new_full, (idx * row_chunk,), (row_chunk,))
        diff = jax.lax.psum(jnp.sum(jnp.abs(my - x_blk)), src_axes + (dst_axis,))
        return my, diff / mesh.shape[dst_axis]

    in_specs = (src_spec, src_spec, blk_spec, blk_spec, blk_spec)
    step = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=(src_spec, P()),
        check_vma=False,
    )
    return jax.jit(step), (src_spec, blk_spec)


def distributed_pagerank(
    el: EdgeList,
    mesh,
    *,
    iters: int = 20,
    damping: float = 0.85,
    src_axes: tuple[str, ...] = ("data",),
    dst_axis: str = "model",
    tol: float = 0.0,
):
    """Run PageRank on the mesh; returns (ranks (n,), iterations)."""
    R = int(np.prod([mesh.shape[a] for a in src_axes]))
    C = mesh.shape[dst_axis]
    blocks = build_device_blocks(el, R, C)
    step, (src_spec, blk_spec) = make_pagerank_step(
        mesh,
        blocks.n,
        blocks.n_pad,
        src_axes=src_axes,
        dst_axis=dst_axis,
        damping=damping,
    )
    x = np.zeros(blocks.n_pad, np.float32)
    x[: blocks.n] = 1.0 / blocks.n
    dang = np.zeros(blocks.n_pad, np.float32)
    dang[: blocks.n] = (el.out_degree == 0).astype(np.float32)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    x = put(x, src_spec)
    dang = put(dang, src_spec)
    src_l = put(blocks.src_local, blk_spec)
    dst_l = put(blocks.dst_local, blk_spec)
    w = put(blocks.weight, blk_spec)
    it = 0
    for it in range(1, iters + 1):
        x, diff = step(x, dang, src_l, dst_l, w)
        if tol and float(diff) < tol:
            break
    return np.asarray(x)[: blocks.n], it


# ---------------------------------------------------------------------------
# Dry-run support: paper-scale graphs as ShapeDtypeStructs (no allocation).
# ---------------------------------------------------------------------------
GRAPH_SCALES = {
    # name: (n, m) from paper Table III
    "live-journal": (4_850_000, 69_000_000),
    "twitter": (41_700_000, 1_470_000_000),
    "yahoo-web": (720_000_000, 6_640_000_000),
}


def graph_input_specs(name: str, mesh, src_axes=("data",), dst_axis="model"):
    """SDS stand-ins for a paper-scale graph on this mesh (dry-run)."""
    n, m = GRAPH_SCALES[name]
    R = int(np.prod([mesh.shape[a] for a in src_axes]))
    C = mesh.shape[dst_axis]
    lcm = int(np.lcm(R, C))
    n_pad = lcm * -(-n // lcm)
    e_max = -(-int(m * 1.10) // (R * C))  # 10% imbalance headroom
    src_spec = P(src_axes if len(src_axes) > 1 else src_axes[0])
    blk_spec = P(src_axes if len(src_axes) > 1 else src_axes[0], dst_axis, None)
    sds = jax.ShapeDtypeStruct
    mk = lambda shape, dt, spec: sds(shape, dt, sharding=NamedSharding(mesh, spec))
    return {
        "n": n,
        "n_pad": n_pad,
        "x": mk((n_pad,), jnp.float32, src_spec),
        "dang": mk((n_pad,), jnp.float32, src_spec),
        "src_l": mk((R, C, e_max), jnp.int32, blk_spec),
        "dst_l": mk((R, C, e_max), jnp.int32, blk_spec),
        "w": mk((R, C, e_max), jnp.float32, blk_spec),
    }


def _selftest():  # pragma: no cover — exercised via subprocess in tests
    import os

    assert os.environ.get("XLA_FLAGS", "").count("device_count"), (
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=N"
    )
    from repro.core import NXGraphEngine, PageRank, build_dsss
    from repro.graph.generators import rmat
    from repro.graph.preprocess import degree_and_densify

    src, dst = rmat(9, edge_factor=8, seed=5)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    ranks, iters = distributed_pagerank(el, mesh, iters=12)
    ref = NXGraphEngine(build_dsss(el, 4), PageRank(), strategy="fused").run(
        12, tol=0.0
    )
    err = float(np.abs(ranks - ref.attrs).max())
    print(f"selftest: n={el.n} m={el.m} iters={iters} max_err={err:.3e}")
    assert err < 1e-6, err
    # multi-source-axis variant (pod axis folded into the source dim)
    mesh3 = jax.make_mesh((2, 1, 2), ("pod", "data", "model"))
    ranks3, _ = distributed_pagerank(
        el, mesh3, iters=12, src_axes=("pod", "data")
    )
    err3 = float(np.abs(ranks3 - ref.attrs).max())
    print(f"selftest multi-pod: max_err={err3:.3e}")
    assert err3 < 1e-6, err3
    print("selftest OK")


if __name__ == "__main__":
    _selftest()
