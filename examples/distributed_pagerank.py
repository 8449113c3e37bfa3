"""Multi-device NXgraph: the DSSS grid on a (data × model) mesh.

The mesh is built from the devices present: a 2x2 grid on a four-chip TPU
host, 1x1 on one chip. To try it without chips, give the CPU backend
virtual devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_pagerank.py
"""
import jax
import numpy as np
from jax.sharding import Mesh

from repro import compile_cache
from repro.core import NXGraphEngine, PageRank, build_dsss
from repro.core.distributed import distributed_pagerank
from repro.graph.generators import rmat
from repro.graph.preprocess import degree_and_densify


def grid_shape(num_devices: int) -> tuple[int, int]:
    """The most nearly square (R, C) with R·C = num_devices and R ≥ C."""
    c = int(np.sqrt(num_devices))
    while num_devices % c:
        c -= 1
    return num_devices // c, c


def main():
    compile_cache.enable()
    devices = jax.devices()
    R, C = grid_shape(len(devices))
    mesh = Mesh(np.array(devices).reshape(R, C), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} on {len(devices)} "
          f"{devices[0].platform} device(s) — sub-shard grid {R}x{C}")
    src, dst = rmat(12, edge_factor=8, seed=3)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    ranks, iters = distributed_pagerank(el, mesh, iters=15)
    ref = NXGraphEngine(build_dsss(el, 4), PageRank(), strategy="fused").run(
        15, tol=0.0
    )
    err = float(np.abs(ranks - ref.attrs).max())
    print(f"n={el.n} m={el.m} iters={iters} max|Δ| vs single-device = {err:.2e}")
    assert err < 1e-6


if __name__ == "__main__":
    main()
