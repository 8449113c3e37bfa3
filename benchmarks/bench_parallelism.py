"""Paper Fig 10 (thread sweep) — TPU analogue: device-grid sweep.

Two parts, kept apart in the row names:

* ``grid_RxC_<platform>`` rows run in this process on ``jax.devices()``:
  every grid the devices present can hold (1x1 on one chip; up to 2x2 on a
  four-chip host). These are the only rows that time real devices.
* ``cpu_model_grid_RxC`` rows come from child processes pinned to
  ``JAX_PLATFORMS=cpu`` with forced host devices. They only model a larger
  fake device grid (the work/collective split per grid) and never touch an
  accelerator — a chip belongs to one process, which here is the parent.
  Their times are CPU times.
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
from jax.sharding import Mesh

from benchmarks._util import row
from repro.core.distributed import distributed_pagerank
from repro.graph.generators import rmat
from repro.graph.preprocess import degree_and_densify

GRIDS = [(1, 1), (2, 1), (2, 2), (4, 2)]
ITERS = 3

_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import jax, numpy as np
from repro.graph.generators import rmat
from repro.graph.preprocess import degree_and_densify
from repro.core.distributed import distributed_pagerank
R, C = int(sys.argv[2]), int(sys.argv[3])
src, dst = rmat(13, edge_factor=8, seed=1)
el = degree_and_densify(src, dst, drop_self_loops=True)
mesh = jax.make_mesh((R, C), ("data", "model"))
t0 = time.time(); ranks, it = distributed_pagerank(el, mesh, iters=3); dt = (time.time()-t0)/3
print(json.dumps({"sec_per_iter": dt, "m": int(el.m)}))
"""


def _device_rows():
    devices = jax.devices()
    el = degree_and_densify(*rmat(13, edge_factor=8, seed=1), drop_self_loops=True)
    rows = []
    for r, c in GRIDS:
        if r * c > len(devices):
            continue
        mesh = Mesh(np.array(devices[: r * c]).reshape(r, c), ("data", "model"))
        t0 = time.time()
        distributed_pagerank(el, mesh, iters=ITERS)
        dt = (time.time() - t0) / ITERS
        name = f"grid_{r}x{c}_{devices[0].platform}"
        rows.append(row(name, dt, f"MTEPS={el.m / dt / 1e6:.1f}"))
    return rows


def _cpu_model_rows():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ, PYTHONPATH=os.path.join(here, "src"), JAX_PLATFORMS="cpu"
    )
    rows = []
    for r, c in GRIDS:
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, str(r * c), str(r), str(c)],
            capture_output=True,
            text=True,
            env=env,
            timeout=600,
            check=True,
        )
        d = json.loads(out.stdout.strip().splitlines()[-1])
        mteps = d["m"] / d["sec_per_iter"] / 1e6
        rows.append(row(f"cpu_model_grid_{r}x{c}", d["sec_per_iter"], f"MTEPS={mteps:.1f}"))
    return rows


def run():
    return _device_rows() + _cpu_model_rows()


def main():
    print("\n".join(run()))


if __name__ == "__main__":
    main()
