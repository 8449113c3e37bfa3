"""Compiles of the main path for a described (not attached) TPU v5e.

The TPU compiler is installed even where no chip is: it compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse
(unaligned blocks, too much VMEM, programs larger than HBM). Nothing runs.
The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several pytest workers
only the worker given this file may.

* The XLA scan sweep, its selective variant and the batched apply at the
  ``chip_smoke.py`` size (n ≈ 4.85 M, ~67 M edge slots): each fits HBM.
* ``execution="auto"`` resolves to the scan, and only the CPU backend
  interprets Pallas kernels.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import BFS, PageRank
from repro.core import session as session_mod
from repro.kernels.dsss_spmv import default_interpret

HBM_BYTES = 16 * 10**9  # one TPU v5e chip (Google Cloud "TPU v5e" docs)

# chip_smoke.py scale: LiveJournal's vertex count (paper Table III) over
# P = 16 intervals, and 8192 tiles of 8192 edge slots (~67 M).
SMOKE_P = 16
SMOKE_N_PAD = 4_850_000
SMOKE_T = 8192
SMOKE_NT = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pagerank_aux(n_pad, sharding, K=None):
    lead = () if K is None else (K,)
    return {
        "inv_out_degree": _sds((n_pad,), jnp.float32, sharding),
        "dangling": _sds((n_pad,), jnp.float32, sharding),
        "inv_n": _sds(lead, jnp.float32, sharding),
    }


def _tiles(NT, T, sharding):
    tiles = {
        k: _sds((NT, T), jnp.int32, sharding)
        for k in ("src", "dst", "run_local", "run_dst")
    }
    tiles["e_valid"] = _sds((NT,), jnp.int32, sharding)
    return tiles


def _case(name, sharding, n_pad):
    if name == "pagerank":
        return PageRank(), jnp.float32, _pagerank_aux(n_pad, sharding)
    return BFS(), jnp.int32, {}


def _fits_hbm(compiled):
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert 0 < total < HBM_BYTES, total
    return total


@pytest.mark.parametrize(
    "program,K", [("pagerank", 1), ("bfs", 1), ("bfs", 16)]
)
def test_scan_sweep_and_apply_fit_hbm_at_smoke_size(one_chip, program, K):
    prog, dtype, aux = _case(program, one_chip, SMOKE_N_PAD)
    sweep, apply_all = session_mod._packed_jits(True)
    select = session_mod._packed_select_jits(True)
    flat = _sds((K, SMOKE_N_PAD), dtype, one_chip)
    tiles = _tiles(SMOKE_NT, SMOKE_T, one_chip)
    row_active = _sds((SMOKE_P,), jnp.bool_, one_chip)
    static = dict(has_weights=False, aux_batched=False)

    _fits_hbm(
        sweep.lower(prog, flat, flat, aux, tiles, row_active, **static)
        .compile()
    )
    bucket = SMOKE_NT // 4
    _fits_hbm(
        select.lower(
            prog, flat, flat, aux, tiles,
            _sds((bucket,), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip),
            row_active, **static,
        ).compile()
    )
    isz = SMOKE_N_PAD // SMOKE_P
    rows = _sds((K, SMOKE_P, isz), dtype, one_chip)
    globals_ = (
        {"dangling_mass": _sds((K,), jnp.float32, one_chip)}
        if program == "pagerank" else {}
    )
    _fits_hbm(
        apply_all.lower(
            prog, rows, rows, aux, globals_,
            _sds((SMOKE_P, isz), jnp.bool_, one_chip),
            _sds((), jnp.float32, one_chip),
            aux_batched=False,
        ).compile()
    )


def test_auto_rule_and_interpret_resolution(topo, monkeypatch):
    """auto resolves to the scan on the TPU; only CPU interprets Pallas."""
    from repro.core import ExecutionPlan, GraphSession, build_dsss
    from repro.graph.generators import erdos_renyi
    from repro.graph.preprocess import degree_and_densify

    assert topo.devices[0].platform == "tpu"
    sess = GraphSession(
        build_dsss(degree_and_densify(*erdos_renyi(60, 300, seed=0)), 4)
    )
    assert default_interpret() is True  # the CPU backend interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    for residency in ("device", "host"):
        assert sess.resolved_execution("spu", residency) == "packed"
    assert sess.compile(ExecutionPlan(PageRank())).execution == "packed"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        default_interpret()
