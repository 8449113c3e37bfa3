"""Pallas kernels — the compiled substrate of the reproduction.

The engine's update sweep runs as the XLA scan (`execution="packed"`)
on every platform. The fused sweep kernel (`execution="packed_kernel"`)
does not yet lower for TPU (see `packed_sweep.py`); it runs in interpret
mode on CPU, where the parity suites hold it bitwise to the scan.

- packed_sweep.py: the fused gather→combine→windowed-run-reduce→
  hub-scatter sweep over `PackedSweep` tiles — one `pallas_call` per
  update sweep, gridded over (query, tile) with BlockSpec-pipelined
  HBM→VMEM tile DMA; bit-identical to the scan path by exact fold-order
  reproduction. Selected only by an explicit `execution="packed_kernel"`
  on CPU.
- dsss_spmv.py: the single-sub-shard ToHub update as an MXU one-hot
  windowed segment reduction (building block / standalone kernel).
- flash_attention.py: tiled online-softmax attention for the LM wing
  (causal / sliding-window / softcap / GQA-via-index_map).
- ops.py: jit'd wrappers and host-side operand staging; ref.py:
  pure-jnp oracles every kernel is swept against.

Every kernel resolves `interpret=None` through
`dsss_spmv.default_interpret()`: compiled on TPU, interpreted on CPU,
refused on any other backend.
"""
from repro.kernels.ops import (
    attention,
    prepare_packed_tiles,
    prepare_subshard_operands,
    subshard_update,
)
from repro.kernels.packed_sweep import (
    packed_sweep_update,
    packed_sweep_update_select,
)

__all__ = [
    "attention",
    "prepare_packed_tiles",
    "prepare_subshard_operands",
    "subshard_update",
    "packed_sweep_update",
    "packed_sweep_update_select",
]
