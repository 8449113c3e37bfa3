"""Pallas kernels — the compiled substrate of the reproduction.

The engine's update sweep runs as the XLA scan (`execution="packed"`,
`core/session.py`) on every platform; no kernel here is on its path.

- dsss_spmv.py: the single-sub-shard ToHub update as an MXU one-hot
  windowed segment reduction (building block / standalone kernel).
- flash_attention.py: tiled online-softmax attention for the LM wing
  (causal / sliding-window / softcap / GQA-via-index_map).
- ops.py: jit'd wrappers and host-side operand staging (including the
  tile upload the scan carries); ref.py: pure-jnp oracles every kernel is
  swept against.

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

__all__ = [
    "attention",
    "prepare_packed_tiles",
    "prepare_subshard_operands",
    "subshard_update",
]
