"""The ⊕-identities of the reduce monoids — single source of truth.

Two *different* identity families exist on purpose, and every consumer must
pick the right one:

* :func:`reduce_identity` — the **algorithmic** identity folded into
  accumulators and masked-off contributions by the engine
  (``core/session.py``) and the vertex programs. For integer min/max it is
  ``±INF_DEPTH`` (2³⁰), the programs' "unreached" sentinel: BFS depths
  saturate at it, so the identity must match what ``apply``/``output``
  compare against.
* :func:`padding_identity` — the **segment-op-compatible** padding value
  used by the Pallas kernel path (``kernels/dsss_spmv.py`` /
  ``kernels/ops.py``). It must equal what ``jax.ops.segment_min`` /
  ``segment_max`` put in *empty* segments (±inf for floats, the integer
  dtype's extrema for ints), because the kernel's windowed partials are
  checked bitwise against those reference reductions.

Before this module each file hand-rolled its own variant and the integer
min/max values had already drifted (``INF_DEPTH`` vs ``iinfo.max``) — which
is correct, but only as long as each stays on its side; keeping both in one
place makes the split explicit and un-driftable.
"""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np

__all__ = [
    "INF_DEPTH",
    "reduce_identity",
    "padding_identity",
    "padding_identity_value",
]

# The programs' saturating "infinite depth / distance" for integer min/max
# attributes (BFS depth, SSSP hop counts). Small enough that `x + 1` never
# overflows int32 during the monotone relaxation.
INF_DEPTH = np.int32(2**30)


def reduce_identity(reduce: str, dtype) -> Any:
    """Algorithmic ⊕-identity (engine accumulators, masked contributions)."""
    if reduce == "sum":
        return jnp.zeros((), dtype)
    if reduce == "min":
        return (
            jnp.array(INF_DEPTH, dtype)
            if jnp.issubdtype(dtype, jnp.integer)
            else jnp.array(jnp.inf, dtype)
        )
    if reduce == "max":
        return (
            jnp.array(-INF_DEPTH, dtype)
            if jnp.issubdtype(dtype, jnp.integer)
            else jnp.array(-jnp.inf, dtype)
        )
    raise ValueError(f"unknown reduce {reduce!r}")


def padding_identity(reduce: str, dtype) -> jnp.ndarray:
    """Segment-op-compatible identity (Pallas kernel padding, jnp scalar).

    Matches ``jax.ops.segment_{sum,min,max}`` empty-segment fill values
    exactly, so identity-padded kernel inputs are bitwise equivalent to the
    reference segment reductions.
    """
    return jnp.asarray(padding_identity_value(reduce, dtype), dtype)


def padding_identity_value(reduce: str, dtype) -> float | int:
    """Python-scalar variant of :func:`padding_identity` for numpy staging."""
    if reduce == "sum":
        return 0.0
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        big: float | int = float("inf")
    else:
        big = int(jnp.iinfo(dtype).max)
    if reduce == "min":
        return big
    if reduce == "max":
        return -big
    raise ValueError(f"unknown reduce {reduce!r}")
