"""Jit'd public wrappers around the Pallas kernels.

``subshard_update`` is the full DSSS sub-shard update: the Pallas kernel
produces per-edge-block windowed hub partials, and a cheap slot-scatter
(the FromHub fold, O(unique destinations) ≪ O(edges)) turns them into the
destination-interval update. ``attention`` dispatches between the Pallas
flash kernel and the jnp reference by flag (models use this entry point).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.identities import padding_identity_value
from repro.kernels import ref as _ref
from repro.kernels.dsss_spmv import E_BLK, default_interpret, dsss_spmv_block_partials
from repro.kernels.flash_attention import flash_attention

__all__ = [
    "subshard_update",
    "attention",
    "prepare_subshard_operands",
    "prepare_from_subshard",
    "prepare_from_host_block",
    "prepare_from_packed_tile",
    "prepare_packed_tiles",
    "default_interpret",
    "E_BLK",
]


def prepare_subshard_operands(
    src_local: np.ndarray,
    hub_inv_global: np.ndarray,
    weights: np.ndarray | None,
    dtype,
    *,
    gather_op: str,
    reduce: str,
):
    """Host-side staging: pad edge arrays to E_BLK and compute block bases.

    Padded edges carry identity weights so they contribute the ⊕-identity:
    for ``mul``/sum  w=0 → contrib 0; for ``add``/min w=+inf → contrib inf.

    Supported (gather_op, reduce) pairs: ("mul","sum") — PageRank-family;
    ("add","min"/"max") — BFS/SSSP/WCC/label-propagation. "mul" with
    min/max has no finite multiplicative padding identity and no user.
    """
    if gather_op == "mul" and reduce != "sum":
        raise ValueError("gather_op='mul' requires reduce='sum'")
    e = len(src_local)
    e_pad = max(E_BLK, -(-e // E_BLK) * E_BLK)
    pad = e_pad - e
    ident_w = (
        padding_identity_value(reduce, jnp.dtype(dtype))
        if gather_op == "add"
        else 0.0
    )
    src_idx = np.pad(src_local, (0, pad))
    hub_inv = np.pad(
        hub_inv_global, (0, pad), constant_values=hub_inv_global[-1] if e else 0
    )
    # Build the padded weight buffer directly in the kernel dtype — no wide
    # intermediate (a float64 staging copy doubles transient memory on
    # large sub-shards for no precision gain: the values are cast anyway).
    w = np.empty(e_pad, np.dtype(jnp.dtype(dtype)))
    if weights is None:
        w[:e] = 1.0 if gather_op == "mul" else 0.0
    else:
        w[:e] = np.asarray(weights, w.dtype)
    w[e:] = ident_w
    block_base = hub_inv[::E_BLK].astype(np.int32)
    return (
        jnp.asarray(src_idx, jnp.int32),
        jnp.asarray(hub_inv, jnp.int32),
        jnp.asarray(w),
        jnp.asarray(block_base, jnp.int32),
    )


def prepare_from_subshard(ss, dtype, *, gather_op: str, reduce: str):
    """Stage kernel operands straight from a :class:`repro.core.dsss.SubShard`.

    The session hookup: ``GraphSession.kernel_operands(i, j, ...)`` caches
    the result per (sub-shard, semiring), so the TPU kernel path shares the
    stage-once lifecycle of the jnp block primitives.
    """
    return prepare_subshard_operands(
        ss.src_local, ss.hub_inv, ss.weights, dtype,
        gather_op=gather_op, reduce=reduce,
    )


def prepare_from_host_block(blk: dict, dtype, *, gather_op: str, reduce: str):
    """Stage kernel operands from a padded host block (the session's
    'shard file' dict from :meth:`repro.core.dsss.DSSSGraph.host_blocks`).

    The host buffers are bucket-padded for the jnp block primitives; the
    Pallas kernel pads to ``E_BLK`` with its own identity semantics, so we
    hand it the unpadded ``e``-edge prefix views (zero-copy slices).
    """
    e = blk["e"]
    return prepare_subshard_operands(
        blk["src_local"][:e],
        blk["hub_inv"][:e],
        None if blk["weights"] is None else blk["weights"][:e],
        dtype,
        gather_op=gather_op,
        reduce=reduce,
    )


def subshard_update(
    src_vals: jax.Array,  # (isize,)
    src_idx: jax.Array,  # (E_pad,) from prepare_subshard_operands
    hub_inv: jax.Array,
    weights: jax.Array,
    block_base: jax.Array,
    num_slots: int,
    *,
    gather_op: str = "mul",
    reduce: str = "sum",
    interpret: bool | None = None,
) -> jax.Array:
    """Full sub-shard ToHub on the Pallas kernel; returns (num_slots,) hub.

    ``interpret=None`` auto-selects: compiled on TPU, interpreted on CPU
    (see :func:`repro.kernels.dsss_spmv.default_interpret`).
    """
    if interpret is None:
        interpret = default_interpret()
    return _subshard_update_jit(
        src_vals, src_idx, hub_inv, weights, block_base, num_slots,
        gather_op=gather_op, reduce=reduce, interpret=interpret,
    )


def prepare_from_packed_tile(packed, t: int, dtype, *, gather_op: str, reduce: str):
    """Stage kernel operands from one destination-aligned packed tile.

    A :class:`repro.core.dsss.PackedSweep` tile is a valid kernel edge
    stream by construction: its global hub slots (``base_slot +
    run_local``) are non-decreasing along the tile, so the windowed
    one-hot reduce of ``dsss_spmv`` applies unchanged. Tile source
    indices are *global* padded vertex ids — pass the flat ``(n_pad,)``
    attribute array as ``src_vals`` (the tile does not belong to a single
    source interval once sub-shards coalesce).
    """
    e = int(packed.e_valid[t])
    hub_inv_global = (
        packed.base_slot[t] + packed.run_local[t, :e].astype(np.int64)
    )
    # The windowed one-hot reduce is only sound over a non-decreasing slot
    # stream — true for every adaptive tile and for dst-sorted subshard
    # tiles, but NOT for a src_sorted graph's scrambled blocks.
    if e and np.any(np.diff(hub_inv_global) < 0):
        raise ValueError(
            f"tile {t} has decreasing hub slots (src_sorted layout?) — "
            "not a valid windowed kernel stream"
        )
    w = None if packed.weights is None else packed.weights[t, :e]
    return prepare_subshard_operands(
        packed.src[t, :e], hub_inv_global, w, dtype,
        gather_op=gather_op, reduce=reduce,
    )


def prepare_packed_tiles(packed, *, has_weights: bool) -> dict:
    """Stage the full tile-packed sweep layout as device operand leaves.

    The scan path (``core/session.py::_packed_sweep_impl``) carries these
    leaves through ``lax.scan`` over their leading (NT,) tile axis.
    Per-tile metadata (``base_slot``/``u``/``row_offset``/intervals) stays
    host-side on the :class:`~repro.core.dsss.PackedSweep` for meter
    accounting and stream planning. ``run_end`` is staged only where the
    packing admits the run fold, which the scan reads from its presence.
    """
    tiles = {
        "src": jnp.asarray(packed.src),
        "dst": jnp.asarray(packed.dst),
        "run_local": jnp.asarray(packed.run_local),
        "run_dst": jnp.asarray(packed.run_dst),
        "e_valid": jnp.asarray(packed.e_valid),
    }
    if has_weights:
        tiles["weights"] = jnp.asarray(packed.weights)
    if packed.run_end is not None:
        tiles["run_end"] = jnp.asarray(packed.run_end)
    return tiles


@functools.partial(
    jax.jit, static_argnames=("num_slots", "gather_op", "reduce", "interpret")
)
def _subshard_update_jit(
    src_vals: jax.Array,
    src_idx: jax.Array,
    hub_inv: jax.Array,
    weights: jax.Array,
    block_base: jax.Array,
    num_slots: int,
    *,
    gather_op: str,
    reduce: str,
    interpret: bool,
) -> jax.Array:
    partials = dsss_spmv_block_partials(
        src_vals,
        src_idx,
        hub_inv,
        weights,
        block_base,
        gather_op=gather_op,
        reduce=reduce,
        interpret=interpret,
    )  # (num_blocks, W)
    nb, w = partials.shape
    # Slot-scatter: partial row b covers slots [base_b, base_b + W); fold all
    # rows into the hub vector. O(num_blocks · W) ≪ O(edges) when d > 1.
    slot_ids = (block_base[:, None] + jnp.arange(w)[None, :]).reshape(-1)
    flat = partials.reshape(-1)
    if reduce == "sum":
        return jax.ops.segment_sum(flat, slot_ids, num_segments=num_slots)
    if reduce == "min":
        return jax.ops.segment_min(flat, slot_ids, num_segments=num_slots)
    return jax.ops.segment_max(flat, slot_ids, num_segments=num_slots)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    use_kernel: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Model-facing attention entry point.

    ``use_kernel=False`` (default on this CPU container) runs the jnp
    reference; ``use_kernel=True`` runs the Pallas flash kernel.
    ``interpret=None`` auto-selects (compiled on TPU, interpreted on
    CPU, where it validates the kernel).
    """
    if use_kernel:
        if interpret is None:
            interpret = default_interpret()
        return flash_attention(
            q,
            k,
            v,
            causal=causal,
            window=window,
            softcap=softcap,
            scale=scale,
            interpret=interpret,
        )
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
    )
