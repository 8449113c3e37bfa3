"""Pallas TPU kernel for the DSSS sub-shard update (ToHub phase).

TPU-native re-expression of the paper's destination-sorted fine-grained
parallelism (§III-D). On CPU, destination sorting removes write conflicts
between threads; on TPU there are no conflicting threads, but the same sort
gives every *edge block* a dense, narrow range of **hub slots** (unique
destinations), so the per-block segment reduction becomes a small dense
``contribution · one_hot`` product that runs on the MXU — a conflict-free,
layout-aligned reduction instead of a serial scatter.

Pipeline per grid step (one edge block of ``E_BLK`` edges):

  HBM ──DMA──▶ VMEM:  src ids, hub slots, weights of the block
  VMEM:               source-interval attributes (resident — the paper's
                      "interval in memory"; SPU keeps it there all iteration)
  gather   contrib[e] = src_vals[src_idx[e]] ⊙ w[e]      (⊙ = mul | add)
  one-hot  oh[e, s]   = (hub_inv[e] − base_b == s)       (iota compare)
  reduce   sum: (1,E)·(E,W) MXU matmul;  min/max: masked VPU reduce
  out      per-block windowed hub partials (num_blocks, W)

The windowed trick is sound *because* edges are destination-sorted: hub
slots are non-decreasing along the edge stream, so a block of ``E_BLK``
edges touches at most ``E_BLK`` consecutive slots (``W = E_BLK``). The
final slot-scatter (FromHub) is O(unique destinations) and lives in
:mod:`repro.kernels.ops`.

Semiring modes:
  gather_op: "mul" (PageRank: rank/deg · w) | "add" (BFS/SSSP: depth + w)
  reduce:    "sum" | "min" | "max"
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.identities import padding_identity

__all__ = [
    "dsss_spmv_block_partials",
    "default_interpret",
    "E_BLK",
    "MINMAX_CHUNK",
]

E_BLK = 512  # edges per block; also the hub-slot window width W

# min/max reduce chunking: the windowed compare materializes
# (MINMAX_CHUNK, W) values at a time instead of (E_BLK, W) — peak VMEM for
# the compare is MINMAX_CHUNK·E_BLK·4 bytes (256 KB at 128×512 fp32) and is
# independent of E_BLK growth along the edge axis. min/max re-association
# is exact, so chunking cannot change results.
MINMAX_CHUNK = 128
assert E_BLK % MINMAX_CHUNK == 0, "chunked min/max reduce needs E_BLK % chunk == 0"


def default_interpret() -> bool:
    """Pallas interpret mode for this backend: compiled on TPU, interpreted
    on CPU, refused elsewhere.

    The kernels target the TPU lowering; the CPU backend runs them in the
    interpreter for the parity suites. Any other backend (a GPU, or a
    platform this package has never run on) raises instead of silently
    interpreting, so a device run can never hide in the interpreter.
    Callers pass ``interpret=None`` to defer to this; an explicit bool
    always wins (e.g. ``interpret=True`` on TPU to debug a kernel).
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here compile for TPU or interpret on CPU; backend "
        f"{backend!r} is neither (pass interpret= explicitly to override)"
    )


def _kernel(
    src_vals_ref,  # (isize,)          resident source-interval attributes
    src_idx_ref,  # (E_BLK,)           edge source offsets within interval
    hub_inv_ref,  # (E_BLK,)           edge -> global hub slot
    w_ref,  # (E_BLK,)                 edge weights (identity-padded)
    base_ref,  # (1,)                  first hub slot of this block
    out_ref,  # (1, W)                 windowed hub partials for this block
    *,
    gather_op: str,
    reduce: str,
):
    contrib_dtype = out_ref.dtype
    vals = jnp.take(src_vals_ref[...], src_idx_ref[...], axis=0)
    w = w_ref[...]
    if gather_op == "mul":
        contrib = (vals * w).astype(contrib_dtype)
    else:
        contrib = (vals + w).astype(contrib_dtype)
    slots = hub_inv_ref[...] - base_ref[0]
    W = out_ref.shape[1]
    if reduce == "sum":
        # One-hot over the slot window. Destination-sorted edges guarantee
        # 0 <= slots < W for all valid edges; identity-padded edges may
        # fall anywhere and contribute the identity.
        # MXU path: (1, E) · (E, W).
        oh = slots[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        out = jnp.dot(
            contrib[None, :], oh.astype(contrib_dtype), preferred_element_type=jnp.float32
        ).astype(contrib_dtype)
        out_ref[...] = out
    else:
        # Windowed segmented reduce for min/max, in chunks of MINMAX_CHUNK
        # edges: the full masked one-hot would materialize O(E_BLK · W)
        # values per block, which scales quadratically with the edge-block
        # size and blows VMEM on BFS/SSSP tiles; the chunked compare keeps
        # peak live values at O(MINMAX_CHUNK · W) while staying VPU-shaped
        # (min/max re-association is exact, so results are unchanged).
        ident = padding_identity(reduce, contrib_dtype)
        iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
        num_chunks = slots.shape[0] // MINMAX_CHUNK

        def chunk(c, red):
            sl = jax.lax.dynamic_slice_in_dim(slots, c * MINMAX_CHUNK, MINMAX_CHUNK)
            cb = jax.lax.dynamic_slice_in_dim(contrib, c * MINMAX_CHUNK, MINMAX_CHUNK)
            masked = jnp.where(sl[:, None] == iota_w, cb[:, None], ident)
            part = (
                jnp.min(masked, axis=0) if reduce == "min" else jnp.max(masked, axis=0)
            )
            return (
                jnp.minimum(red, part) if reduce == "min" else jnp.maximum(red, part)
            )

        red = jax.lax.fori_loop(
            0, num_chunks, chunk, jnp.full((W,), ident, contrib_dtype)
        )
        out_ref[...] = red[None, :]


def dsss_spmv_block_partials(
    src_vals: jax.Array,  # (isize,) float
    src_idx: jax.Array,  # (E_pad,) int32, E_pad % E_BLK == 0
    hub_inv: jax.Array,  # (E_pad,) int32 global hub slots (non-decreasing)
    weights: jax.Array,  # (E_pad,) same dtype as src_vals, identity-padded
    block_base: jax.Array,  # (num_blocks,) int32 = hub_inv[b*E_BLK]
    *,
    gather_op: str = "mul",
    reduce: str = "sum",
    interpret: bool | None = None,
) -> jax.Array:
    """Run the kernel over all edge blocks; returns (num_blocks, W) partials.

    ``interpret=None`` (default) resolves via :func:`default_interpret` —
    compiled on TPU, interpreted elsewhere.
    """
    if interpret is None:
        interpret = default_interpret()
    return _block_partials_jit(
        src_vals, src_idx, hub_inv, weights, block_base,
        gather_op=gather_op, reduce=reduce, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("gather_op", "reduce", "interpret")
)
def _block_partials_jit(
    src_vals, src_idx, hub_inv, weights, block_base,
    *, gather_op: str, reduce: str, interpret: bool,
) -> jax.Array:
    e_pad = src_idx.shape[0]
    assert e_pad % E_BLK == 0, f"pad edges to a multiple of {E_BLK}"
    num_blocks = e_pad // E_BLK
    grid = (num_blocks,)
    return pl.pallas_call(
        functools.partial(_kernel, gather_op=gather_op, reduce=reduce),
        grid=grid,
        in_specs=[
            pl.BlockSpec(src_vals.shape, lambda b: (0,) * src_vals.ndim),
            pl.BlockSpec((E_BLK,), lambda b: (b,)),
            pl.BlockSpec((E_BLK,), lambda b: (b,)),
            pl.BlockSpec((E_BLK,), lambda b: (b,)),
            pl.BlockSpec((1,), lambda b: (b,)),
        ],
        out_specs=pl.BlockSpec((1, E_BLK), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((num_blocks, E_BLK), src_vals.dtype),
        interpret=interpret,
    )(src_vals, src_idx, hub_inv, weights, block_base)
