"""Fused Pallas kernel for the whole packed update sweep (the compiled path).

This is the tile-native big brother of ``dsss_spmv.py``: instead of one
windowed ToHub per sub-shard plus an XLA slot-scatter outside, one
``pallas_call`` consumes the :class:`repro.core.dsss.PackedSweep` layout
end to end —

  grid = (K, NT)                 query-major, tiles innermost
  HBM ──BlockSpec DMA──▶ VMEM:   per-tile src / dst / run_local / run_dst /
                                 e_valid / weights blocks (Pallas pipelines
                                 grid-mapped inputs, so tile t+1's DMA is in
                                 flight while tile t computes — the
                                 double-buffered streaming the DSSS layout
                                 was designed for)
  VMEM resident per query:       flat (n_pad,) attributes, aux leaves, the
                                 per-vertex activity mask, and the running
                                 ⊕-accumulator (an output block revisited
                                 across all NT tile steps, flushed once)
  per tile:  gather → combine (``program.gather``, traced into the kernel)
             → windowed run-reduce over the ``run_local`` hub-slot window
             → FromHub scatter of run partials into the accumulator at
               ``run_dst``

Fold-order contract (the acceptance gate of the ``packed_kernel``
execution backend): results must equal
:func:`repro.kernels.ref.packed_sweep_update_ref` *bitwise*, the
run-partial fold of the per-block executor. Against the XLA scan
(``_packed_sweep_impl``, ``core/session.py``), which scatters each edge
straight into the accumulator at its ``dst``, min/max results are
bitwise equal and float sums agree to float32 rounding (a reassociation
of a destination's in-edges). The reference pins the floating-point
fold order exactly:

* the per-run partial must be the **ascending-edge-order** left fold —
  what XLA's in-order scatter-add gives ``jax.ops.segment_sum``. A one-hot
  MXU matmul (the ``dsss_spmv`` sum path) re-associates the adds, so the
  sum path here is a sequential ``fori_loop`` over the tile's edges, each
  step a vectorized (T,) select-accumulate. min/max re-association is
  exact, so those reduce with the chunked masked compare (VPU-shaped, same
  idiom as ``dsss_spmv``), initialized with the *segment-op* fill value
  (:func:`repro.core.identities.segment_fill_value` — bitwise what empty
  segments hold in the reference).
* the FromHub fold must apply run partials in **ascending run order**
  (ascending source-interval order — the schedules' fold order). Grid
  steps are sequential and the scatter loop walks slots 0..T-1, so the
  order is exact by construction; padded run slots (``run_dst == n_pad``)
  leave the accumulator bit-untouched via a read-select-write (an
  unconditional ``acc + 0.0`` would flip ``-0.0`` to ``+0.0``).

Masking mirrors the scan path: edges past ``e_valid`` and edges whose
source vertex is inactive this sweep contribute exact ⊕-identities.

VMEM budget: per query the kernel keeps ``attrs + acc + activity + aux``
resident plus the output block, each double-buffered by the pipeline —
:func:`resident_vmem_bytes`. That is the paper's own fused-tier
assumption (intervals sized to fit fast memory); larger graphs belong to
the scan path. :func:`kernel_fits_vmem` judges the fit against the
device's VMEM (:data:`VMEM_BYTES`), and a compiled call asks the compiler
for exactly that footprint plus headroom as its scoped VMEM limit.

TPU lowering status: every BlockSpec meets the TPU tiling rule (each
operand carries a unit middle axis, so its ``(1, L)`` block equals the
array's last two dims), but Mosaic still refuses the kernel body — see
:data:`TPU_LOWERING_BLOCKER`. ``execution="auto"`` therefore never picks
this kernel (once it lowers, ``auto`` is to pick it on TPU exactly where
:func:`kernel_fits_vmem` holds), and an explicit ``"packed_kernel"`` on a
TPU backend raises (``GraphSession.resolved_execution``). ``interpret=None`` resolves via
:func:`repro.kernels.dsss_spmv.default_interpret` — interpreted on CPU,
where the parity suites run it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.identities import reduce_identity, segment_fill_value
from repro.kernels.dsss_spmv import MINMAX_CHUNK, default_interpret

__all__ = [
    "VMEM_BYTES",
    "kernel_fits_vmem",
    "packed_sweep_update",
    "packed_sweep_update_select",
    "resident_vmem_bytes",
]

# VMEM per TensorCore, keyed by ``jax.Device.device_kind``. A kind missing
# here is an error, never a default: the kernel's fit cannot be judged.
VMEM_BYTES = {
    "TPU v5 lite": 128 * 2**20,  # v5e
}
# Left to Mosaic's internal scratch on top of the blocks' footprint.
_VMEM_HEADROOM = 4 * 2**20

TPU_LOWERING_BLOCKER = (
    "the Pallas TPU lowering refuses the fused sweep kernel: its per-edge "
    "gathers (jnp.take of an n_pad-wide VMEM vector at T source ids) hit "
    "'Only 2D gather is supported' (Mosaic lowers only same-shape "
    "take_along_axis gathers), and with the gathers stubbed out its "
    "value-level dynamic indexing (the per-edge sum fold, the min/max "
    "chunk slices and the per-slot hub scatter) hits 'Unimplemented "
    "primitive ... dynamic_slice'"
)


def _combine(reduce: str, a, b):
    if reduce == "sum":
        return a + b
    if reduce == "min":
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _kernel(
    attrs_ref,  # (1, n_pad)  query's previous attributes (resident)
    acc_in_ref,  # (1, n_pad) incoming ⊕-accumulator (streaming carry)
    act_ref,  # (1, n_pad)   int32 per-vertex activity mask (resident)
    *refs,  # aux refs, tile refs, out_ref — split by static aux_spec
    program,
    aux_spec: tuple,  # ((name, kind), ...) kind ∈ {"vertex", "scalar"}
    has_weights: bool,
    n_pad: int,
    T: int,
):
    out_ref = refs[-1]  # (1, n_pad) accumulator, revisited across tiles
    aux_refs = refs[: len(aux_spec)]
    tile_refs = refs[len(aux_spec) : -1]
    src_ref, dst_ref, run_ref, rdst_ref, ev_ref = tile_refs[:5]
    w_ref = tile_refs[5] if has_weights else None

    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():  # first tile of this query: load the carried accumulator
        out_ref[...] = acc_in_ref[...]

    attrs = attrs_ref[0]
    src = src_ref[0]
    dst = dst_ref[0]
    run = run_ref[0]
    rdst = rdst_ref[0]

    # -- gather + combine (the program's per-edge semiring term) ------------
    vals = jnp.take(attrs, src)
    s_aux: dict = {}
    d_aux: dict | None = {} if program.needs_dst_aux else None
    for (name, kind), ref in zip(aux_spec, aux_refs):
        if kind == "vertex":
            arr = ref[0]
            s_aux[name] = jnp.take(arr, src)
            if d_aux is not None:
                d_aux[name] = jnp.take(arr, dst)
        else:
            s_aux[name] = ref[0]
            if d_aux is not None:
                d_aux[name] = ref[0]
    w = w_ref[0] if has_weights else None
    contrib = program.gather(vals, w, s_aux, d_aux)
    ident = reduce_identity(program.reduce, contrib.dtype)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (T,), 0)
    mask = (iota_t < ev_ref[0]) & (jnp.take(act_ref[0], src) > 0)
    contrib = jnp.where(mask, contrib, ident)

    # -- windowed run-reduce over the hub-slot window -----------------------
    fill = segment_fill_value(program.reduce, contrib.dtype)
    if program.reduce == "sum":
        # Ascending-edge-order left fold: bitwise the reference
        # segment_sum (XLA applies scatter-add updates in order). Each
        # step is one vectorized (T,) select-accumulate on the VPU.
        def edge(e, win):
            c = jax.lax.dynamic_index_in_dim(contrib, e, keepdims=False)
            s = jax.lax.dynamic_index_in_dim(run, e, keepdims=False)
            return jnp.where(iota_t == s, win + c, win)

        win = jax.lax.fori_loop(
            0, T, edge, jnp.full((T,), fill, contrib.dtype)
        )
    else:
        # min/max re-association is exact — chunked masked compare
        # (the dsss_spmv VPU idiom). dynamic_slice clamps the last chunk
        # start, so a non-divisible T re-reads a few edges; min/max is
        # idempotent over duplicates, results unchanged.
        chunk = min(MINMAX_CHUNK, T)
        num_chunks = -(-T // chunk)
        iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

        def chunk_body(c, red):
            sl = jax.lax.dynamic_slice_in_dim(run, c * chunk, chunk)
            cb = jax.lax.dynamic_slice_in_dim(contrib, c * chunk, chunk)
            masked = jnp.where(sl[:, None] == iota_w, cb[:, None], fill)
            part = (
                jnp.min(masked, axis=0)
                if program.reduce == "min"
                else jnp.max(masked, axis=0)
            )
            return _combine(program.reduce, red, part)

        win = jax.lax.fori_loop(
            0, num_chunks, chunk_body, jnp.full((T,), fill, contrib.dtype)
        )

    # -- FromHub: fold run partials into the accumulator at run_dst ---------
    # Sequential over slots 0..T-1 == ascending run order == the
    # schedules' ascending-source-interval fold order (bit-identity with
    # acc.at[run_dst].add/min/max, which serializes duplicates in order).
    acc_dtype = out_ref.dtype

    def run_fold(r, carry):
        idx = jax.lax.dynamic_index_in_dim(rdst, r, keepdims=False)
        valid = idx < n_pad  # padded slots carry the n_pad sentinel
        i = jnp.minimum(idx, n_pad - 1)
        v = jax.lax.dynamic_index_in_dim(win, r, keepdims=False)
        cur = out_ref[pl.ds(0, 1), pl.ds(i, 1)]
        upd = _combine(program.reduce, cur, v.astype(acc_dtype))
        out_ref[pl.ds(0, 1), pl.ds(i, 1)] = jnp.where(valid, upd, cur)
        return carry

    jax.lax.fori_loop(0, T, run_fold, 0)


def _normalize_aux(aux: dict, aux_batched: bool, K: int):
    """Flatten the aux dict to uniformly-2D operands + a static spec.

    Mirrors the scan path's per-query view (``v[src] if v.ndim == 1 else
    v``): after stripping the optional leading (K,) batch axis, 1-D
    leaves are per-vertex (gathered by endpoint), 0-D leaves are scalars.
    Each operand becomes (Ka, L) with Ka ∈ {1, K}; the BlockSpec index
    map broadcasts shared leaves across the query grid axis.
    """
    spec = []
    operands = []
    for name in sorted(aux):
        v = jnp.asarray(aux[name])
        per_query_ndim = v.ndim - (1 if aux_batched else 0)
        if per_query_ndim == 1:
            kind = "vertex"
            op = v if aux_batched else v[None, :]
        elif per_query_ndim == 0:
            kind = "scalar"
            op = v[:, None] if aux_batched else v[None, None]
        else:
            raise ValueError(
                f"aux leaf {name!r} has unsupported per-query rank "
                f"{per_query_ndim} for the packed kernel"
            )
        spec.append((name, kind))
        operands.append(op)
    return tuple(spec), operands


def _block_bytes(width: int) -> int:
    """VMEM bytes of one (1, width) 32-bit block: its single row pads to a
    whole 8-sublane × 128-lane tile."""
    return 8 * (-(-width // 128) * 128) * 4


def resident_vmem_bytes(
    n_pad: int, T: int, n_vertex_aux: int, n_scalar_aux: int = 0,
    has_weights: bool = False,
) -> int:
    """VMEM the kernel's blocks hold at one grid step, double-buffered.

    attrs, the incoming accumulator, the activity mask, the output and
    each per-vertex aux leaf are ``(1, n_pad)`` blocks; the tile leaves
    (src, dst, run_local, run_dst, weights) are ``(1, T)``; ``e_valid``
    and each scalar aux leaf are ``(1, 1)``. The grid holds one query's
    blocks at a time, so the batch width K does not enter.
    """
    wide = (4 + n_vertex_aux) * _block_bytes(n_pad)
    narrow = (4 + int(has_weights)) * _block_bytes(T)
    scalars = (1 + n_scalar_aux) * _block_bytes(1)
    return 2 * (wide + narrow + scalars)


def kernel_fits_vmem(
    device_kind: str, n_pad: int, T: int, n_vertex_aux: int,
    n_scalar_aux: int = 0, has_weights: bool = False,
) -> bool:
    """Whether the kernel's resident footprint fits this device's VMEM."""
    if device_kind not in VMEM_BYTES:
        raise ValueError(
            f"no VMEM size is known for device kind {device_kind!r} "
            f"(known: {sorted(VMEM_BYTES)})"
        )
    need = resident_vmem_bytes(n_pad, T, n_vertex_aux, n_scalar_aux, has_weights)
    return need + _VMEM_HEADROOM <= VMEM_BYTES[device_kind]


def packed_sweep_update(
    program,
    attrs_flat: jax.Array,  # (K, n_pad) previous attributes (read-only)
    acc_flat: jax.Array,  # (K, n_pad) running ⊕ accumulators (carry)
    aux: dict,  # run-constant aux; (K,)-leading leaves when aux_batched
    tiles: dict,  # PackedSweep device leaves, (NT, ...) leading axis
    row_active: jax.Array,  # (P,) bool — the sweep's active source intervals
    has_weights: bool,
    aux_batched: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused-kernel gather-reduce pass; drop-in for ``_packed_sweep_impl``.

    Call signature (minus ``interpret``) matches the scan implementation,
    so the session's streaming/selective machinery drives either
    executable unchanged: under host/disk residency ``tiles`` is one
    streamed chunk and ``acc_flat`` the carry between chunks.
    """
    if interpret is None:
        interpret = default_interpret()
    K, n_pad = attrs_flat.shape
    NT, T = tiles["src"].shape
    P = row_active.shape[0]
    vert_active = jnp.broadcast_to(row_active[:, None], (P, n_pad // P))
    vert_active = vert_active.astype(jnp.int32).reshape(1, n_pad)
    aux_spec, aux_ops = _normalize_aux(aux, aux_batched, K)

    # Every operand gets a unit middle axis: a (1, L) block over a (R, 1, L)
    # array has last two dims equal to the array's, which is what the TPU
    # tiling rule asks of blocks that are not (8, 128)-divisible.
    def _rows(op):  # (R, L) -> (R, 1, L)
        return op.reshape(op.shape[0], 1, op.shape[1])

    def _spec(width, per_row):
        return pl.BlockSpec(
            (pl.Squeezed(), 1, width),
            (lambda k, t: (k, 0, 0)) if per_row == "query"
            else (lambda k, t: (t, 0, 0)) if per_row == "tile"
            else (lambda k, t: (0, 0, 0)),
        )

    in_specs = [
        _spec(n_pad, "query"),  # attrs
        _spec(n_pad, "query"),  # acc in
        _spec(n_pad, "shared"),  # activity
        *[
            _spec(op.shape[1], "query" if op.shape[0] == K else "shared")
            for op in aux_ops
        ],
        *[_spec(T, "tile") for _ in range(4)],  # src, dst, run_local, run_dst
        _spec(1, "tile"),  # e_valid
    ]
    operands = [
        attrs_flat,
        acc_flat,
        vert_active,
        *aux_ops,
        tiles["src"],
        tiles["dst"],
        tiles["run_local"],
        tiles["run_dst"],
        tiles["e_valid"][:, None],
    ]
    if has_weights:
        in_specs.append(_spec(T, "tile"))
        operands.append(tiles["weights"])
    compiler_params = None
    if not interpret:
        n_vertex = sum(kind == "vertex" for _, kind in aux_spec)
        vmem = resident_vmem_bytes(
            n_pad, T, n_vertex, len(aux_spec) - n_vertex, has_weights
        )
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=vmem + _VMEM_HEADROOM
        )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            program=program,
            aux_spec=aux_spec,
            has_weights=has_weights,
            n_pad=n_pad,
            T=T,
        ),
        grid=(K, NT),
        in_specs=in_specs,
        out_specs=_spec(n_pad, "query"),
        out_shape=jax.ShapeDtypeStruct((K, 1, n_pad), acc_flat.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(*[_rows(op) for op in operands])
    return out.reshape(K, n_pad)


def packed_sweep_update_select(
    program,
    attrs_flat: jax.Array,  # (K, n_pad)
    acc_flat: jax.Array,  # (K, n_pad)
    aux: dict,
    tiles: dict,  # (NT, ...) staged tile leaves
    idx: jax.Array,  # (bucket,) int32 active tile indices, 0-padded
    a_valid: jax.Array,  # scalar int32: real entries in idx
    row_active: jax.Array,  # (P,) bool
    has_weights: bool,
    aux_batched: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Selective-execution frontend: compact active tiles, then the kernel.

    Same contract as ``_packed_sweep_select_impl``: ``idx`` is ascending
    (fold order preserved), padding entries are neutralized by zeroing
    their ``e_valid`` so every edge masks to an exact ⊕-identity. The
    gather runs as plain XLA ops in front of the ``pallas_call``; the
    kernel grid then walks only the compacted bucket.
    """
    sel = {k: v[idx] for k, v in tiles.items()}
    keep = jnp.arange(idx.shape[0]) < a_valid
    sel["e_valid"] = jnp.where(keep, sel["e_valid"], 0)
    return packed_sweep_update(
        program, attrs_flat, acc_flat, aux, sel, row_active, has_weights,
        aux_batched, interpret,
    )
