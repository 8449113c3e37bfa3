"""GraphSession — stage the graph once, run many programs, batch many queries.

NXgraph's core abstraction (paper §II-B) is a graph that *stays put* while
interval/sub-shard schedules stream over it. This module is that abstraction
as an API: a :class:`GraphSession` owns the device-staged DSSS blocks, the
fused edge arrays and the SPU residency sets — built once per graph — and
executes any number of :class:`repro.core.plan.ExecutionPlan` jobs against
them. ``session.run(plan)`` runs one job; ``session.run_batch(plans)`` fuses
K compatible jobs (e.g. 64 BFS sources, a parameter sweep) into a *single*
streamed pass over the edge blocks: attributes carry a leading batch axis
and every block primitive is vmapped over it, so the slow-tier edge traffic
is paid once, not K times.

Execution layout: attributes are held as ``(K, P, interval_size)`` — K
queries × P intervals — and all block primitives batch over the leading
axis (K = 1 for single runs; XLA collapses the unit axis). Byte-meter
accounting under batching: *edge* bytes are charged once per block per
sweep (the streamed pass is shared), while *interval* and *hub* bytes are
charged K× (each query owns its attribute state). ``meters.iterations``
always equals the number of update sweeps executed.

The per-iteration schedules themselves (SPU / DPU / MPU / fused, paper
§III-B) are unchanged from the engine; custom schedules (the TurboGraph-like
baseline) register via :meth:`GraphSession.register_strategy`.

Out-of-core execution (paper §I "streamlined disk access"): the session's
``residency`` axis decides whether sub-shard blocks live on the device
("device"), or stay as pinned host (numpy) buffers that are streamed to the
device per sweep with double-buffered prefetch ("host"), with the resident
set — the blocks the ``memory_budget`` pins in the fast tier — computed by
:meth:`GraphSession._resolve_residency` and *enforced* by
:class:`_BlockFetcher`. Graphs larger than the fast tier run in "host" mode
with device-held topology bounded by the budget (plus a two-block streaming
ring), bit-identical to the device-resident run.

Compiled sweeps (the ``execution`` axis): the paper's headline number is
raw per-iteration speed — its DSSS structure exists so the inner loop is a
streamlined, conflict-free pass over sorted edge blocks. The per-block
executor re-enters Python for every sub-shard (O(P²) jit dispatches per
sweep); with ``execution="packed"`` the session instead stages the
:class:`repro.core.dsss.PackedSweep` tile layout once — destination-
aligned fixed-size tiles cut only at destination-run boundaries, so
padding stays bounded on power-law graphs instead of being dictated by the
largest hub-heavy sub-shard — and runs the entire gather-reduce phase of a
sweep as **one** ``jax.lax.scan`` over the tile axis, one batched
accumulator init, and one batched apply — ~4 dispatches per sweep
regardless of P. Results are bit-identical to the per-block path for all
of SPU/DPU/MPU (see :class:`~repro.core.dsss.PackedSweep` for why the
run-aligned stream order reproduces every schedule's fold order exactly),
and the modelled byte/edge meters are computed from the packed metadata to
be field-for-field identical. Under enforced host residency the packed
path does not downgrade: the tile axis is chunked and streamed
host→device with the same double-buffered prefetch discipline as
:class:`_BlockFetcher` (a budget-pinned tile prefix stays device-resident,
each streamed chunk charges ``bytes_h2d``), so SPU/DPU/MPU all run packed
out-of-core.

Frontier-aware selective execution (the ``activity`` plan axis): monotone
programs (BFS/SSSP/WCC) track the per-sweep interval frontier — the
``changed`` output of the previous sweep — and, under ``activity="auto"``
(the default), skip everything that frontier cannot touch: inactive source
intervals on the per-block path, inactive tiles in the packed scan (a
compacted active-tile gather, bucketed to keep jit variants ≤ log2(NT)),
and inactive streamed chunks in the host/disk tiers — so the *physical*
``bytes_h2d`` / ``bytes_disk_read`` shrink with the frontier, not just the
modelled charges. Results are bit-identical to ``activity="off"`` full
sweeps (skipped work contributes exact ⊕-identities by the monotone
contract) and the per-sweep frontier trace is returned as
``Result.activity_log``, from which the iomodel activity terms
(``selective_streamed_tiles`` / ``streamed_block_bytes`` /
``disk_read_bytes(active_rows=...)``) reconstruct the byte meters exactly.

The third tier (paper §IV, the actual *disk*): a graph stored as a
``.dsss`` container (:mod:`repro.storage`) opens with
:meth:`GraphSession.open` into ``residency="disk"`` — the host-side
block buffers and packed tile arrays become read-only **mmap views of
the file**, so nothing edge-scale is resident in host RAM either. The
same streaming machinery (block fetcher / packed chunk streamer) then
moves data disk→device; each mmap fetch of a block or tile chunk that is
neither device-pinned (``memory_budget``) nor RAM-cached
(``host_memory_budget``, the mid tier of the three-level budget)
additionally charges ``Meters.bytes_disk_read`` — checked against the
``disk_read_bytes`` / ``packed_disk_bytes`` closed forms in
:mod:`repro.core.iomodel`. Results stay bit-identical and the model
meters field-identical across all three residencies.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dsss import (
    DSSSGraph,
    active_tile_mask,
    next_bucket,
    tile_source_spans,
)
from repro.core.iomodel import (
    IOParams,
    PACKED_SLOT_BYTES,
    StrategyChoice,
    modelled_io,
    mpu_q,
    select_strategy,
)
from repro.core.plan import ExecutionPlan
from repro.core.vertex_programs import VertexProgram, reduce_identity
from repro.obs.registry import REGISTRY as _REGISTRY
from repro.obs.trace import NO_SPAN
from repro.obs.trace import TRACER as _TRACER
from repro.reliability.checkpoint import (
    SnapshotError,
    latest_snapshot,
    load_snapshot,
    save_snapshot,
)
from repro.reliability.faults import FaultPlan, with_transient_retries

__all__ = [
    "GraphSession",
    "Meters",
    "MODEL_METER_FIELDS",
    "Result",
    "BatchResult",
    "CompiledPlan",
    "PackedStreamPlan",
    "IdentityLRU",
    "get_session",
    "clear_session_cache",
]


# The *modelled* Meters fields — identical across execution modes and
# residencies by contract (tests/test_packed_sweep.py and the residency
# property suite compare exactly this set; keeping the one list here is
# what stops them from drifting when a field is added). The remaining
# fields (wall_seconds, bytes_h2d, peak_device_graph_bytes) are physical:
# they describe whichever data path actually ran.
MODEL_METER_FIELDS = (
    "bytes_read_edges",
    "bytes_read_intervals",
    "bytes_read_hubs",
    "bytes_written_hubs",
    "bytes_written_intervals",
    "iterations",
    "blocks_processed",
    "blocks_skipped",
    "edges_processed",
)


# ---------------------------------------------------------------------------
# Observability handles (repro.obs). The physical byte kinds (h2d,
# disk_read) are incremented on the same lines that charge the Meters
# field, at the transfer/mmap boundary, so a live scrape sees them mid-run;
# the model kinds and the sweep count are published once per run from
# Meters. Either way a run's registry deltas recombine field-for-field
# with Result.meters (tests/test_obs.py). All no-ops under REPRO_OBS=0.
# ---------------------------------------------------------------------------
_OBS_BYTES = _REGISTRY.counter(
    "repro_engine_bytes_total",
    "Engine bytes moved/charged, by Meters field (bytes_<kind>)",
    ("kind",),
)
_OBS_H2D = _OBS_BYTES.labels(kind="h2d")
_OBS_DISK = _OBS_BYTES.labels(kind="disk_read")
# Model-unit byte fields, published as run deltas at the end of _execute.
_OBS_MODEL_BYTES = tuple(
    (f, _OBS_BYTES.labels(kind=f[len("bytes_"):]))
    for f in MODEL_METER_FIELDS
    if f.startswith("bytes_")
)
_OBS_SWEEPS = _REGISTRY.counter(
    "repro_engine_sweeps_total", "Update sweeps executed"
)
_OBS_TILES = _REGISTRY.counter(
    "repro_engine_tiles_swept_total",
    "Packed tiles covered by dispatched sweep scans (active tiles only "
    "under selective execution; the bucket padding is not counted)",
)
_OBS_MSG_TILES = _REGISTRY.counter(
    "repro_engine_vertex_message_tiles_total",
    "Tiles swept with one precomputed per-vertex message per edge "
    "(unweighted programs without destination aux, XLA scan sweep)",
)
_OBS_STREAM_CHUNKS = _REGISTRY.counter(
    "repro_engine_stream_chunks_total",
    "Streamed tile chunks dispatched under host/disk residency (the "
    "device-pinned tile prefix is not a chunk)",
)
_OBS_FETCH_S = _REGISTRY.counter(
    "repro_engine_stream_fetch_seconds_total",
    "Host seconds spent fetching streamed chunks: the mmap slice or RAM "
    "copy plus issuing the device_put (time.perf_counter)",
)
_OBS_RUNS = _REGISTRY.counter(
    "repro_engine_runs_total",
    "Engine runs completed",
    ("program", "strategy", "residency", "execution"),
)
_OBS_PEAK = _REGISTRY.gauge(
    "repro_engine_peak_device_graph_bytes",
    "Device-held topology high-water mark of the last run (model units)",
)
_OBS_DRIFT = _REGISTRY.gauge(
    "repro_iomodel_drift_ratio",
    "Measured/modelled per-iteration slow-tier bytes of the last run with "
    "a Table II closed form (1.0 = the exactness contract holds live)",
    ("direction", "strategy"),
)
# Monotone per-process run ids, linking "sweep"/"checkpoint" trace spans
# to their enclosing "run" span's metadata.
_RUN_SEQ = itertools.count(1)


@dataclasses.dataclass
class Meters:
    """Slow-tier byte counters + scheduling statistics.

    The ``bytes_read_*`` / ``bytes_written_*`` fields are the paper's
    Table II slow-tier traffic, charged in *model units* (``e·Be`` per
    streamed block, ``interval_size·Ba`` per interval load/save). Under
    ``residency="host"`` the edge charges coincide with real host→device
    transfers — a block is charged exactly when it is actually copied —
    and two extra fields report the physical side of the same events:

    * ``bytes_h2d``: raw bytes of the numpy buffers actually shipped to
      the device (bucket-padded, index-encoded — ≥ the model bytes).
    * ``peak_device_graph_bytes``: high-water mark of device-held edge
      topology in model units (pinned resident set + the ≤2-block
      prefetch ring). Under ``residency="device"`` this is the whole
      graph; under ``"host"`` it is bounded by the memory budget plus
      the documented two-block streaming slack.
    * ``bytes_disk_read``: raw bytes fetched from the mmap'd ``.dsss``
      tier under ``residency="disk"`` — charged at the mmap-fetch layer
      whenever a streamed block / tile chunk is neither device-pinned
      nor host-RAM-cached (the ``host_memory_budget`` mid tier). It
      models cold-cache streaming: the OS page cache may physically
      absorb re-reads, but the meter charges each per-sweep fetch, which
      is what the ``disk_read_bytes`` / ``packed_disk_bytes`` closed
      forms (repro.core.iomodel) predict exactly. Zero under the other
      residencies.
    """

    bytes_read_edges: float = 0.0
    bytes_read_intervals: float = 0.0
    bytes_read_hubs: float = 0.0
    bytes_written_hubs: float = 0.0
    bytes_written_intervals: float = 0.0
    bytes_h2d: float = 0.0
    bytes_disk_read: float = 0.0
    peak_device_graph_bytes: float = 0.0
    iterations: int = 0
    blocks_processed: int = 0
    blocks_skipped: int = 0
    edges_processed: int = 0
    wall_seconds: float = 0.0

    def model_dict(self) -> dict:
        """The modelled fields only (see :data:`MODEL_METER_FIELDS`)."""
        return {f: getattr(self, f) for f in MODEL_METER_FIELDS}

    @property
    def bytes_read(self) -> float:
        return self.bytes_read_edges + self.bytes_read_intervals + self.bytes_read_hubs

    @property
    def bytes_written(self) -> float:
        return self.bytes_written_hubs + self.bytes_written_intervals

    @property
    def bytes_total(self) -> float:
        return self.bytes_read + self.bytes_written

    def per_iteration(self) -> "Meters":
        k = max(self.iterations, 1)
        out = Meters(**{f.name: getattr(self, f.name) for f in dataclasses.fields(self)})
        for f in (
            "bytes_read_edges",
            "bytes_read_intervals",
            "bytes_read_hubs",
            "bytes_written_hubs",
            "bytes_written_intervals",
            "bytes_h2d",
            "bytes_disk_read",
        ):
            setattr(out, f, getattr(self, f) / k)
        return out

    def merge(self, other: "Meters") -> "Meters":
        """Accumulate another run's counters into this one (in place).

        Every field sums — including ``iterations`` — so ``per_iteration()``
        of a merged meter remains the true per-sweep average. The one
        exception is ``peak_device_graph_bytes``, a high-water mark:
        merging takes the max (sequential runs reuse the same device).
        """
        for f in dataclasses.fields(self):
            if f.name == "peak_device_graph_bytes":
                setattr(self, f.name, max(getattr(self, f.name), getattr(other, f.name)))
            else:
                setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))
        return self


@dataclasses.dataclass
class Result:
    attrs: np.ndarray
    output: Any
    iterations: int
    converged: bool
    meters: Meters
    strategy: StrategyChoice
    # One (P,) bool array per executed sweep: the source intervals that
    # sweep processed (union over the batch). All-True every sweep for
    # non-selective runs; under selective execution this is the frontier
    # trace the iomodel activity terms (selective_streamed_tiles /
    # streamed_block_bytes / disk_read_bytes) reconstruct the physical
    # byte meters from, exactly. Shared by every member of a batch.
    activity_log: tuple = ()


@dataclasses.dataclass
class BatchResult:
    """K plans executed in one streamed pass.

    ``results[m]`` holds per-query attrs/output; every member shares the
    batch-level ``meters`` object (one edge stream, K attribute states).
    ``iterations`` is the number of shared update sweeps executed.
    """

    results: list[Result]
    meters: Meters
    iterations: int
    converged: bool
    fused: bool  # False when plans were incompatible and ran sequentially
    activity_log: tuple = ()  # per-sweep (P,) processed-interval bitmaps

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def __getitem__(self, m: int) -> Result:
        return self.results[m]


@dataclasses.dataclass(frozen=True)
class CompiledPlan:
    """A plan resolved against one session: strategy + residency, no state.

    ``residency`` is the *resolved* placement mode ("device", "host" or
    "disk" — never "auto"); ``resident`` is the set of sub-shard keys the
    memory budget pins in the fast tier. Under "host"/"disk" the
    resident set is enforced (those blocks are device-pinned, the rest
    are streamed per sweep — from pinned host buffers or from the mmap'd
    store); under "device" every block stays on device and the same
    resident set drives the modelled byte meters only. ``host_cached``
    is the disk tier's mid level: the sub-shards the
    ``host_memory_budget`` keeps materialized in host RAM, whose fetches
    do not charge ``bytes_disk_read`` (empty except under "disk").
    """

    params: IOParams
    choice: StrategyChoice
    resident: frozenset
    residency: str = "device"
    host_cached: frozenset = frozenset()
    # Resolved execution mode: "packed" (the scan) iff a compiled sweep
    # path will actually run (an SPU/DPU/MPU schedule — any residency),
    # else "per_block". Never "auto".
    execution: str = "per_block"
    # Resolved activity mode: "selective" iff the program is monotone and
    # the plan's activity axis is "auto" — frontier-aware interval/tile/
    # chunk skipping; else "off" (full sweeps). Never "auto".
    activity: str = "off"


@dataclasses.dataclass(frozen=True)
class PackedStreamPlan:
    """How packed execution places tiles under enforced host residency.

    ``pin_tiles`` leading tiles stay device-resident (the budget's fast
    tier, mirroring the per-block resident set: SPU pins the leftover
    after both attribute copies, DPU/MPU pin nothing — their I/O model
    streams every edge); the remaining tiles are streamed per sweep in
    chunks of ``chunk_tiles``, double-buffered, so peak device topology is
    the pinned prefix plus at most two chunks (``max_chunk_model_bytes``
    each — the packed counterpart of the per-block two-block slack).

    ``host_tiles`` is the disk tier's mid level (0 except for
    disk-backed sessions): the chunk-aligned count of tiles immediately
    after the pinned prefix that the ``host_memory_budget`` keeps
    materialized in host RAM — streaming those chunks charges
    ``bytes_h2d`` but not ``bytes_disk_read``; everything past
    ``pin_tiles + host_tiles`` re-reads from the mmap'd store each sweep.
    """

    pin_tiles: int
    chunk_tiles: int
    num_tiles: int
    tile_edges: int
    pin_model_bytes: float  # real-edge model bytes of the pinned prefix
    max_chunk_model_bytes: float  # largest streamed chunk, model units
    host_tiles: int = 0


# ---------------------------------------------------------------------------
# Jitted block primitives, batched over a leading K (query) axis via vmap.
# ``program`` is a frozen dataclass => hashable => usable as a static
# argument; jit caches one executable per (program, bucket, num_segments, K)
# combination, shared by every session/plan that uses the same program.
# Block index arrays are query-invariant and enter the vmapped body by
# closure (broadcast); attributes/accumulators carry K, and aux dicts enter
# as vmap operands: with ``aux_batched=False`` (the common case — one aux
# shared by all K queries) every aux leaf broadcasts (in_axes=None), with
# ``aux_batched=True`` every leaf carries its own leading K axis (per-query
# aux, e.g. a run_batch of MaxLabelForward plans with different masks) and
# is mapped — inside the vmap each query sees its own slice at the
# original ndim, so the per-leaf ``ndim == 1`` gather checks are unchanged.
# ---------------------------------------------------------------------------
def _aux_axes(aux: dict, aux_batched: bool):
    """vmap in_axes pytree for an aux dict under either batching mode."""
    return {k: (0 if aux_batched else None) for k in aux}



def _gather_reduce_core(
    program, prev_src, src_aux, dst_aux, src_local, dst_local, weights,
    e_valid, acc, num_segments, has_weights,
):
    vals = prev_src[src_local]
    s_aux = {k: (v[src_local] if getattr(v, "ndim", 0) == 1 else v) for k, v in src_aux.items()}
    d_aux = (
        {k: (v[dst_local] if getattr(v, "ndim", 0) == 1 else v) for k, v in dst_aux.items()}
        if program.needs_dst_aux
        else None
    )
    contrib = program.gather(vals, weights if has_weights else None, s_aux, d_aux)
    ident = reduce_identity(program.reduce, contrib.dtype)
    mask = jnp.arange(contrib.shape[0]) < e_valid
    contrib = jnp.where(mask, contrib, ident)
    if program.reduce == "sum":
        red = jax.ops.segment_sum(contrib, dst_local, num_segments=num_segments)
        return jnp.add(acc, red.astype(acc.dtype))
    if program.reduce == "min":
        red = jax.ops.segment_min(contrib, dst_local, num_segments=num_segments)
        return jnp.minimum(acc, red.astype(acc.dtype))
    red = jax.ops.segment_max(contrib, dst_local, num_segments=num_segments)
    return jnp.maximum(acc, red.astype(acc.dtype))


@functools.partial(
    jax.jit,
    static_argnames=("program", "num_segments", "has_weights", "aux_batched"),
)
def _block_gather_reduce(
    program: VertexProgram,
    prev_src: jnp.ndarray,  # (K, isize) source-interval attributes
    src_aux: dict,  # per-source-interval aux; (K,)-leading when aux_batched
    dst_aux: dict,  # per-dest-interval aux (or empty)
    src_local: jnp.ndarray,  # (bucket,)
    dst_local: jnp.ndarray,  # (bucket,)
    weights: jnp.ndarray | None,
    e_valid: jnp.ndarray,  # scalar int32: real edge count in the bucket
    acc: jnp.ndarray,  # (K, num_segments) running ⊕ accumulator
    num_segments: int,
    has_weights: bool,
    aux_batched: bool = False,
):
    def one(pv, a, sx, dx):
        return _gather_reduce_core(
            program, pv, sx, dx, src_local, dst_local, weights,
            e_valid, a, num_segments, has_weights,
        )

    return jax.vmap(
        one,
        in_axes=(
            0,
            0,
            _aux_axes(src_aux, aux_batched),
            _aux_axes(dst_aux, aux_batched),
        ),
    )(prev_src, acc, src_aux, dst_aux)


def _to_hub_core(
    program, prev_src, src_aux, dst_aux, src_local, hub_inv, dst_local,
    weights, e_valid, num_segments, has_weights,
):
    vals = prev_src[src_local]
    s_aux = {k: (v[src_local] if getattr(v, "ndim", 0) == 1 else v) for k, v in src_aux.items()}
    d_aux = (
        {k: (v[dst_local] if getattr(v, "ndim", 0) == 1 else v) for k, v in dst_aux.items()}
        if program.needs_dst_aux
        else None
    )
    contrib = program.gather(vals, weights if has_weights else None, s_aux, d_aux)
    ident = reduce_identity(program.reduce, contrib.dtype)
    mask = jnp.arange(contrib.shape[0]) < e_valid
    contrib = jnp.where(mask, contrib, ident)
    if program.reduce == "sum":
        return jax.ops.segment_sum(contrib, hub_inv, num_segments=num_segments)
    if program.reduce == "min":
        return jax.ops.segment_min(contrib, hub_inv, num_segments=num_segments)
    return jax.ops.segment_max(contrib, hub_inv, num_segments=num_segments)


@functools.partial(
    jax.jit,
    static_argnames=("program", "num_segments", "has_weights", "aux_batched"),
)
def _block_to_hub(
    program: VertexProgram,
    prev_src: jnp.ndarray,  # (K, isize)
    src_aux: dict,
    dst_aux: dict,
    src_local: jnp.ndarray,
    hub_inv: jnp.ndarray,  # (bucket,) edge -> hub slot
    dst_local: jnp.ndarray,
    weights: jnp.ndarray | None,
    e_valid: jnp.ndarray,
    num_segments: int,  # number of hub slots (unique destinations), padded
    has_weights: bool,
    aux_batched: bool = False,
):
    """ToHub (paper Alg. 6 line 4): partial ⊕ per unique destination."""

    def one(pv, sx, dx):
        return _to_hub_core(
            program, pv, sx, dx, src_local, hub_inv, dst_local,
            weights, e_valid, num_segments, has_weights,
        )

    return jax.vmap(
        one,
        in_axes=(
            0,
            _aux_axes(src_aux, aux_batched),
            _aux_axes(dst_aux, aux_batched),
        ),
    )(prev_src, src_aux, dst_aux)


@functools.partial(jax.jit, static_argnames=("program",))
def _block_from_hub(
    program: VertexProgram,
    acc: jnp.ndarray,  # (K, isize)
    hub_dst: jnp.ndarray,  # (u,) unique local destinations
    partial: jnp.ndarray,  # (K, u) hub values
    u_valid: jnp.ndarray,  # scalar: real number of hub slots
):
    """FromHub (paper Alg. 6 line 11): fold one hub into the accumulator."""

    def one(a, p):
        ident = reduce_identity(program.reduce, a.dtype)
        mask = jnp.arange(p.shape[0]) < u_valid
        p = jnp.where(mask, p.astype(a.dtype), ident)
        if program.reduce == "sum":
            return a.at[hub_dst].add(p, mode="drop")
        if program.reduce == "min":
            return a.at[hub_dst].min(p, mode="drop")
        return a.at[hub_dst].max(p, mode="drop")

    return jax.vmap(one)(acc, partial)


@functools.partial(jax.jit, static_argnames=("program", "aux_batched"))
def _apply_interval(
    program: VertexProgram,
    old: jnp.ndarray,  # (K, isize)
    acc: jnp.ndarray,  # (K, isize)
    aux: dict,  # interval view; (K,)-leading leaves when aux_batched
    globals_: dict,  # per-query iteration scalars, (K,)-leading leaves
    valid: jnp.ndarray,  # (isize,) bool — mask off padding in the last interval
    tol: jnp.ndarray,
    aux_batched: bool = False,
):
    def one(o, a, ax, gl):
        new = program.apply(o, a, ax, gl)
        new = jnp.where(valid, new, o)
        changed = jnp.any(program.changed(o, new, tol) & valid)
        return new, changed

    return jax.vmap(one, in_axes=(0, 0, _aux_axes(aux, aux_batched), 0))(
        old, acc, aux, globals_
    )


@functools.partial(jax.jit, static_argnames=("program", "aux_batched"))
def _pre_iteration(
    program: VertexProgram,
    attrs_flat: jnp.ndarray,
    aux: dict,
    aux_batched: bool = False,
):
    """Per-query iteration globals (e.g. PageRank dangling mass), (K,)-leaved."""
    return jax.vmap(
        lambda a, ax: program.pre_iteration(a, ax),
        in_axes=(0, _aux_axes(aux, aux_batched)),
    )(attrs_flat, aux)


def _fused_core(
    program, attrs, aux, src, dst, weights, valid, tol, n_pad, P, has_weights
):
    globals_ = program.pre_iteration(attrs, aux)
    vals = attrs[src]
    s_aux = {k: (v[src] if getattr(v, "ndim", 0) == 1 else v) for k, v in aux.items()}
    d_aux = (
        {k: (v[dst] if getattr(v, "ndim", 0) == 1 else v) for k, v in aux.items()}
        if program.needs_dst_aux
        else None
    )
    contrib = program.gather(vals, weights if has_weights else None, s_aux, d_aux)
    if program.reduce == "sum":
        red = jax.ops.segment_sum(contrib, dst, num_segments=n_pad)
    elif program.reduce == "min":
        red = jax.ops.segment_min(contrib, dst, num_segments=n_pad)
    else:
        red = jax.ops.segment_max(contrib, dst, num_segments=n_pad)
    red = red.astype(attrs.dtype)
    new = program.apply(attrs, red, aux, globals_)
    new = jnp.where(valid, new, attrs)
    changed = program.changed(attrs, new, tol) & valid
    changed_iv = jnp.any(changed.reshape(P, -1), axis=1)
    return new, changed_iv


@functools.partial(
    jax.jit,
    static_argnames=("program", "n_pad", "P", "has_weights", "aux_batched"),
)
def _fused_iteration(
    program: VertexProgram,
    attrs: jnp.ndarray,  # (K, n_pad)
    aux: dict,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    weights: jnp.ndarray | None,
    valid: jnp.ndarray,
    tol: jnp.ndarray,
    n_pad: int,
    P: int,
    has_weights: bool,
    aux_batched: bool = False,
):
    def one(a, ax):
        return _fused_core(
            program, a, ax, src, dst, weights, valid, tol, n_pad, P, has_weights
        )

    return jax.vmap(one, in_axes=(0, _aux_axes(aux, aux_batched)))(attrs, aux)


# ---------------------------------------------------------------------------
# Compiled (tile-packed) sweep primitives. One jax.lax.scan over the packed
# tile axis replaces the per-sub-shard dispatch loop: the whole gather-reduce
# phase of an update sweep is a single XLA program (or, under host
# residency, one program per streamed tile chunk). Bit-identity with the
# per-block path holds because (a) tiles are cut only at destination-run
# boundaries, so every (sub-shard, destination) partial ⊕ is computed over
# the same values in the same order as the per-block segment reduce,
# (b) stream order folds each destination's sub-shard partials in ascending
# source-interval order — the fold order of SPU and of the DPU/MPU
# two-phase schedules alike — and (c) padding and inactive-row edges
# contribute exact ⊕-identities. When the gather reads nothing per edge
# (no weights, no destination aux) its contribution is a function of the
# source vertex alone, so the sweep computes it once per vertex before the
# scan — inactive sources already set to the ⊕-identity — and each edge
# gathers that one message: the same elementwise values, bit for bit.
# ---------------------------------------------------------------------------
def _stack_interval_aux(aux: dict, P: int, isz: int) -> dict:
    """Reshape 1-D (n_pad,) aux leaves to (P, isz) interval rows in-trace."""
    return {
        k: (v.reshape(P, isz) if getattr(v, "ndim", 0) == 1 else v)
        for k, v in aux.items()
    }


def _vertex_message_applies(program: VertexProgram, has_weights: bool) -> bool:
    """Whether ``program``'s per-edge contribution depends on the source
    vertex alone, so a sweep may gather one precomputed message per edge."""
    return not has_weights and not program.needs_dst_aux


def _vertex_messages(program, attrs_flat, aux, vert_active, aux_batched):
    """(K, n_pad) per-vertex messages: ``program.gather`` over every vertex,
    with sources in inactive intervals set to the ⊕-identity."""

    def one(pv, auxq):
        msg = program.gather(pv, None, auxq, None)
        return jnp.where(
            vert_active, msg, reduce_identity(program.reduce, msg.dtype)
        )

    return jax.vmap(one, in_axes=(0, _aux_axes(aux, aux_batched)))(
        attrs_flat, aux
    )


def _message_contributions(program, msgs, tile):
    """(K, T) contributions of one tile from the per-vertex messages: one
    gather per edge, tile padding (past ``e_valid``) set to the identity."""
    src = tile["src"]
    live = jnp.arange(src.shape[-1]) < tile["e_valid"]

    def one(m):
        return jnp.where(
            live, m[src], reduce_identity(program.reduce, m.dtype)
        )

    return jax.vmap(one)(msgs)


def _edge_contributions(
    program, attrs_flat, aux, vert_active, has_weights, aux_batched, tile
):
    """(K, T) contributions of one tile gathered per edge: source attribute,
    weight, source and destination aux; padding and edges whose source
    interval is inactive set to the identity."""
    src = tile["src"]
    dst = tile["dst"]
    w = tile["weights"] if has_weights else None
    live = (jnp.arange(src.shape[-1]) < tile["e_valid"]) & vert_active[src]

    def one(pv, auxq):
        vals = pv[src]
        s_aux = {
            k: (v[src] if getattr(v, "ndim", 0) == 1 else v)
            for k, v in auxq.items()
        }
        d_aux = (
            {
                k: (v[dst] if getattr(v, "ndim", 0) == 1 else v)
                for k, v in auxq.items()
            }
            if program.needs_dst_aux
            else None
        )
        contrib = program.gather(vals, w, s_aux, d_aux)
        return jnp.where(
            live, contrib, reduce_identity(program.reduce, contrib.dtype)
        )

    return jax.vmap(one, in_axes=(0, _aux_axes(aux, aux_batched)))(
        attrs_flat, aux
    )


def _fold_tile(program, contrib, tile, acc_flat):
    """Scatter-fold a tile's (K, T) contributions into the (K, n_pad)
    accumulator at each edge's global ``dst``: one indexed pass per edge.

    Padding slots (past ``e_valid``) index the ``n_pad`` sentinel and are
    dropped, so they leave the accumulator bit-untouched. Updates apply in
    slot order (XLA serialises conflicting scatter updates in order on
    CPU and TPU), so a destination's float sum is a left fold of its
    in-edges in the tile stream's order: a reassociation of the per-block
    executor's run-partial fold, equal to it for min and max.
    """
    n_pad = acc_flat.shape[-1]
    dst = tile["dst"]
    live = jnp.arange(dst.shape[-1]) < tile["e_valid"]
    dst = jnp.where(live, dst, n_pad)

    def one(c, aq):
        with jax.named_scope("scatter_fold"):
            fold = aq.at[dst]
            c = c.astype(aq.dtype)
            if program.reduce == "sum":
                return fold.add(c, mode="drop")
            if program.reduce == "min":
                return fold.min(c, mode="drop")
            return fold.max(c, mode="drop")

    return jax.vmap(one)(contrib, acc_flat)


def _packed_sweep_impl(
    program: VertexProgram,
    attrs_flat: jnp.ndarray,  # (K, n_pad) previous attributes (read-only)
    acc_flat: jnp.ndarray,  # (K, n_pad) running ⊕ accumulators (donatable)
    aux: dict,  # run-constant aux; (K,)-leading leaves when aux_batched
    tiles: dict,  # PackedSweep device arrays, (NT, ...) leaves
    row_active: jnp.ndarray,  # (P,) bool — sweep's active source intervals
    has_weights: bool,
    aux_batched: bool = False,
):
    """The gather-reduce phase of one update sweep over a tile sequence.

    Each scan step processes one destination-aligned tile: build the
    tile's contributions from its global ``src`` ids, then scatter-fold
    them straight into the flat accumulator at their global ``dst``
    (:func:`_fold_tile`), one indexed pass per edge. The scan reads
    neither ``run_local`` nor ``run_dst``. Min and max results equal the
    per-block executor's bit for bit; a float sum folds each
    destination's in-edges left to right in the tile stream's order, a
    reassociation of the per-block run-partial fold (equal up to float32
    rounding, not bitwise). Every run of this executable folds in the
    same order, so residency tiers, chunking, selective scans and resume
    stay bit-identical to one another.

    Edges whose source interval is inactive this sweep (monotone activity
    tracking — the (P,) row mask is expanded to a per-vertex mask
    in-trace, so only P bools cross the host→device boundary per sweep)
    contribute exact ⊕-identities; edges past ``e_valid`` (tile padding)
    index the ``n_pad`` sentinel and are dropped by the scatter.
    Unweighted programs without destination aux
    (:func:`_vertex_message_applies`) apply the activity mask once per
    vertex, in the messages built before the scan, and each step gathers
    one message per edge and masks only the padding; the others gather
    attribute, aux and mask per edge. Called
    once over all tiles under device residency, and once per streamed
    chunk (same executable, smaller leading axis) under host residency —
    the scan carry composes exactly.
    """
    n_pad = attrs_flat.shape[-1]
    # Interval mask -> per-vertex mask. A broadcast, not jnp.repeat: the
    # TPU compiler spends ~100 s constant-folding repeat's index arithmetic
    # at n_pad ≈ 5 M, and under a second on this.
    P = row_active.shape[0]
    vert_active = jnp.broadcast_to(row_active[:, None], (P, n_pad // P))
    vert_active = vert_active.reshape(n_pad)

    if _vertex_message_applies(program, has_weights):
        with jax.named_scope("vertex_message"):
            msgs = _vertex_messages(
                program, attrs_flat, aux, vert_active, aux_batched
            )
        contributions = functools.partial(
            _message_contributions, program, msgs
        )
    else:
        contributions = functools.partial(
            _edge_contributions, program, attrs_flat, aux, vert_active,
            has_weights, aux_batched,
        )

    # The phases carry named scopes, so the op profile and HLO dumps
    # attribute each fusion of the step to the gather or the fold.
    def body(carry, tile):
        with jax.named_scope("gather"):
            contrib = contributions(tile)
        return _fold_tile(program, contrib, tile, carry), None

    acc_flat, _ = jax.lax.scan(body, acc_flat, tiles)
    return acc_flat


def _apply_all_impl(
    program: VertexProgram,
    old: jnp.ndarray,  # (K, P, isz)
    acc: jnp.ndarray,  # (K, P, isz) (donatable)
    aux: dict,
    globals_: dict,  # (K,)-leading leaves from _pre_iteration
    valid: jnp.ndarray,  # (P, isz) bool
    tol: jnp.ndarray,
    aux_batched: bool = False,
):
    """All P interval applies of a sweep in one batched dispatch.

    Elementwise identical to P ``_apply_interval`` calls. Untouched
    monotone intervals carry identity accumulators, so their apply is an
    exact no-op and ``changed`` is False — matching the per-block skip.
    """
    K, P, isz = old.shape
    if aux_batched:
        # Per-query aux: (K, n_pad) leaves fold to (K, P, isz) interval
        # rows and map over the query axis alongside the attributes.
        aux2 = {
            k: (v.reshape(K, P, isz) if getattr(v, "ndim", 0) == 2 else v)
            for k, v in aux.items()
        }
        q_axes = {k: 0 for k in aux2}
    else:
        aux2 = _stack_interval_aux(aux, P, isz)
        q_axes = {k: None for k in aux2}

    def per_interval(o, a, auxv, v, gl):
        with jax.named_scope("apply"):
            new = program.apply(o, a, auxv, gl)
            new = jnp.where(v, new, o)
            changed = jnp.any(program.changed(o, new, tol) & v)
        return new, changed

    def per_query(o, a, auxq, gl):
        iv_axes = {
            k: (0 if getattr(v, "ndim", 0) == 2 else None)
            for k, v in auxq.items()
        }
        return jax.vmap(per_interval, in_axes=(0, 0, iv_axes, 0, None))(
            o, a, auxq, valid, gl
        )

    return jax.vmap(per_query, in_axes=(0, 0, q_axes, 0))(
        old, acc, aux2, globals_
    )


@functools.lru_cache(maxsize=None)
def _packed_jits(donate: bool):
    """The two packed-sweep executables, with accumulator donation off-CPU.

    Donation lets XLA reuse the ⊕-accumulator buffer across the scan and
    the apply (the paper's in-place attribute update); the CPU backend
    does not support donation, so it is keyed off to avoid per-compile
    warnings there.
    """
    donate_kw = {"donate_argnums": (2,)} if donate else {}
    sweep = jax.jit(
        _packed_sweep_impl,
        static_argnames=("program", "has_weights", "aux_batched"),
        **donate_kw,
    )
    apply_all = jax.jit(
        _apply_all_impl,
        static_argnames=("program", "aux_batched"),
        **donate_kw,
    )
    return sweep, apply_all


def _packed_sweep_select_impl(
    program: VertexProgram,
    attrs_flat: jnp.ndarray,  # (K, n_pad)
    acc_flat: jnp.ndarray,  # (K, n_pad) (donatable)
    aux: dict,
    tiles: dict,  # (NT, ...) staged tile leaves
    idx: jnp.ndarray,  # (bucket,) int32 active tile indices, 0-padded
    a_valid: jnp.ndarray,  # scalar int32: real entries in idx
    row_active: jnp.ndarray,  # (P,) bool
    has_weights: bool,
    aux_batched: bool = False,
):
    """Compacted active-tile sweep: scan only the gathered tiles.

    ``idx`` holds the active tile indices in ascending order (so the scan
    preserves the full sweep's ascending-source-interval fold order),
    padded with tile 0 to a power-of-two bucket — padding entries are
    neutralized by forcing their ``e_valid`` to 0, which masks every edge
    to an exact ⊕-identity. The gather keeps the scan's tile shape
    static, so jit compiles at most ``log2(NT)`` bucket variants instead
    of one executable per frontier size.
    """
    sel = {k: v[idx] for k, v in tiles.items()}
    keep = jnp.arange(idx.shape[0]) < a_valid
    sel["e_valid"] = jnp.where(keep, sel["e_valid"], 0)
    return _packed_sweep_impl(
        program, attrs_flat, acc_flat, aux, sel, row_active, has_weights,
        aux_batched,
    )


@functools.lru_cache(maxsize=None)
def _packed_select_jits(donate: bool):
    """The compacted-gather sweep executable (selective packed path)."""
    donate_kw = {"donate_argnums": (2,)} if donate else {}
    return jax.jit(
        _packed_sweep_select_impl,
        static_argnames=("program", "has_weights", "aux_batched"),
        **donate_kw,
    )


# ---------------------------------------------------------------------------
# Per-run context handed to the iteration bodies.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _RunContext:
    session: "GraphSession"
    program: VertexProgram
    choice: StrategyChoice
    resident: frozenset
    params: IOParams
    aux: dict
    aux_views: list[dict]  # all P interval views, hoisted once per run
    valid: jnp.ndarray  # (P, isize) bool
    tol: jnp.ndarray
    K: int
    residency: str = "device"  # resolved placement ("device" | "host")
    fetcher: _BlockFetcher = None  # type: ignore[assignment]
    activity: str = "off"  # resolved activity ("selective" | "off")
    aux_batched: bool = False  # aux leaves carry a leading (K,) query axis
    trace: bool = False  # record the sweep's phase spans (TraceSpec.sweeps)
    tiles_swept: int = 0  # tiles the run's scans covered so far

    @property
    def block_keys(self) -> frozenset:
        return self.session.block_keys


def _rows_to_process(ctx: _RunContext, active: np.ndarray) -> list[int]:
    """Selective runs skip source intervals inactive for *every* query
    (paper §II-B activity tracking, unioned over the batch axis).

    Resolved per compile: ``"selective"`` iff the program is monotone
    (re-gathering an unchanged source is an exact no-op) and the plan did
    not force ``activity="off"`` — the A/B baseline where every interval
    is processed and every chunk streamed each sweep.
    """
    P = ctx.session.graph.P
    if ctx.activity == "selective":
        return [i for i in range(P) if active[:, i].any()]
    return list(range(P))


def _iteration_spu(ctx: _RunContext, attrs, active, meters: Meters):
    """Paper Algorithm 5: row-major, all intervals ping-pong resident."""
    sess, prog = ctx.session, ctx.program
    g = sess.graph
    isz = g.interval_size
    K = ctx.K
    globals_ = _pre_iteration(
        prog, attrs.reshape(K, -1), ctx.aux, aux_batched=ctx.aux_batched
    )
    ident = reduce_identity(prog.reduce, prog.dtype)
    acc = [jnp.full((K, isz), ident, prog.dtype) for _ in range(g.P)]
    touched = [False] * g.P
    rows = _rows_to_process(ctx, active)
    order = [
        (i, j) for i in rows for j in range(g.P) if (i, j) in ctx.block_keys
    ]
    fetch = ctx.fetcher.begin(order)
    for i, j in order:
        blk = fetch()
        acc[j] = _block_gather_reduce(
            prog,
            attrs[:, i],
            ctx.aux_views[i],
            ctx.aux_views[j] if prog.needs_dst_aux else {},
            blk["src_local"],
            blk["dst_local"],
            blk["weights"],
            blk["e_valid"],
            acc[j],
            num_segments=isz,
            has_weights=sess.has_weights,
            aux_batched=ctx.aux_batched,
        )
        touched[j] = True
        meters.blocks_processed += 1
        meters.edges_processed += blk["e"]
    meters.blocks_skipped += (g.P - len(rows)) * g.P
    new_cols = []
    active_next = np.zeros((K, g.P), dtype=bool)
    for j in range(g.P):
        if not touched[j] and prog.monotone:
            new_cols.append(attrs[:, j])
            continue
        new_j, changed = _apply_interval(
            prog, attrs[:, j], acc[j], ctx.aux_views[j], globals_,
            ctx.valid[j], ctx.tol, aux_batched=ctx.aux_batched,
        )
        new_cols.append(new_j)
        active_next[:, j] = np.asarray(changed)
    return jnp.stack(new_cols, axis=1), active_next


def _iteration_two_phase(ctx: _RunContext, attrs, active, meters: Meters, Q: int):
    """Paper Algorithms 6 (Q=0: DPU) and 7 (0<Q<P: MPU).

    Intervals < Q are ping-pong resident (SPU-like); intervals >= Q are
    cold: their contributions route through hubs and they are loaded/saved
    once per iteration. Interval and hub bytes are charged per query (K×):
    each query owns its attribute state, while the edge stream is shared.
    """
    sess, prog = ctx.session, ctx.program
    g = sess.graph
    isz = g.interval_size
    K = ctx.K
    globals_ = _pre_iteration(
        prog, attrs.reshape(K, -1), ctx.aux, aux_batched=ctx.aux_batched
    )
    ident = reduce_identity(prog.reduce, prog.dtype)
    acc = [jnp.full((K, isz), ident, prog.dtype) for _ in range(g.P)]
    touched = [False] * g.P
    # Hub state between the phases: (partial, hub_dst, u_valid, u). Keeping
    # the (small) hub metadata here means phase 2 never re-touches the edge
    # block — each sub-shard is fetched exactly once per sweep.
    hubs: dict[tuple[int, int], tuple] = {}
    rows = _rows_to_process(ctx, active)
    iv_bytes = isz * ctx.params.Ba * K

    # Every sub-shard is visited once: (j < Q or i >= Q) blocks in the
    # row-major phase, deferred (i < Q, j >= Q) blocks in the column-major
    # phase. Declaring the order up front drives the streaming prefetch.
    phase1 = [
        (i, j)
        for i in rows
        for j in range(g.P)
        if (j < Q or i >= Q) and (i, j) in ctx.block_keys
    ]
    phase2 = [
        (i, j)
        for j in range(g.P)
        if j >= Q
        for i in rows
        if i < Q and (i, j) in ctx.block_keys
    ]
    fetch = ctx.fetcher.begin(phase1 + phase2)

    def _direct(i: int, j: int, blk: dict) -> None:
        """UpdateInMemory (paper Alg. 7 lines 4, 10, 20)."""
        acc[j] = _block_gather_reduce(
            prog,
            attrs[:, i],
            ctx.aux_views[i],
            ctx.aux_views[j] if prog.needs_dst_aux else {},
            blk["src_local"],
            blk["dst_local"],
            blk["weights"],
            blk["e_valid"],
            acc[j],
            num_segments=isz,
            has_weights=sess.has_weights,
            aux_batched=ctx.aux_batched,
        )
        touched[j] = True
        meters.blocks_processed += 1
        meters.edges_processed += blk["e"]

    # Phase 1 (row-major): resident rows (i < Q) update resident
    # destinations (j < Q); cold rows (i >= Q) are loaded once, updating
    # resident destinations directly and cold destinations via ToHub.
    # Blocks (i < Q, j >= Q) are deferred to the column phase so that
    # only one cold accumulator is ever live (paper Alg. 7 lines 17-24).
    for i in rows:
        if i >= Q:
            meters.bytes_read_intervals += iv_bytes  # LoadFromDisk(I_i)
        for j in range(g.P):
            if (i, j) not in ctx.block_keys or not (j < Q or i >= Q):
                continue
            blk = fetch()
            if j < Q:
                _direct(i, j, blk)
            else:
                # UpdateToHub (cold source AND cold destination).
                partial = _block_to_hub(
                    prog,
                    attrs[:, i],
                    ctx.aux_views[i],
                    ctx.aux_views[j] if prog.needs_dst_aux else {},
                    blk["src_local"],
                    blk["hub_inv"],
                    blk["dst_local"],
                    blk["weights"],
                    blk["e_valid"],
                    num_segments=blk["u_bucket"],
                    has_weights=sess.has_weights,
                    aux_batched=ctx.aux_batched,
                )
                hubs[(i, j)] = (partial, blk["hub_dst"], blk["u_valid"], blk["u"])
                touched[j] = True
                meters.bytes_written_hubs += blk["u"] * (
                    ctx.params.Ba + sess.Bv
                ) * K
                meters.blocks_processed += 1
                meters.edges_processed += blk["e"]
    meters.blocks_skipped += (g.P - len(rows)) * g.P

    # Phase 2 (column-major): resident columns apply directly; cold
    # columns first take deferred resident-source blocks, then fold hubs,
    # then save (paper Alg. 6 lines 8-14 / Alg. 7 lines 17-26).
    new_cols: list[jnp.ndarray] = [None] * g.P  # type: ignore[list-item]
    active_next = np.zeros((K, g.P), dtype=bool)
    for j in range(g.P):
        if j >= Q:
            for i in rows:
                if i < Q and (i, j) in ctx.block_keys:
                    _direct(i, j, fetch())
            for i in rows:
                h = hubs.get((i, j))
                if h is None:
                    continue
                partial, hub_dst, u_valid, u = h
                acc[j] = _block_from_hub(prog, acc[j], hub_dst, partial, u_valid)
                meters.bytes_read_hubs += u * (ctx.params.Ba + sess.Bv) * K
        if not touched[j] and prog.monotone:
            new_cols[j] = attrs[:, j]
            continue
        if j >= Q and prog.monotone:
            # Monotone apply needs the previous attributes of a cold
            # interval — one extra interval read vs. the paper's
            # PageRank-style accounting (documented deviation).
            meters.bytes_read_intervals += iv_bytes
        new_j, changed = _apply_interval(
            prog, attrs[:, j], acc[j], ctx.aux_views[j], globals_,
            ctx.valid[j], ctx.tol, aux_batched=ctx.aux_batched,
        )
        new_cols[j] = new_j
        active_next[:, j] = np.asarray(changed)
        if j >= Q:
            meters.bytes_written_intervals += iv_bytes  # SaveToDisk(I_j)
    return jnp.stack(new_cols, axis=1), active_next


def _iteration_dpu(ctx, attrs, active, meters):
    return _iteration_two_phase(ctx, attrs, active, meters, Q=0)


def _iteration_mpu(ctx, attrs, active, meters):
    return _iteration_two_phase(ctx, attrs, active, meters, Q=ctx.choice.Q)


def _iteration_fused(ctx: _RunContext, attrs, active, meters: Meters):
    """One XLA program per iteration: global gather + segment-reduce.

    Produces bit-identical results to SPU for sum/min/max programs; this
    is the TPU-native fast path (HBM-resident, no host scheduling) and
    the baseline the Pallas kernel (kernels/dsss_spmv.py) is checked
    against.
    """
    sess, prog = ctx.session, ctx.program
    g = sess.graph
    K = ctx.K
    fa = sess.fused_arrays()
    flat, changed_iv = _fused_iteration(
        prog,
        attrs.reshape(K, -1),
        ctx.aux,
        fa["src"],
        fa["dst"],
        fa["weights"],
        ctx.valid.reshape(-1),
        ctx.tol,
        n_pad=g.n_pad,
        P=g.P,
        has_weights=sess.has_weights,
        aux_batched=ctx.aux_batched,
    )
    meters.blocks_processed += len(sess.block_keys)
    meters.edges_processed += g.m
    return flat.reshape(K, g.P, g.interval_size), np.asarray(changed_iv)


# ---------------------------------------------------------------------------
# Packed execution: the same SPU/DPU/MPU schedules, one compiled sweep.
# The numeric pass is strategy-independent (every schedule folds each
# destination interval in ascending source-interval order — see
# repro.core.dsss.PackedSweep); what distinguishes the strategies is their
# slow-tier traffic, which is charged here from the packed metadata with
# exactly the control flow of the per-block bodies.
# ---------------------------------------------------------------------------
def _charge_packed_spu(ctx: _RunContext, rows: list[int], meters: Meters) -> None:
    """Meter mutations of ``_iteration_spu``, from metadata alone."""
    sess = ctx.session
    g = sess.graph
    host = sess.host_blocks
    Be = sess.Be
    for i in rows:
        for j in range(g.P):
            if (i, j) not in ctx.block_keys:
                continue
            e = host[(i, j)]["e"]
            if (i, j) not in ctx.resident:
                meters.bytes_read_edges += e * Be
            meters.blocks_processed += 1
            meters.edges_processed += e
    meters.blocks_skipped += (g.P - len(rows)) * g.P


def _charge_packed_two_phase(
    ctx: _RunContext, rows: list[int], meters: Meters, Q: int
) -> None:
    """Meter mutations of ``_iteration_two_phase``, from metadata alone.

    Mirrors the two-phase control flow line for line — phase-1 direct and
    ToHub charges, deferred phase-2 direct blocks, hub folds, interval
    load/saves and the documented monotone cold-interval re-read — so the
    packed run's Meters are field-for-field identical to the per-block
    run's.
    """
    sess, prog = ctx.session, ctx.program
    g = sess.graph
    host = sess.host_blocks
    Be = sess.Be
    K = ctx.K
    iv_bytes = g.interval_size * ctx.params.Ba * K
    hub_bytes = ctx.params.Ba + sess.Bv
    touched = [False] * g.P
    hub_u: dict[tuple[int, int], int] = {}
    # Phase 1 (row-major): direct blocks (j < Q) and ToHub blocks (i >= Q,
    # j >= Q); cold source intervals load once.
    for i in rows:
        if i >= Q:
            meters.bytes_read_intervals += iv_bytes
        for j in range(g.P):
            if (i, j) not in ctx.block_keys or not (j < Q or i >= Q):
                continue
            e = host[(i, j)]["e"]
            if (i, j) not in ctx.resident:
                meters.bytes_read_edges += e * Be
            if j >= Q:
                u = host[(i, j)]["u"]
                hub_u[(i, j)] = u
                meters.bytes_written_hubs += u * hub_bytes * K
            touched[j] = True
            meters.blocks_processed += 1
            meters.edges_processed += e
    meters.blocks_skipped += (g.P - len(rows)) * g.P
    # Phase 2 (column-major): deferred (i < Q, j >= Q) direct blocks, hub
    # folds, then the cold-interval apply traffic.
    for j in range(g.P):
        if j >= Q:
            for i in rows:
                if i < Q and (i, j) in ctx.block_keys:
                    e = host[(i, j)]["e"]
                    if (i, j) not in ctx.resident:
                        meters.bytes_read_edges += e * Be
                    meters.blocks_processed += 1
                    meters.edges_processed += e
                    touched[j] = True
            for i in rows:
                u = hub_u.get((i, j))
                if u is not None:
                    meters.bytes_read_hubs += u * hub_bytes * K
        if not touched[j] and prog.monotone:
            continue
        if j >= Q and prog.monotone:
            # Monotone apply re-reads the cold interval's previous
            # attributes (documented deviation, as in the per-block path).
            meters.bytes_read_intervals += iv_bytes
        if j >= Q:
            meters.bytes_written_intervals += iv_bytes
    return None


def _packed_host_chunk(packed, lo: int, hi: int, has_weights: bool) -> dict:
    """Host (numpy) views of tiles [lo, hi) in the streaming leaf schema."""
    chunk = {
        "src": packed.src[lo:hi],
        "dst": packed.dst[lo:hi],
        "run_local": packed.run_local[lo:hi],
        "run_dst": packed.run_dst[lo:hi],
        "e_valid": packed.e_valid[lo:hi],
    }
    if has_weights:
        chunk["weights"] = packed.weights[lo:hi]
    return chunk


def _chunk_nbytes(chunk: dict) -> int:
    return sum(a.nbytes for a in chunk.values())


def _sweep_tile_slab(
    ctx: _RunContext, attrs_flat, acc, tiles, row_active, sweep, window
):
    """Run the packed scan over one staged tile slab, compacted to ``window``.

    ``tiles`` is a dict of device leaves with leading axis ``len(window)``
    (the full staged layout, or the pinned prefix). ``window=None`` (full
    sweep) and an all-True window use the plain scan — the exact
    executable the ``activity="off"`` baseline runs; a partial window
    gathers the active tiles into a power-of-two bucket and runs the
    compacted scan (≤ log2(NT) jit variants); an all-False window is a
    pure no-op. ``np.flatnonzero`` keeps the gathered tiles in ascending
    order, preserving the full sweep's fold order — bit-identity.
    """
    sess, prog = ctx.session, ctx.program
    hw = sess.has_weights
    if window is None or window.all():
        n = int(tiles["e_valid"].shape[0])
        _count_tiles(ctx, n)
        with _TRACER.span("sweep.scan", tiles=n) if ctx.trace else NO_SPAN:
            return sweep(
                prog, attrs_flat, acc, ctx.aux, tiles, row_active,
                has_weights=hw, aux_batched=ctx.aux_batched,
            )
    local = np.flatnonzero(window)
    if local.size == 0:
        return acc
    count = int(window.shape[0])
    bucket = min(next_bucket(int(local.size)), count)
    idx = np.zeros(bucket, np.int32)
    idx[: local.size] = local
    select = _packed_select_jits(jax.default_backend() != "cpu")
    _count_tiles(ctx, int(local.size))
    with (
        _TRACER.span("sweep.scan", tiles=int(local.size), bucket=bucket)
        if ctx.trace
        else NO_SPAN
    ):
        return select(
            prog, attrs_flat, acc, ctx.aux, tiles,
            jnp.asarray(idx), jnp.asarray(np.int32(local.size)), row_active,
            has_weights=hw, aux_batched=ctx.aux_batched,
        )


def _count_tiles(ctx: _RunContext, n: int) -> None:
    """Charge ``n`` tiles to the run and ``repro_engine_tiles_swept_total``
    at the scan's dispatch site, and to
    ``repro_engine_vertex_message_tiles_total`` where the scan gathers one
    per-vertex message per edge."""
    ctx.tiles_swept += n
    _OBS_TILES.inc(n)
    if _vertex_message_applies(ctx.program, ctx.session.has_weights):
        _OBS_MSG_TILES.inc(n)


def _packed_host_sweep(
    ctx: _RunContext, attrs_flat, acc, row_active, meters: Meters, sweep,
    tile_active=None,
):
    """Host-resident packed execution: stream tile chunks through the scan.

    The pinned tile prefix (what the memory budget keeps device-resident,
    see :meth:`GraphSession.packed_stream_plan`) runs first from its staged
    device arrays; the remaining tiles are cut into fixed chunks and
    streamed host→device with the same double-buffered discipline as
    :class:`_BlockFetcher` — while chunk ``c`` computes, chunk ``c+1``'s
    transfer is already in flight (``jax.device_put`` is async). Each
    streamed chunk charges its raw padded bytes to ``bytes_h2d`` and its
    real-edge model bytes to the ``peak_device_graph_bytes`` high-water
    mark (pinned prefix + at most two in-flight chunks). The *model* byte
    meters are charged from metadata exactly as under device residency —
    physical streaming never changes them.

    ``residency="disk"`` runs the same loop over mmap-backed tile arrays:
    chunks inside the ``host_memory_budget``-cached window (the plan's
    ``host_tiles``) are served from materialized RAM copies, every other
    chunk is sliced straight out of the file and additionally charges its
    raw bytes to ``bytes_disk_read`` — the ``packed_disk_bytes`` closed
    form.

    ``tile_active`` (selective execution) restricts the physical stream
    to the frontier: chunks containing no active tile are never fetched —
    no transfer, no ``bytes_h2d``/``bytes_disk_read`` charge — and the
    pinned prefix runs compacted to its active tiles. The closed forms
    gain the same activity term via
    :func:`repro.core.iomodel.selective_streamed_tiles`, keeping
    measured-vs-modelled equality exact.

    Each chunk's fetch (slice, ``device_put`` call) is timed into
    ``repro_engine_stream_fetch_seconds_total``; chunks count into
    ``repro_engine_stream_chunks_total``. On one TPU v5e, streaming
    554 one-tile chunks (1 MB each, from the page cache) per sweep of a
    Graph500 scale-21 graph, a fetch took ~1.05 ms of host time against
    ~1.75 ms of device time per tile: the double buffer hid the stream,
    the device idled ~1 % of a job, and a sweep took within 1 % of the
    device tier's time at the same seed. All chunks but a shorter last
    one share one shape, and warm-up's full sweep compiles the pinned
    slab's scan and both chunk shapes, so no chunk compiles in a timed
    run.
    """
    sess, prog = ctx.session, ctx.program
    packed = sess._staged.packed_host(sess.packing)
    splan = sess.packed_stream_plan(ctx.choice.strategy, ctx.params.Ba)
    hw = sess.has_weights
    disk = ctx.residency == "disk"
    cache_end = splan.pin_tiles + splan.host_tiles
    pins, pin_model = sess._ensure_packed_pins(splan.pin_tiles)
    meters.peak_device_graph_bytes = max(
        meters.peak_device_graph_bytes, pin_model
    )
    if pins is not None:
        acc = _sweep_tile_slab(
            ctx, attrs_flat, acc, pins, row_active, sweep,
            None if tile_active is None else tile_active[: splan.pin_tiles],
        )
    nt = packed.num_tiles
    if splan.pin_tiles >= nt:
        return acc
    Be = sess.Be
    starts = [
        lo
        for lo in range(splan.pin_tiles, nt, splan.chunk_tiles)
        if tile_active is None
        or tile_active[lo : min(lo + splan.chunk_tiles, nt)].any()
    ]
    if not starts:
        return acc

    def fetch(idx: int) -> tuple[dict, Any, float, bool]:
        t0 = time.perf_counter()
        lo = starts[idx]
        hi = min(lo + splan.chunk_tiles, nt)
        with _TRACER.span("sweep.fetch", tiles=hi - lo) if ctx.trace else NO_SPAN:
            cached = disk and hi <= cache_end
            if cached:
                host = sess._packed_ram_chunk(lo, hi)
            else:
                host = _packed_host_chunk(packed, lo, hi, hw)
            model = float(packed.e_valid[lo:hi].sum()) * Be
            # The chunk transfer is the packed path's "h2d" injection
            # boundary; transient faults retry in place (see
            # _BlockFetcher._upload for the discipline).
            dev = with_transient_retries(
                sess._injector, f"chunk:{lo}", lambda: jax.device_put(host)
            )
        _OBS_FETCH_S.inc(time.perf_counter() - t0)
        return host, dev, model, cached

    cur = None
    for idx in range(len(starts)):
        n = min(starts[idx] + splan.chunk_tiles, nt) - starts[idx]
        _count_tiles(ctx, n)
        _OBS_STREAM_CHUNKS.inc()
        # One span per chunk: the next chunk's H2D (and, first, this
        # one's), then this one's dispatch.
        with _TRACER.span("sweep.chunk", tiles=n) if ctx.trace else NO_SPAN:
            if cur is None:
                cur = fetch(0)
            nxt = fetch(idx + 1) if idx + 1 < len(starts) else None
            host, dev, model, cached = cur
            nb = _chunk_nbytes(host)
            meters.bytes_h2d += nb
            _OBS_H2D.inc(nb)
            if disk and not cached:
                meters.bytes_disk_read += nb
                _OBS_DISK.inc(nb)
            live = pin_model + model + (nxt[2] if nxt is not None else 0.0)
            meters.peak_device_graph_bytes = max(
                meters.peak_device_graph_bytes, live
            )
            acc = sweep(
                prog, attrs_flat, acc, ctx.aux, dev, row_active,
                has_weights=hw, aux_batched=ctx.aux_batched,
            )
        cur = nxt
    return acc


def _iteration_packed(ctx: _RunContext, attrs, active, meters: Meters):
    """One update sweep as ~4 XLA dispatches, for any of SPU/DPU/MPU.

    pre-iteration globals → one accumulator init → one scan over the
    packed tiles (or one per streamed tile chunk under host residency) →
    one batched apply. The per-strategy slow-tier meters are charged from
    the packed metadata before the compiled pass runs.

    Traced phases (``ctx.trace``): ``sweep.plan`` (rows, meter charges,
    pre-iteration globals, tile activity), ``sweep.scan`` /
    ``sweep.chunk`` (the scan dispatches), ``sweep.apply`` (the apply
    dispatch) and ``sweep.sync`` (the host read of ``changed``).
    """
    sess, prog = ctx.session, ctx.program
    g = sess.graph
    K = ctx.K
    strategy = ctx.choice.strategy
    with _TRACER.span("sweep.plan") if ctx.trace else NO_SPAN:
        rows = _rows_to_process(ctx, active)
        if strategy == "spu":
            _charge_packed_spu(ctx, rows, meters)
        else:
            _charge_packed_two_phase(
                ctx, rows, meters, Q=0 if strategy == "dpu" else ctx.choice.Q
            )
        globals_ = _pre_iteration(
            prog, attrs.reshape(K, -1), ctx.aux, aux_batched=ctx.aux_batched
        )
        ident = reduce_identity(prog.reduce, prog.dtype)
        attrs_flat = attrs.reshape(K, g.n_pad)
        acc = jnp.full((K, g.n_pad), ident, prog.dtype)
        row_mask = np.zeros(g.P, dtype=bool)
        row_mask[rows] = True
        row_active = jnp.asarray(row_mask)
        # Selective execution: map the interval frontier onto the tile axis
        # (a tile is active iff any source interval in its span is) and run
        # the sweep compacted to active tiles / active streamed chunks. A
        # full frontier short-circuits to the plain sweep — the same
        # executable as activity="off".
        selective = ctx.activity == "selective" and not row_mask.all()
        tile_active = (
            sess._packed_tile_activity(row_mask) if selective else None
        )
    sweep, apply_all = _packed_jits(jax.default_backend() != "cpu")
    if ctx.residency in ("host", "disk"):
        acc = _packed_host_sweep(
            ctx, attrs_flat, acc, row_active, meters, sweep, tile_active
        )
    else:
        tiles = sess._staged.packed_tiles(sess.packing)
        acc = _sweep_tile_slab(
            ctx, attrs_flat, acc, tiles, row_active, sweep, tile_active
        )
    acc = acc.reshape(K, g.P, g.interval_size)
    with _TRACER.span("sweep.apply") if ctx.trace else NO_SPAN:
        new, changed = apply_all(
            prog, attrs, acc, ctx.aux, globals_, ctx.valid, ctx.tol,
            aux_batched=ctx.aux_batched,
        )
    with _TRACER.span("sweep.sync") if ctx.trace else NO_SPAN:
        return new, np.asarray(changed)


def _batch_aux(prog: VertexProgram, g, kwargs_list: list[dict]) -> tuple[dict, bool]:
    """Build the batch's aux dict: shared, or vmap-stacked per query.

    When every query's ``make_aux`` output is identical (the common case —
    BFS roots and SSSP sources don't enter aux), the shared dict is
    returned with ``aux_batched=False`` and broadcasts across the batch
    exactly as before. When they differ but are stackable (same keys,
    shapes and dtypes — e.g. a batch of ``MaxLabelForward`` plans with
    different masks), every leaf is stacked with a leading ``(K,)`` query
    axis and ``aux_batched=True`` tells the primitives to vmap over it.
    Aux dicts that cannot be stacked raise :class:`TypeError` — silently
    applying query 0's aux to all K (the old behaviour) produced wrong
    results for queries 1..K-1.
    """
    aux_list = [prog.make_aux(g, **kw) for kw in kwargs_list]
    aux0 = aux_list[0]
    if len(aux_list) == 1:
        return aux0, False
    identical = True
    for aux in aux_list[1:]:
        if set(aux) != set(aux0):
            raise TypeError(
                f"aux-incompatible batch for program {prog.name!r}: queries "
                f"produced different aux keys ({sorted(aux0)} vs "
                f"{sorted(aux)}); run these plans individually"
            )
        for k in aux0:
            a, b = np.asarray(aux[k]), np.asarray(aux0[k])
            if a.shape != b.shape or a.dtype != b.dtype:
                raise TypeError(
                    f"aux-incompatible batch for program {prog.name!r}: "
                    f"leaf {k!r} differs in shape/dtype across queries "
                    f"({b.shape}/{b.dtype} vs {a.shape}/{a.dtype}); run "
                    "these plans individually"
                )
            if identical and not np.array_equal(a, b):
                identical = False
    if identical:
        return aux0, False
    stacked = {
        k: jnp.stack([jnp.asarray(a[k]) for a in aux_list]) for k in aux0
    }
    return stacked, True


# ---------------------------------------------------------------------------
# The session.
# ---------------------------------------------------------------------------
def _device_block(host: dict) -> dict:
    """Upload one padded host block (the 'shard file') to the device."""
    return {
        "src_local": jnp.asarray(host["src_local"], jnp.int32),
        "dst_local": jnp.asarray(host["dst_local"], jnp.int32),
        "hub_inv": jnp.asarray(host["hub_inv"], jnp.int32),
        "hub_dst": jnp.asarray(host["hub_dst"], jnp.int32),
        "e_valid": jnp.asarray(host["e"], jnp.int32),
        "u_valid": jnp.asarray(host["u"], jnp.int32),
        "e": host["e"],
        "u": host["u"],
        "u_bucket": host["u_bucket"],
        "weights": (
            None
            if host["weights"] is None
            else jnp.asarray(host["weights"], jnp.float32)
        ),
    }


def _host_block_nbytes(host: dict) -> int:
    """Raw bytes a host→device copy of this block actually ships."""
    total = 0
    for name in ("src_local", "dst_local", "hub_inv", "hub_dst", "weights"):
        arr = host.get(name)
        if arr is not None:
            total += arr.nbytes
    return total


class _StagedGraph:
    """Staged arrays that are a pure function of the graph.

    Shared between every :class:`GraphSession` variant of one graph (e.g.
    different memory budgets / residency modes). The *host* blocks — padded
    numpy sub-shard buffers, the in-memory equivalent of the paper's shard
    files — are built eagerly once; the full *device* mirror is staged
    lazily, only when a device-resident session first needs it, so
    host-streamed sessions never upload the whole graph.

    A disk-backed staging (``store`` given — a
    :class:`repro.storage.format.DSSSStore`) takes this one tier lower:
    the host block dict and the packed sweep become read-only **mmap
    views** of the ``.dsss`` file's block/tile segments, so building the
    staging allocates nothing edge-scale and the fetch layer pages data
    in straight from disk.
    """

    def __init__(self, graph: DSSSGraph, store=None):
        self.graph = graph
        self.store = store
        self.host_blocks = (
            store.host_blocks() if store is not None else graph.host_blocks()
        )
        self.block_keys = frozenset(self.host_blocks)
        self._device_blocks: dict[tuple[int, int], dict] | None = None
        self._packed_host: dict[str, Any] = {}  # packing mode -> PackedSweep
        self._packed_tiles: dict[str, dict] = {}  # packing mode -> device leaves
        self._packed_spans: dict[str, tuple] = {}  # mode -> (first_i, last_i)
        self.fused: dict | None = None
        self.kernel_operands: dict[tuple, tuple] = {}

    def device_blocks(self) -> dict[tuple[int, int], dict]:
        """The all-on-device block dict (staged once, residency="device")."""
        if self._device_blocks is None:
            with _TRACER.span(
                "stage_device_blocks", cat="staging",
                blocks=len(self.host_blocks),
            ):
                self._device_blocks = {
                    key: _device_block(host)
                    for key, host in self.host_blocks.items()
                }
        return self._device_blocks

    def packed_host(self, mode: str):
        """The host-side :class:`~repro.core.dsss.PackedSweep`, built once.

        This is the streaming source of truth under host residency (tile
        chunks are sliced straight out of these numpy arrays) and the
        metadata source for meters, stream planning and tests. Disk-backed
        stagings return the store's mmap'd tile section when its packing
        mode matches (a stored graph skips repacking); other modes fall
        back to an in-memory repack of the (mmap-backed) flat arrays.
        """
        packed = self._packed_host.get(mode)
        if packed is None:
            if self.store is not None:
                stored = self.store.packed()
                if stored is not None and stored.mode == mode:
                    packed = stored
            if packed is None:
                with _TRACER.span(
                    "stage_packed_host", cat="staging", mode=mode
                ) as span:
                    packed = self.graph.packed_sweep(mode)
                    if _TRACER.enabled:
                        span.set(
                            tile_edges=int(packed.tile_edges),
                            num_tiles=int(packed.num_tiles),
                            padded_edge_slots=int(packed.padded_edge_slots),
                        )
            self._packed_host[mode] = packed
        return packed

    def packed_tiles(self, mode: str) -> dict:
        """Device arrays of the tile-packed sweep layout, staged once.

        The scan carries exactly these leaves per tile (global endpoint
        ids, windowed run slots, the run→destination scatter map, weights
        when present, and the valid edge count); per-tile metadata
        (``base_slot``/``u``/``row_offset``/intervals) stays host-side on
        the :class:`~repro.core.dsss.PackedSweep` for meter accounting
        and stream planning. Packed device mode never stages the per-block
        device mirror — these arrays *are* the device topology.
        """
        tiles = self._packed_tiles.get(mode)
        if tiles is None:
            from repro.kernels.ops import prepare_packed_tiles

            packed = self.packed_host(mode)
            with _TRACER.span(
                "stage_packed_tiles", cat="staging",
                mode=mode, tiles=int(packed.num_tiles),
            ):
                tiles = prepare_packed_tiles(
                    packed, has_weights=packed.weights is not None
                )
            self._packed_tiles[mode] = tiles
        return tiles

    def packed_spans(self, mode: str) -> tuple:
        """Per-tile inclusive source-interval spans, computed once.

        The ``(first_i, last_i)`` arrays of
        :func:`repro.core.dsss.tile_source_spans` — the host-side
        metadata selective execution folds the (P,) interval frontier
        onto the tile axis with, each sweep, in O(P + NT).
        """
        spans = self._packed_spans.get(mode)
        if spans is None:
            spans = tile_source_spans(
                self.packed_host(mode), self.graph.interval_size
            )
            self._packed_spans[mode] = spans
        return spans


class _BlockFetcher:
    """Per-run edge-block access layer — the enforcement point of residency.

    Every schedule body obtains sub-shard blocks exclusively through this
    object, in its declared sweep order, so edge byte meters are charged
    where the data actually moves instead of being recomputed per strategy:

    * ``residency="device"``: blocks come from the staged device mirror;
      a fetch of a key outside the resident set charges ``e·Be`` model
      bytes (the simulated slow tier — seed behaviour, unchanged).
    * ``residency="host"``: only the resident set is device-pinned.
      Fetching any other key performs a real host→device copy of the
      pinned host buffer, double-buffered: while block t computes, block
      t+1's transfer is already in flight (``jax.device_put`` is async).
      The charge is the same ``e·Be`` — it now *is* the transfer — and
      ``bytes_h2d`` additionally records the raw padded bytes shipped.
    * ``residency="disk"``: identical streaming discipline, but the host
      buffers are mmap views of the ``.dsss`` store. A fetch of a block
      that is neither device-pinned nor in the ``host_memory_budget``'s
      RAM cache touches the file and charges its raw padded bytes to
      ``bytes_disk_read`` at this — the mmap-fetch — layer; RAM-cached
      blocks are served from materialized copies free of disk charge.
      The model meters are charged exactly as under "host", so the
      modelled contract is residency-invariant.

    The streaming ring holds at most one prefetched block beyond the one
    in use, so peak device topology bytes stay ≤ resident + 2 blocks.
    """

    def __init__(
        self,
        session: "GraphSession",
        compiled: CompiledPlan,
        meters: Meters,
        pinned: dict[tuple[int, int], dict],
    ):
        self._session = session
        self._inj = session._injector
        self._resident = compiled.resident
        self._host_mode = compiled.residency in ("host", "disk")
        self._disk_mode = compiled.residency == "disk"
        self._host_cached = compiled.host_cached
        self._meters = meters
        self._pinned = pinned
        self._ring: dict[tuple[int, int], dict] = {}
        self._order: list[tuple[int, int]] = []
        self._pos = 0
        Be = session.Be
        host = session._staged.host_blocks
        self._model_bytes = {k: h["e"] * Be for k, h in host.items()}
        if self._host_mode:
            self._pinned_model = float(
                sum(self._model_bytes[k] for k in pinned)
            )
            # The pinned resident set occupies the device for the whole
            # run, whether or not any block is streamed on top of it.
            meters.peak_device_graph_bytes = max(
                meters.peak_device_graph_bytes, self._pinned_model
            )
        else:
            # Everything is device-resident: the high-water mark is the
            # whole staged topology, reported once up front.
            total = float(sum(self._model_bytes.values()))
            meters.peak_device_graph_bytes = max(
                meters.peak_device_graph_bytes, total
            )

    def begin(self, order: list[tuple[int, int]]) -> Callable[[], dict]:
        """Declare this sweep's block order; returns the sequential fetch.

        The first streamed block's transfer is issued immediately so the
        sweep starts with its double buffer warm.
        """
        self._order = order
        self._pos = 0
        if self._host_mode and order:
            self._prefetch(order[0])
        return self._next

    def _host_source(self, key: tuple[int, int]) -> dict:
        """The host-side buffers a streamed fetch ships — and the disk
        charge, levied exactly where the mmap pages are touched."""
        if self._disk_mode:
            if key in self._host_cached:
                return self._session._host_cache_block(key)
            host = self._session._staged.host_blocks[key]
            nb = _host_block_nbytes(host)
            self._meters.bytes_disk_read += nb
            _OBS_DISK.inc(nb)
            return host
        return self._session._staged.host_blocks[key]

    def _upload(self, key: tuple[int, int], host: dict) -> dict:
        """One host→device block transfer — the "h2d" injection boundary.

        Injected transient faults are retried in place (bounded, with
        backoff) so rate-based fault plans heal at the I/O layer; only a
        fault burst deeper than the retry budget escapes to the caller
        (where serving-level retry takes over). The ``bytes_h2d`` charge
        lands after success, so meters are identical however many retries
        it took.
        """
        blk = with_transient_retries(
            self._inj, f"block:{key[0]},{key[1]}", lambda: _device_block(host)
        )
        nb = _host_block_nbytes(host)
        self._meters.bytes_h2d += nb
        _OBS_H2D.inc(nb)
        return blk

    def _prefetch(self, key: tuple[int, int]) -> None:
        if key in self._pinned or key in self._ring:
            return
        self._ring[key] = self._upload(key, self._host_source(key))

    def _next(self) -> dict:
        key = self._order[self._pos]
        self._pos += 1
        if not self._host_mode:
            if key not in self._resident:
                self._meters.bytes_read_edges += self._model_bytes[key]
            return self._session._staged.device_blocks()[key]
        blk = self._pinned.get(key)
        if blk is not None:
            if self._pos < len(self._order):
                self._prefetch(self._order[self._pos])
            return blk
        blk = self._ring.pop(key, None)
        if blk is None:  # cold start / out-of-order access
            blk = self._upload(key, self._host_source(key))
        if self._pos < len(self._order):
            self._prefetch(self._order[self._pos])
        self._meters.bytes_read_edges += self._model_bytes[key]
        live = (
            self._pinned_model
            + self._model_bytes[key]
            + sum(self._model_bytes[k] for k in self._ring)
        )
        self._meters.peak_device_graph_bytes = max(
            self._meters.peak_device_graph_bytes, live
        )
        return blk


class GraphSession:
    """Staged graph state shared by every run.

    Args:
      graph: sharded :class:`DSSSGraph`.
      memory_budget: bytes of fast-tier memory (B_M). ``None`` = unlimited.
      residency: where sub-shard edge blocks live between sweeps.

        * ``"device"`` — every block is staged to the device once (the
          seed behaviour). ``memory_budget`` only parameterizes the
          *modelled* byte meters and the adaptive strategy choice.
        * ``"host"`` — the budget is **enforced**: only the resident set
          that :meth:`_resolve_residency` computes from ``memory_budget``
          is device-pinned; every other block stays a pinned host (numpy)
          buffer and is streamed to the device per sweep with
          double-buffered prefetch, in the schedule's sequential sub-shard
          order. Results are bit-identical to ``"device"`` and the
          modelled byte meters are unchanged — they now coincide with the
          real transfers (``Meters.bytes_h2d`` reports the raw bytes).
          Vertex-attribute state (``2·n_pad·Ba``) and hub state remain
          fast-tier resident; their slow-tier traffic under DPU/MPU
          remains modelled, as in the paper. The ``"fused"`` strategy is
          the explicitly device-resident fast path and ignores residency.
        * ``"disk"`` — the third tier (disk-backed sessions only; open
          one with :meth:`GraphSession.open`): host blocks and packed
          tiles are mmap views of a ``.dsss`` store, streamed
          disk→device by the same machinery as ``"host"``. The
          three-level budget applies: ``memory_budget`` pins device
          topology exactly as under "host", ``host_memory_budget``
          bounds a RAM cache of blocks / tile chunks (in streaming
          order, after the device pins; ``None`` caches everything), and
          every fetch outside both charges ``Meters.bytes_disk_read`` at
          the mmap layer. Results are bit-identical and the model meters
          field-identical to the other residencies.
        * ``"auto"`` — ``"disk"`` for disk-backed sessions; otherwise
          ``"host"`` when a ``memory_budget`` is set, ``"device"``
          otherwise (an unlimited budget pins everything, making the two
          modes identical).

      execution: how the SPU/DPU/MPU schedules drive the device.

        * ``"per_block"`` — the host-scheduled legacy path: one jit
          dispatch per sub-shard through :class:`_BlockFetcher` (O(P²)
          host round-trips per sweep). Always used for custom/fused
          strategies.
        * ``"packed"`` — the compiled sweep path: the
          :class:`repro.core.dsss.PackedSweep` tile layout is staged once
          and every update sweep runs as one ``lax.scan`` + one batched
          apply (~4 dispatches per sweep, independent of P). Under host
          residency the tile stream is chunked and streamed host→device
          with double-buffered prefetch (see
          :meth:`packed_stream_plan`) instead of staging — packed
          execution no longer downgrades out-of-core. Bit-identical
          results and field-for-field identical *model* meters either
          way (``bytes_h2d``/``peak_device_graph_bytes`` report the
          physical transfers of whichever path ran). Custom and fused
          schedules downgrade to ``"per_block"`` (they own their loop).
        * ``"auto"`` (default) — ``"packed"`` wherever packed applies,
          ``"per_block"`` where it does not.

      packing: tile layout for the packed path — ``"adaptive"``
        (destination-aligned fixed-size tiles, chosen per graph to bound
        padding; the default for DSSS layouts), ``"subshard"`` (legacy
        one-tile-per-largest-sub-shard; forced for ``src_sorted`` graphs,
        whose scrambled destination runs only whole-sub-shard windows
        reduce correctly), or ``"auto"``.

      Be: bytes per edge in the I/O model (8 = two int32 ids; +4 is added
        automatically for weighted graphs).
      Bv: bytes per vertex id.

    Host-side staging happens once in ``__init__`` (padded per-sub-shard
    numpy buffers — the 'shard files'); device staging is all-at-once for
    ``"device"`` residency and budget-bounded for ``"host"``. Plans are
    compiled lazily and cached, so repeated ``run``/``run_batch`` calls
    re-use the staged blocks and the jit executables.
    """

    _strategies: dict[str, Callable] = {
        "spu": _iteration_spu,
        "dpu": _iteration_dpu,
        "mpu": _iteration_mpu,
        "fused": _iteration_fused,
    }

    def __init__(
        self,
        graph: DSSSGraph,
        *,
        memory_budget: int | None = None,
        residency: str = "auto",
        execution: str = "auto",
        packing: str = "auto",
        Be: int = 8,
        Bv: int = 4,
        staged: _StagedGraph | None = None,
        host_memory_budget: int | None = None,
        fault_plan: FaultPlan | None = None,
    ):
        if residency not in ("device", "host", "disk", "auto"):
            raise ValueError(
                "residency must be 'device', 'host', 'disk' or 'auto', "
                f"got {residency!r}"
            )
        if execution not in ("per_block", "packed", "auto"):
            raise ValueError(
                "execution must be 'per_block', 'packed' or 'auto', "
                f"got {execution!r}"
            )
        if packing not in ("adaptive", "subshard", "auto"):
            raise ValueError(
                "packing must be 'adaptive', 'subshard' or 'auto', "
                f"got {packing!r}"
            )
        self.graph = graph
        self.memory_budget = memory_budget
        self.residency = residency
        self.execution = execution
        # Tile-packing layout for the compiled sweep path: "adaptive"
        # (destination-aligned fixed-size tiles) wherever the DSSS layout
        # allows it; src_sorted (GraphChi-like) graphs scramble destination
        # runs inside blocks, so only the whole-sub-shard packing groups
        # their per-destination reduces correctly.
        if packing == "auto":
            packing = "subshard" if graph.src_sorted else "adaptive"
        elif packing == "adaptive" and graph.src_sorted:
            raise ValueError(
                "packing='adaptive' requires destination-sorted sub-shards; "
                "src_sorted graphs support only packing='subshard'"
            )
        self.packing = packing
        self.has_weights = graph.weights is not None
        self.Be = Be + (4 if self.has_weights else 0)
        self.Bv = Bv
        self._hub_d = graph.mean_hub_in_degree()
        if staged is not None and staged.graph is not graph:
            raise ValueError("staged arrays belong to a different graph")
        self._staged = staged if staged is not None else _StagedGraph(graph)
        self._store = self._staged.store
        if residency == "disk" and self._store is None:
            raise ValueError(
                "residency='disk' requires a disk-backed session — open the "
                "graph from a .dsss container with GraphSession.open(path) "
                "(see repro.storage)"
            )
        if host_memory_budget is not None and self._store is None:
            raise ValueError(
                "host_memory_budget is the disk tier's RAM-cache bound and "
                "only applies to disk-backed sessions (GraphSession.open); "
                "in-memory sessions are bounded by memory_budget alone"
            )
        self.host_memory_budget = host_memory_budget
        # One live injector shared by every layer of this session (engine
        # loop, block fetcher, packed stream, backing store) so per-spec
        # fire budgets are spent once, globally.
        self._injector = None
        if fault_plan is not None:
            self.inject_faults(fault_plan)
        self._residency: dict[int, frozenset] = {}  # Ba -> resident set
        self._compiled: dict[tuple, CompiledPlan] = {}
        self._pinned: dict[tuple[int, int], dict] = {}  # host mode device pins
        # Packed host-mode pins: (pin_tiles, device leaves, model, actual).
        self._packed_pins: tuple[int, dict | None, float, float] | None = None
        self._stream_plans: dict[tuple[bool, int], PackedStreamPlan] = {}
        # Disk-tier RAM caches (the host_memory_budget mid tier): blocks /
        # packed tile chunks materialized out of the mmap'd store, bounded
        # by _resolve_host_cache / PackedStreamPlan.host_tiles.
        self._host_cache: dict[tuple[int, int], dict] = {}
        self._packed_ram: dict[tuple[int, int], dict] = {}

    @classmethod
    def open(
        cls,
        path: str,
        *,
        memory_budget: int | None = None,
        host_memory_budget: int | None = None,
        residency: str = "auto",
        execution: str = "auto",
        packing: str = "auto",
        Be: int = 8,
        Bv: int = 4,
        verify: bool = True,
        fault_plan: FaultPlan | None = None,
        read_policy=None,
    ) -> "GraphSession":
        """Open a ``.dsss`` container as a disk-backed session.

        The graph, its padded sub-shard blocks and its stored packed tile
        layout all become mmap views of the file — nothing edge-scale is
        materialized in host RAM, and ``residency`` defaults (via
        ``"auto"``) to ``"disk"``: sweeps stream blocks / tile chunks
        disk→device under the three-level
        ``memory_budget`` / ``host_memory_budget`` hierarchy.
        ``verify=True`` (default) checks every segment checksum first — a
        truncated or bit-flipped file fails loudly instead of computing
        garbage; pass ``verify=False`` to skip the full-file read for
        very large graphs.

        ``read_policy`` (a :class:`repro.storage.format.ReadPolicy`)
        enables *self-healing* segment reads instead: each block/tile
        segment is checksum-verified on first touch with bounded re-read +
        backoff, and a segment that stays bad is quarantined behind a
        structured :class:`repro.storage.format.DegradedReadError` — the
        fetch layer never returns garbage. ``fault_plan`` attaches a
        :class:`repro.reliability.FaultPlan` injector to the session and
        its store (see :meth:`inject_faults`).
        """
        from repro.storage.format import open_dsss

        store = open_dsss(path, verify=verify, read_policy=read_policy)
        graph = store.graph()
        return cls(
            graph,
            memory_budget=memory_budget,
            residency=residency,
            execution=execution,
            packing=packing,
            Be=Be,
            Bv=Bv,
            staged=_StagedGraph(graph, store=store),
            host_memory_budget=host_memory_budget,
            fault_plan=fault_plan,
        )

    @property
    def store(self):
        """The backing :class:`repro.storage.format.DSSSStore` (or None)."""
        return self._store

    def inject_faults(self, plan: FaultPlan | None) -> None:
        """Attach (or clear, with ``None``) a deterministic fault plan.

        Builds one live :class:`repro.reliability.FaultInjector` shared by
        the engine loop (``"sweep"`` site), the block fetcher / packed
        chunk streamer (``"h2d"`` site) and the backing ``.dsss`` store
        (``"storage"`` site), so a plan's fire budgets are accounted once
        across layers.
        """
        self._injector = plan.injector() if plan is not None else None
        if self._store is not None:
            self._store.attach_faults(self._injector)

    @property
    def fault_injector(self):
        """The live injector of the attached fault plan (or None)."""
        return self._injector

    def _heal_store_segments(self, prefix: str) -> None:
        """Verify-on-first-touch for the store segments a stream reads.

        Disk-residency self-healing: before the fetch layer pages block
        (``blk_*``) or packed tile (``p_*``) data out of the mmap, every
        backing segment is checksum-verified once — with bounded re-read +
        backoff under the store's :class:`~repro.storage.format.ReadPolicy`
        — so a torn read heals and persistent corruption surfaces as a
        structured :class:`~repro.storage.format.DegradedReadError` instead
        of garbage results. No-op without a read policy (the
        ``open(verify=True)`` whole-file check is then the only guard) and
        after the first touch (verified segments are remembered).
        """
        store = self._store
        if store is None or store.read_policy is None:
            return
        store.ensure_segments(
            n for n in store.segments if n.startswith(prefix)
        )

    @property
    def block_keys(self) -> frozenset:
        """Keys of the non-empty sub-shards (placement-independent)."""
        return self._staged.block_keys

    @property
    def host_blocks(self) -> dict[tuple[int, int], dict]:
        """The padded numpy 'shard files' (always present, never uploaded)."""
        return self._staged.host_blocks

    @property
    def blocks(self) -> dict[tuple[int, int], dict]:
        """Back-compat staged-block view.

        Under ``"device"``/``"auto"``-without-budget residency this is the
        all-on-device dict (staged once); under enforced ``"host"`` or
        ``"disk"`` residency it is the host-side dict (numpy buffers or
        mmap views) — returning the device mirror here would silently
        stage the whole graph and break the budget.
        """
        if self.resolved_residency() in ("host", "disk"):
            return self._staged.host_blocks
        return self._staged.device_blocks()

    def resolved_residency(self, override: str | None = None) -> str:
        """Resolve the residency axis to 'device', 'host' or 'disk'."""
        mode = override or self.residency
        if mode == "auto":
            if self._store is not None:
                mode = "disk"
            else:
                mode = "host" if self.memory_budget is not None else "device"
        if mode == "disk" and self._store is None:
            raise ValueError(
                "residency='disk' requires a disk-backed session — open the "
                "graph with GraphSession.open(path)"
            )
        return mode

    def resolved_execution(
        self,
        strategy: str,
        residency: str,
        override: str | None = None,
    ) -> str:
        """Resolve the execution axis: 'per_block' | 'packed'.

        ``strategy`` must already be resolved (a schedule name, not
        "auto") and ``residency`` must be 'device' or 'host'. The packed
        paths apply to the native block schedules (SPU/DPU/MPU) under
        *both* residencies — under "host" the tile chunks are streamed
        with double-buffered prefetch instead of the per-block fetcher, so
        out-of-core runs no longer downgrade. ``"auto"`` resolves to the
        XLA scan ``"packed"`` on every platform. The fused fast path and
        custom registered schedules run per-block even when ``"packed"``
        was requested explicitly (a forgiving downgrade, like
        residency="auto": results and meters are identical).
        """
        mode = override or self.execution
        applies = strategy in ("spu", "dpu", "mpu")
        if not applies:
            return "per_block"
        if mode == "auto":
            return "packed"
        return mode

    # -- budget accounting ---------------------------------------------------
    def staged_host_bytes(self) -> int:
        """Raw host RAM the staged graph currently occupies (pool accounting).

        In-memory sessions: the padded numpy 'shard files' — the dominant
        per-graph staging cost a :class:`repro.serving.pool.SessionPool`
        charges against its capacity. Disk-backed sessions: the mmap views
        cost nothing resident, so only the materialized RAM caches (the
        ``host_memory_budget`` mid tier) count — the figure grows as
        cached blocks / tile chunks are first touched.
        """
        if self._store is not None:
            total = sum(
                _host_block_nbytes(b) for b in self._host_cache.values()
            )
            total += sum(
                sum(a.nbytes for a in chunk.values())
                for chunk in self._packed_ram.values()
            )
            return int(total)
        return int(
            sum(_host_block_nbytes(b) for b in self.host_blocks.values())
        )

    def pinned_device_bytes(self) -> tuple[float, float]:
        """(model, actual) bytes of the currently device-pinned topology.

        Covers both pinning mechanisms — per-block pins (per-block host
        execution) and the packed tile-prefix pins (packed host execution);
        at most one is populated at a time (each releases the other).
        Model bytes use the I/O-model accounting (``e·Be`` real edges, the
        same units as ``memory_budget``); actual bytes are the raw padded
        buffer sizes (bucket/tile padding makes them larger).
        """
        model = float(
            sum(self.host_blocks[k]["e"] * self.Be for k in self._pinned)
        )
        actual = float(
            sum(_host_block_nbytes(self.host_blocks[k]) for k in self._pinned)
        )
        if self._packed_pins is not None:
            model += self._packed_pins[2]
            actual += self._packed_pins[3]
        return model, actual

    def packed_stream_plan(self, strategy: str, Ba: int) -> PackedStreamPlan:
        """Tile placement for packed execution under host residency.

        Mirrors :meth:`_resolve_residency`'s budget semantics at tile
        granularity: for SPU the budget leftover after both attribute
        copies (``2·n_pad·Ba``) pins a prefix of the tile stream; DPU/MPU
        pin no edge topology (their Table II model streams ``m·Be`` every
        sweep). The streamed remainder is chunked to at most
        ``min(256 KiB, budget/4)`` of tile data per chunk (never below one
        tile), so tight budgets stream tile-by-tile while generous ones
        amortise dispatches — the double buffer keeps ≤ 2 chunks in
        flight.

        The target was set for CPU-sized tiles. On a Graph500 scale-21
        graph (adaptive tiles of 65,536 slots, 512 KiB of model bytes
        each) it yields one tile per chunk: 554 chunks, each its own
        ``device_put`` and scan dispatch, per sweep of 971 tiles with
        417 pinned (one TPU v5e). Where the packer picks 32,768-slot
        tiles (a graph whose largest destination run fits them), each
        half-size tile is still its own chunk: 1,109 per sweep, and the
        host fell behind the device (idle 18 % of a job). Coarser chunks
        are the first lever on the streamed sweep's host cost.
        """
        pins_apply = strategy == "spu"
        key = (pins_apply, Ba)
        plan = self._stream_plans.get(key)
        if plan is not None:
            return plan
        packed = self._staged.packed_host(self.packing)
        nt, T = packed.num_tiles, packed.tile_edges
        Be = self.Be
        cum = np.cumsum(packed.e_valid.astype(np.int64)) * Be
        if self.memory_budget is None:
            pin = nt
        elif pins_apply:
            leftover = self.memory_budget - 2 * self.graph.n_pad * Ba
            pin = int(np.searchsorted(cum, leftover, side="right"))
        else:
            pin = 0
        pin_model = float(cum[pin - 1]) if pin else 0.0
        tile_bytes = max(T * Be, 1)
        target = 256 * 1024
        if self.memory_budget is not None:
            target = min(target, max(self.memory_budget // 4, tile_bytes))
        chunk = max(1, min(int(target // tile_bytes), max(nt - pin, 1)))
        max_chunk = 0.0
        for lo in range(pin, nt, chunk):
            hi = min(lo + chunk, nt)
            hi_cum = float(cum[hi - 1])
            lo_cum = float(cum[lo - 1]) if lo else 0.0
            max_chunk = max(max_chunk, hi_cum - lo_cum)
        # Disk tier's mid level: whole streamed chunks, in order, that the
        # host_memory_budget keeps materialized in RAM (chunk-aligned so a
        # chunk is either fully cached or fully mmap-streamed).
        host_tiles = 0
        if self._store is not None:
            if self.host_memory_budget is None:
                host_tiles = nt - pin
            else:
                per_edge = PACKED_SLOT_BYTES + (4 if self.has_weights else 0)
                leftover = self.host_memory_budget
                for lo in range(pin, nt, chunk):
                    hi = min(lo + chunk, nt)
                    raw = (hi - lo) * (T * per_edge + 4)
                    if leftover < raw:
                        break
                    leftover -= raw
                    host_tiles += hi - lo
        plan = PackedStreamPlan(
            pin_tiles=pin,
            chunk_tiles=chunk,
            num_tiles=nt,
            tile_edges=T,
            pin_model_bytes=pin_model,
            max_chunk_model_bytes=max_chunk,
            host_tiles=host_tiles,
        )
        self._stream_plans[key] = plan
        return plan

    def _packed_tile_activity(self, row_active: np.ndarray) -> np.ndarray:
        """(NT,) bool tile-activity map for this sweep's interval frontier.

        Derived from the previous sweep's ``changed`` output (the (P,)
        ``row_active`` bitmap) and the packed layout's per-tile source
        spans — see :func:`repro.core.dsss.active_tile_mask`. Conservative
        for coalesced tiles whose span covers an empty-but-active-counted
        interval (processed unnecessarily, never skipped wrongly).
        """
        first, last = self._staged.packed_spans(self.packing)
        return active_tile_mask(row_active, first, last)

    def _ensure_packed_pins(self, pin_tiles: int) -> tuple[dict | None, float]:
        """Device-pin exactly the leading ``pin_tiles`` tiles (host mode).

        Returns ``(device leaves or None, model bytes)``. Like
        :meth:`_ensure_pinned`, a changed pin count releases the previous
        device copies first; the per-block pin dict is also released (the
        two mechanisms must never both occupy the device).
        """
        self._pinned.clear()
        if self._packed_pins is not None and self._packed_pins[0] == pin_tiles:
            return self._packed_pins[1], self._packed_pins[2]
        self._packed_pins = None
        if pin_tiles <= 0:
            self._packed_pins = (0, None, 0.0, 0.0)
            return None, 0.0
        packed = self._staged.packed_host(self.packing)
        with _TRACER.span(
            "stage_packed_pins", cat="staging", tiles=pin_tiles
        ):
            host = _packed_host_chunk(packed, 0, pin_tiles, self.has_weights)
            dev = jax.device_put(host)
        model = float(packed.e_valid[:pin_tiles].sum()) * self.Be
        actual = float(_chunk_nbytes(host))
        self._packed_pins = (pin_tiles, dev, model, actual)
        return dev, model

    # -- strategy registry ---------------------------------------------------
    @classmethod
    def register_strategy(cls, name: str, iteration_fn: Callable) -> None:
        """Register a custom per-iteration schedule (e.g. a baseline).

        ``iteration_fn(ctx, attrs, active, meters) -> (attrs, active_next)``
        with ``attrs`` shaped ``(K, P, interval_size)`` and ``active``
        ``(K, P)`` bool.
        """
        cls._strategies[name] = iteration_fn

    # -- staging -------------------------------------------------------------
    def fused_arrays(self) -> dict:
        """Whole-graph edge arrays for the fused path, staged lazily once."""
        if self._staged.fused is None:
            g = self.graph
            with _TRACER.span(
                "stage_fused", cat="staging", m=int(g.m)
            ):
                self._staged.fused = dict(
                    src=jnp.asarray(g.src, jnp.int32),
                    dst=jnp.asarray(g.dst, jnp.int32),
                    weights=(
                        None if g.weights is None else jnp.asarray(g.weights)
                    ),
                )
        return self._staged.fused

    def kernel_operands(
        self, i: int, j: int, dtype, *, gather_op: str = "mul", reduce: str = "sum"
    ) -> tuple:
        """Pallas-kernel operands for SS[i, j], staged once per semiring.

        Returns ``(src_idx, hub_inv, weights, block_base)`` as produced by
        :func:`repro.kernels.ops.prepare_subshard_operands` — the TPU hot
        path equivalent of the staged jnp blocks.
        """
        key = (i, j, str(jnp.dtype(dtype)), gather_op, reduce)
        ops = self._staged.kernel_operands.get(key)
        if ops is None:
            from repro.kernels.ops import prepare_from_host_block

            # Stage from the already-built host buffer (shared with the
            # streaming path) instead of re-slicing the flat edge arrays.
            ops = prepare_from_host_block(
                self.host_blocks[(i, j)], dtype, gather_op=gather_op, reduce=reduce
            )
            self._staged.kernel_operands[key] = ops
        return ops

    # -- plan compilation ----------------------------------------------------
    def params_for(self, program: VertexProgram) -> IOParams:
        g = self.graph
        return IOParams(
            n=g.n, m=g.m, Ba=program.attr_bytes, Bv=self.Bv, Be=self.Be,
            d=self._hub_d, P=g.P,
        )

    def compile(self, plan: ExecutionPlan) -> CompiledPlan:
        """Resolve a plan's strategy + residency + execution + activity
        (cached)."""
        key = (
            plan.strategy, plan.program.attr_bytes, plan.residency,
            plan.execution, plan.activity, plan.program.monotone,
        )
        compiled = self._compiled.get(key)
        if compiled is None:
            params = self.params_for(plan.program)
            choice = self._resolve_choice(plan.strategy, params)
            residency = self.resolved_residency(plan.residency)
            compiled = CompiledPlan(
                params=params,
                choice=choice,
                resident=self._resolve_residency(plan.strategy, params),
                residency=residency,
                execution=self.resolved_execution(
                    choice.strategy, residency, plan.execution
                ),
                host_cached=(
                    self._resolve_host_cache(plan.strategy, params)
                    if residency == "disk"
                    else frozenset()
                ),
                activity=(
                    "selective"
                    if plan.program.monotone and plan.activity != "off"
                    else "off"
                ),
            )
            self._compiled[key] = compiled
        return compiled

    def _resolve_choice(self, strategy: str, params: IOParams) -> StrategyChoice:
        if strategy == "auto":
            # Disk-backed sessions select over the three-tier model: the
            # host_memory_budget mid tier adds the modelled disk re-stream
            # term to each candidate's read (see select_strategy).
            return select_strategy(
                params,
                self.memory_budget,
                host_B_M=(
                    self.host_memory_budget if self._store is not None else None
                ),
            )
        if strategy in ("spu", "dpu", "mpu", "fused"):
            Q = self.graph.P
            if strategy == "dpu":
                Q = 0
            elif strategy == "mpu":
                Q = mpu_q(params, self.memory_budget or 0)
            return StrategyChoice(strategy, Q, 0.0, 0.0)
        if strategy in self._strategies:
            return StrategyChoice(strategy, 0, 0.0, 0.0)
        raise ValueError(f"unknown strategy {strategy!r}")

    def _resolve_residency(self, strategy: str, params: IOParams) -> frozenset:
        """The single source of truth for which sub-shards the memory budget
        pins in the fast tier.

        SPU: both attribute copies (``2·n_pad·Ba``) come first; the
        leftover budget pins sub-shards in row-major (schedule) order.
        DPU/MPU: no edge blocks are pinned — attribute/hub state owns the
        fast tier (MPU's Q split governs *interval* residency, which stays
        attribute-side) and every edge block is streamed, exactly as the
        Table II ``m·Be`` read term assumes. Under ``residency="host"``
        this set is physically enforced by :class:`_BlockFetcher`; under
        ``"device"`` it drives the modelled meters only.
        """
        choice_strategy = (
            self._resolve_choice(strategy, params).strategy
            if strategy == "auto"
            else strategy
        )
        if choice_strategy != "spu":
            return frozenset()
        resident = self._residency.get(params.Ba)
        if resident is not None:
            return resident
        if self.memory_budget is None:
            resident = frozenset(self.block_keys)
        else:
            picked = set()
            host = self.host_blocks
            leftover = self.memory_budget - 2 * self.graph.n_pad * params.Ba
            for key in sorted(host):  # row-major, as the SPU schedule runs
                cost = host[key]["e"] * self.Be
                if leftover >= cost:
                    picked.add(key)
                    leftover -= cost
            resident = frozenset(picked)
        self._residency[params.Ba] = resident
        return resident

    def _resolve_host_cache(self, strategy: str, params: IOParams) -> frozenset:
        """The mid tier of the three-level budget (disk residency only).

        Which sub-shards the ``host_memory_budget`` keeps materialized in
        host RAM, picked in the schedules' row-major streaming order over
        the blocks the device budget did *not* pin, costed at their raw
        padded-buffer bytes (what the RAM copy actually occupies).
        ``host_memory_budget=None`` caches everything — the unlimited
        default mirrors ``memory_budget`` semantics. Fetches of cached
        blocks charge no ``bytes_disk_read``; with both budgets bounded,
        per-sweep disk traffic is exactly the ``disk_read_bytes`` closed
        form over the remaining blocks.
        """
        if self._store is None:
            return frozenset()
        resident = self._resolve_residency(strategy, params)
        host = self.host_blocks
        if self.host_memory_budget is None:
            return frozenset(k for k in host if k not in resident)
        picked = set()
        leftover = self.host_memory_budget
        for key in sorted(host):  # row-major, as the schedules stream
            if key in resident:
                continue
            cost = _host_block_nbytes(host[key])
            if leftover >= cost:
                picked.add(key)
                leftover -= cost
        return frozenset(picked)

    def _host_cache_block(self, key: tuple[int, int]) -> dict:
        """RAM-materialized copy of one mmap-backed block (built once)."""
        blk = self._host_cache.get(key)
        if blk is None:
            host = self._staged.host_blocks[key]
            blk = {
                k: (np.array(v) if isinstance(v, np.ndarray) else v)
                for k, v in host.items()
            }
            self._host_cache[key] = blk
        return blk

    def _packed_ram_chunk(self, lo: int, hi: int) -> dict:
        """RAM-materialized copy of one mmap-backed tile chunk (built once)."""
        chunk = self._packed_ram.get((lo, hi))
        if chunk is None:
            packed = self._staged.packed_host(self.packing)
            view = _packed_host_chunk(packed, lo, hi, self.has_weights)
            chunk = {k: np.array(v) for k, v in view.items()}
            self._packed_ram[(lo, hi)] = chunk
        return chunk

    def _ensure_pinned(self, resident: frozenset) -> dict[tuple[int, int], dict]:
        """Device-pin exactly the resident set (host residency only).

        Blocks leaving the resident set are released so successive plans
        with different strategies/budgets cannot accumulate device copies
        past the budget; blocks entering it are uploaded once and reused
        across runs. Packed tile pins are released for the same reason —
        only one pinning mechanism may occupy the device at a time.
        """
        self._packed_pins = None
        for key in [k for k in self._pinned if k not in resident]:
            del self._pinned[key]
        todo = [
            key
            for key in sorted(resident)
            if key in self.block_keys and key not in self._pinned
        ]
        if todo:
            with _TRACER.span(
                "stage_pins", cat="staging", blocks=len(todo)
            ):
                for key in todo:
                    self._pinned[key] = _device_block(self.host_blocks[key])
        return self._pinned

    def _interval_aux(self, aux: dict, k: int, batched: bool = False) -> dict:
        """Interval k's view of the aux dict.

        ``batched=True`` slices per-query ``(K, n_pad)`` leaves to
        ``(K, isz)`` — the leading query axis survives so the primitives'
        ``aux_batched`` vmap maps over it; scalars pass through either way.
        """
        isz = self.graph.interval_size
        if batched:
            return {
                key: (
                    v[:, k * isz : (k + 1) * isz]
                    if getattr(v, "ndim", 0) == 2
                    else v
                )
                for key, v in aux.items()
            }
        return {
            key: (v[k * isz : (k + 1) * isz] if getattr(v, "ndim", 0) == 1 else v)
            for key, v in aux.items()
        }

    # -- execution -----------------------------------------------------------
    def run(
        self,
        plan: ExecutionPlan,
        *,
        resume_from: str | bool | None = None,
        cancel: Callable[[int], None] | None = None,
    ) -> Result:
        """Execute one plan against the staged graph.

        ``resume_from`` restores a sweep-level snapshot and continues:
        a snapshot path, a checkpoint directory (its latest snapshot; an
        empty/missing directory starts fresh — the restore-latest-or-cold
        policy of the train loop), or ``True`` for the plan's own
        ``checkpoint.directory``. The resumed run is bit-identical to an
        uninterrupted one, with field-identical cumulative meters
        (``wall_seconds`` excepted — real elapsed time accumulates across
        attempts). ``cancel`` is a callable invoked with the completed
        sweep count before every sweep; raising
        :class:`repro.reliability.DeadlineExceeded` from it cancels the
        run cooperatively between sweeps (the serving deadline hook).
        """
        batch = self._execute(
            plan, [plan.kwargs_dict()], resume_from=resume_from, cancel=cancel
        )
        res = batch.results[0]
        assert res.iterations == res.meters.iterations, (
            "Result.iterations is defined as the number of update sweeps "
            "executed and must equal meters.iterations"
        )
        return res

    def run_batch(
        self,
        plans: list[ExecutionPlan],
        *,
        resume_from: str | bool | None = None,
        cancel: Callable[[int], None] | None = None,
    ) -> BatchResult:
        """Execute K plans, sharing one streamed pass over the edge blocks.

        Plans fuse when they share a ``batch_key()`` (program, strategy,
        limits and the residency/execution/activity axes) and their aux
        arrays are identical *or* stackable (same keys/shapes/dtypes —
        e.g. per-query masks); stackable aux runs vmapped with a leading
        query axis on the native SPU/DPU/MPU/fused schedules. Everything
        else falls back to sequential ``run`` calls (``fused=False``);
        results are identical either way. ``resume_from`` / ``cancel``
        behave as in :meth:`run`; a fused batch checkpoints and resumes
        as one unit (the snapshot holds all K queries' state).
        """
        if not plans:
            return BatchResult([], Meters(), 0, True, True)
        if self._fusable(plans):
            return self._execute(
                plans[0],
                [p.kwargs_dict() for p in plans],
                resume_from=resume_from,
                cancel=cancel,
            )
        if resume_from:
            raise ValueError(
                "resume_from requires a fusable batch (one snapshot holds "
                "the whole batch's state); these plans fall back to "
                "sequential runs — resume them individually"
            )
        meters = Meters()
        results = [self.run(p, cancel=cancel) for p in plans]
        for r in results:
            meters.merge(r.meters)
        return BatchResult(
            results=results,
            meters=meters,  # summed, incl. iterations (per_iteration stays true)
            iterations=max(r.iterations for r in results),
            converged=all(r.converged for r in results),
            fused=False,
        )

    def _fusable(self, plans: list[ExecutionPlan]) -> bool:
        head = plans[0]
        if any(p.batch_key() != head.batch_key() for p in plans[1:]):
            return False
        g = self.graph
        aux0 = head.program.make_aux(g, **head.kwargs_dict())
        identical = True
        for p in plans[1:]:
            aux = p.program.make_aux(g, **p.kwargs_dict())
            if set(aux) != set(aux0):
                return False
            for k in aux0:
                a, b = np.asarray(aux[k]), np.asarray(aux0[k])
                if a.shape != b.shape or a.dtype != b.dtype:
                    return False
                if identical and not np.array_equal(a, b):
                    identical = False
        if identical:
            return True
        # Differing-but-stackable aux fuses via the batched-aux vmap,
        # which only the native schedules' primitives implement; custom
        # registered strategies fall back to sequential runs.
        return self.compile(head).choice.strategy in (
            "spu", "dpu", "mpu", "fused",
        )

    def _resolve_resume(
        self, plan: ExecutionPlan, resume_from: str | bool | None
    ) -> str | None:
        """Turn a ``resume_from`` argument into a snapshot path (or None).

        A directory resumes from its latest snapshot — or starts fresh
        when it has none (restore-latest-or-cold, like the train loop);
        ``True`` uses the plan's own checkpoint directory; an explicit
        file path must exist.
        """
        if not resume_from:
            return None
        if resume_from is True:
            if plan.checkpoint is None:
                raise ValueError(
                    "resume_from=True needs plan.checkpoint to name the "
                    "snapshot directory"
                )
            resume_from = plan.checkpoint.directory
        if os.path.isdir(resume_from):
            return latest_snapshot(resume_from)
        if not os.path.exists(resume_from):
            raise SnapshotError(f"{resume_from}: no such snapshot")
        return resume_from

    def _save_sweep_snapshot(
        self, spec, plan, attrs, active, converged_at, sweeps,
        activity_log, meters, wall_seconds,
    ) -> None:
        """Atomically snapshot the full iteration state after one sweep."""
        g = self.graph
        mdict = {
            f.name: getattr(meters, f.name) for f in dataclasses.fields(meters)
        }
        # The live meter keeps accumulating; the snapshot records the real
        # elapsed time as of the save without mutating it.
        mdict["wall_seconds"] = wall_seconds
        meta = {
            "sweeps": sweeps,
            "meters": mdict,
            "program": plan.program.name,
            "K": len(converged_at),
            "P": int(g.P),
            "interval_size": int(g.interval_size),
            "n": int(g.n),
            "m": int(g.m),
        }
        arrays = {
            "attrs": np.asarray(attrs),
            "active": np.asarray(active),
            "activity_log": (
                np.stack(activity_log)
                if activity_log
                else np.zeros((0, g.P), dtype=bool)
            ),
            "converged_at": np.asarray(
                [-1 if c is None else c for c in converged_at], np.int64
            ),
        }
        save_snapshot(spec.directory, sweeps, arrays, meta, keep=spec.keep)

    def _restore_sweep_snapshot(
        self, path: str, plan: ExecutionPlan, K: int, meters: Meters
    ):
        """Load one snapshot back into live loop state (validated)."""
        arrays, meta = load_snapshot(path)
        g = self.graph
        expect = {
            "program": plan.program.name,
            "K": K,
            "P": int(g.P),
            "interval_size": int(g.interval_size),
            "n": int(g.n),
            "m": int(g.m),
        }
        for key, want in expect.items():
            got = meta.get(key)
            if got != want:
                raise SnapshotError(
                    f"{path}: snapshot has {key}={got!r} but the resuming "
                    f"plan/session needs {key}={want!r}"
                )
        # Restore the cumulative meters wholesale: the snapshot was taken
        # past this run's setup charges (pins/fused peak), so the restored
        # values already include them — resumed totals match the
        # uninterrupted run field for field.
        for name, value in meta["meters"].items():
            setattr(meters, name, value)
        attrs = jnp.asarray(arrays["attrs"])
        active = np.asarray(arrays["active"])
        converged_at = [
            None if c < 0 else int(c) for c in arrays["converged_at"]
        ]
        activity_log = [np.asarray(row) for row in arrays["activity_log"]]
        return attrs, active, converged_at, int(meta["sweeps"]), activity_log

    def _publish_iomodel_drift(self, compiled, meters: Meters) -> None:
        """Gauge the measured-vs-modelled byte ratio for this run.

        Per direction: (measured model-unit bytes per sweep) / (Table II
        closed-form bytes per sweep). 1.0 means the engine moved exactly
        what the paper's model predicts; activity-selective runs drift
        below 1.0 as the frontier shrinks. Strategies without a closed
        form (custom registrations) publish nothing.
        """
        iters = meters.iterations
        if not iters:
            return
        strategy = compiled.choice.strategy
        try:
            read, write = modelled_io(
                compiled.params, self.memory_budget, strategy
            )
        except ValueError:
            return
        if read > 0:
            _OBS_DRIFT.labels(direction="read", strategy=strategy).set(
                meters.bytes_read / iters / read
            )
        if write > 0:
            _OBS_DRIFT.labels(direction="write", strategy=strategy).set(
                meters.bytes_written / iters / write
            )

    def _execute(
        self,
        plan: ExecutionPlan,
        kwargs_list: list[dict],
        *,
        resume_from: str | bool | None = None,
        cancel: Callable[[int], None] | None = None,
    ) -> BatchResult:
        g = self.graph
        prog = plan.program
        compiled = self.compile(plan)
        if compiled.residency == "disk":
            # Self-healing reads: checksum-verify (once, with bounded
            # re-read under the store's ReadPolicy) every segment this
            # run's data path — pins and streams alike — will mmap, so a
            # bad segment surfaces as a structured DegradedReadError
            # here, before any garbage bytes reach the device.
            self._heal_store_segments(
                "blk_" if compiled.execution == "per_block" else "p_"
            )
        isz = g.interval_size
        K = len(kwargs_list)
        attrs = jnp.stack(
            [prog.init_attrs(g, **kw).reshape(g.P, isz) for kw in kwargs_list]
        )
        active = np.stack([prog.init_active(g, **kw) for kw in kwargs_list])
        aux, aux_batched = _batch_aux(prog, g, kwargs_list)
        if aux_batched and compiled.choice.strategy not in (
            "spu", "dpu", "mpu", "fused",
        ):
            raise TypeError(
                "plans with per-query aux cannot fuse under custom strategy "
                f"{compiled.choice.strategy!r} (its iteration body predates "
                "the batched-aux vmap); run them individually"
            )
        meters = Meters()
        # Observability: plan-scoped tracing turns the process recorder on
        # for this run's duration — staging/pinning included, so the flip
        # happens before the pins below. Per-sweep spans carry the sweep's
        # *physical* byte deltas (their sum over a fresh run equals
        # Result.meters.bytes_h2d / bytes_disk_read exactly — h2d/disk are
        # only ever charged inside sweeps) and the tiles its scans covered.
        # Model-unit byte counters and the sweep count are published once,
        # at run end, as meter deltas; the physical kinds are published at
        # the transfer/mmap boundaries themselves.
        tspec = plan.trace
        obs_on = _REGISTRY.enabled
        was_tracing = _TRACER.enabled
        tracing = was_tracing or tspec is not None
        trace_sweeps = tracing and (tspec is None or tspec.sweeps)
        run_id = next(_RUN_SEQ)
        mark = _TRACER.mark() if tracing else 0
        if tracing and not was_tracing:
            _TRACER.enabled = True
        try:
            # Per-block host/disk runs pin the resident set here; packed
            # host/disk runs pin a tile prefix lazily inside the sweep (the
            # block pins would double-book the device). Device runs leave
            # pins untouched.
            streamed = compiled.residency in ("host", "disk")
            pinned = (
                self._ensure_pinned(compiled.resident)
                if streamed and compiled.execution == "per_block"
                else {}
                if streamed
                else self._pinned
            )
            fetcher = _BlockFetcher(self, compiled, meters, pinned)
            if compiled.choice.strategy == "fused":
                # The fused path holds the whole edge list on device by
                # design (its point is HBM residency); report that honestly.
                meters.peak_device_graph_bytes = max(
                    meters.peak_device_graph_bytes, float(g.m * self.Be)
                )
            ctx = _RunContext(
                session=self,
                program=prog,
                choice=compiled.choice,
                resident=compiled.resident,
                params=compiled.params,
                aux=aux,
                # Hoisted: all P interval views of the (run-constant) aux
                # are sliced once here, not per (i, j) block inside the
                # sweeps.
                aux_views=[
                    self._interval_aux(aux, k, batched=aux_batched)
                    for k in range(g.P)
                ],
                valid=(jnp.arange(g.n_pad) < g.n).reshape(g.P, isz),
                tol=jnp.asarray(plan.tol, jnp.float32),
                K=K,
                residency=compiled.residency,
                fetcher=fetcher,
                activity=compiled.activity,
                aux_batched=aux_batched,
                trace=trace_sweeps,
            )
            if compiled.execution == "packed":
                iteration = _iteration_packed
            else:
                iteration = self._strategies[compiled.choice.strategy]
            converged_at: list[int | None] = [
                0 if not active[m].any() else None for m in range(K)
            ]
            sweeps = 0
            activity_log: list[np.ndarray] = []
            wall0 = 0.0
            snap_path = self._resolve_resume(plan, resume_from)
            if snap_path is not None:
                attrs, active, converged_at, sweeps, activity_log = (
                    self._restore_sweep_snapshot(snap_path, plan, K, meters)
                )
                wall0 = meters.wall_seconds
            ckpt = plan.checkpoint
            inj = self._injector
            sweeps0 = sweeps
            model0 = [getattr(meters, f) for f, _ in _OBS_MODEL_BYTES]
            run_span = (
                _TRACER.span("run", cat="engine", run=run_id)
                if tracing
                else NO_SPAN
            )
            start = time.perf_counter()
            with run_span:
                for _ in range(sweeps, plan.max_iters):
                    if not active.any():
                        break
                    # Cooperative cancellation (serving deadlines) and
                    # injected crashes both land here, on the sweep boundary
                    # — never mid-sweep, so checkpointed state is always
                    # consistent.
                    if cancel is not None:
                        cancel(sweeps)
                    if inj is not None:
                        inj.check("sweep", sweeps)
                    # Record the sweep's processed-interval bitmap (the union
                    # _rows_to_process acts on) before the sweep mutates
                    # `active` — this is the trace the iomodel activity terms
                    # consume.
                    if compiled.activity == "selective":
                        activity_log.append(active.any(axis=0).copy())
                    else:
                        activity_log.append(np.ones(g.P, dtype=bool))
                    if trace_sweeps:
                        s_h2d = meters.bytes_h2d
                        s_disk = meters.bytes_disk_read
                        s_tiles = ctx.tiles_swept
                        sweep_span = _TRACER.span(
                            "sweep", cat="engine", run=run_id, sweep=sweeps
                        )
                    else:
                        sweep_span = NO_SPAN
                    with sweep_span:
                        attrs, active = iteration(ctx, attrs, active, meters)
                        if trace_sweeps:
                            sweep_span.set(
                                bytes_h2d=meters.bytes_h2d - s_h2d,
                                bytes_disk_read=meters.bytes_disk_read - s_disk,
                                tiles=ctx.tiles_swept - s_tiles,
                                active_intervals=int(activity_log[-1].sum()),
                                intervals=int(g.P),
                            )
                    sweeps += 1
                    meters.iterations += 1
                    for m in range(K):
                        if converged_at[m] is None and not active[m].any():
                            converged_at[m] = sweeps
                    if ckpt is not None and sweeps % ckpt.every == 0:
                        with (
                            _TRACER.span(
                                "checkpoint", cat="engine", run=run_id,
                                sweep=sweeps,
                            )
                            if tracing
                            else NO_SPAN
                        ):
                            self._save_sweep_snapshot(
                                ckpt, plan, attrs, active, converged_at,
                                sweeps, activity_log, meters,
                                wall0 + (time.perf_counter() - start),
                            )
                end = time.perf_counter()
                meters.wall_seconds = wall0 + (end - start)
                if tracing:
                    run_span.set(
                        program=prog.name,
                        strategy=compiled.choice.strategy,
                        residency=compiled.residency,
                        execution=compiled.execution,
                        K=K,
                        n=int(g.n),
                        m=int(g.m),
                        P=int(g.P),
                        sweeps=sweeps,
                        bytes_h2d=meters.bytes_h2d,
                        bytes_disk_read=meters.bytes_disk_read,
                        converged=bool(not active.any()),
                    )
            if tracing and tspec is not None and tspec.path:
                _TRACER.export(tspec.path, since=mark)
        finally:
            if tracing and not was_tracing:
                _TRACER.enabled = was_tracing
        if obs_on:
            _OBS_SWEEPS.inc(sweeps - sweeps0)
            for (f, child), before in zip(_OBS_MODEL_BYTES, model0):
                delta = getattr(meters, f) - before
                if delta:
                    child.inc(delta)
            _OBS_RUNS.labels(
                program=prog.name,
                strategy=compiled.choice.strategy,
                residency=compiled.residency,
                execution=compiled.execution,
            ).inc()
            _OBS_PEAK.set(meters.peak_device_graph_bytes)
            self._publish_iomodel_drift(compiled, meters)
        results = []
        for m in range(K):
            flat = attrs[m].reshape(-1)
            # Per-query iterations: the sweep at which this member converged
            # (meaningful for monotone programs, where later sweeps are
            # no-ops for it); otherwise the shared sweep count.
            iterations = (
                converged_at[m]
                if prog.monotone and converged_at[m] is not None
                else sweeps
            )
            results.append(
                Result(
                    attrs=np.asarray(flat[: g.n]),
                    output=prog.output(flat, g),
                    iterations=iterations,
                    converged=converged_at[m] is not None,
                    meters=meters,
                    strategy=compiled.choice,
                    activity_log=tuple(activity_log),
                )
            )
        return BatchResult(
            results=results,
            meters=meters,
            iterations=sweeps,
            converged=not active.any(),
            fused=True,
            activity_log=tuple(activity_log),
        )


# ---------------------------------------------------------------------------
# Identity-keyed weak LRU — shared by the session cache below and the
# sharded-graph cache in repro.core.algorithms.
# ---------------------------------------------------------------------------
class IdentityLRU:
    """Small LRU keyed by ``(id(obj), *extra)`` with a weakref liveness guard.

    Keying by identity is deliberate (the cached value aliases the object's
    arrays); the weakref invalidates the slot so recycled ids can't alias a
    dead object.
    """

    def __init__(self, size: int = 8):
        self._size = size
        self._entries: "OrderedDict[tuple, tuple[weakref.ref, Any]]" = OrderedDict()

    def get_or_build(self, obj, extra: tuple, factory: Callable):
        key = (id(obj), *extra)
        entry = self._entries.get(key)
        if entry is not None and entry[0]() is obj:
            self._entries.move_to_end(key)
            return entry[1]
        value = factory()
        self._entries[key] = (weakref.ref(obj), value)
        while len(self._entries) > self._size:
            self._entries.popitem(last=False)
        return value

    def clear(self) -> None:
        self._entries.clear()


# Session LRU keyed by graph identity — lets the algorithm drivers
# (repro.core.algorithms) share one staged session per graph object. Each
# slot holds the graph's staged device arrays plus the session variants
# (per memory_budget/Be/Bv) built over them, so changing the budget never
# re-uploads the blocks. The cache intentionally keeps the last
# `size` graphs' blocks resident (an LRU retains by design — the cached
# session strongly references its graph); call clear_session_cache() to
# release them, or construct GraphSession directly for throwaway graphs.
_SESSION_LRU = IdentityLRU(size=8)


def get_session(
    graph: DSSSGraph,
    *,
    memory_budget: int | None = None,
    host_memory_budget: int | None = None,
    residency: str = "auto",
    execution: str = "auto",
    packing: str = "auto",
    Be: int = 8,
    Bv: int = 4,
) -> GraphSession:
    """The session for this graph object, staged at most once (LRU of 8).

    Only use this for graph objects the caller keeps alive across calls;
    for a throwaway graph, construct :class:`GraphSession` directly so the
    staged blocks die with it instead of pinning an LRU slot. Variants
    (budgets/residency/execution/packing/byte sizes) share one set of host
    buffers, one lazily-staged device mirror and one packed tile layout
    per packing mode. Every session axis participates in the variant key,
    so callers differing in *any* knob never wrongly share (or spuriously
    duplicate) a session. ``host_memory_budget`` is accepted and keyed for
    consistency and forwarded — in-memory graphs reject it with
    :class:`GraphSession`'s own error (it is the disk tier's RAM bound;
    disk-backed sessions come from :meth:`GraphSession.open` or a
    :class:`repro.serving.pool.SessionPool`, not this cache).
    """
    slot = _SESSION_LRU.get_or_build(
        graph, (), lambda: {"staged": _StagedGraph(graph), "variants": {}}
    )
    key = (
        memory_budget, host_memory_budget, residency, execution, packing,
        Be, Bv,
    )
    session = slot["variants"].get(key)
    if session is None:
        session = GraphSession(
            graph,
            memory_budget=memory_budget,
            host_memory_budget=host_memory_budget,
            residency=residency,
            execution=execution,
            packing=packing,
            Be=Be,
            Bv=Bv,
            staged=slot["staged"],
        )
        slot["variants"][key] = session
    return session


def clear_session_cache() -> None:
    """Release every cached session (and its device-staged blocks)."""
    _SESSION_LRU.clear()
