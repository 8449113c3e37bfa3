"""Destination-Sorted Sub-Shard (DSSS) structure — paper §II-A / §III-A.

The *sharder*: vertices are split into ``P`` equal-sized intervals; edges are
split into ``P²`` sub-shards where ``SS[i, j]`` holds every edge with source
in interval ``i`` and destination in interval ``j``. Within a sub-shard,
edges are sorted by destination id first, then source id — the DSSS ordering
that (a) makes the per-block destination range contiguous and narrow
(conflict-free reduction), and (b) makes source gathers cache/VMEM friendly.

All ``P²`` sub-shards live as slices of one flat edge buffer sorted by
``(j, i, dst, src)`` — a single allocation instead of the paper's P² files
(which hit OS handle limits on Yahoo-web, paper §IV-D).

Hubs (paper §III-B2): for every sub-shard we precompute the *unique
destination* compression used by DPU hubs — ``hub_dst[k]`` local unique
destination ids and ``hub_inv`` mapping each edge to its hub slot. The hub
byte model ``m·(Ba+Bv)/d`` falls out of these counts exactly.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.graph.preprocess import EdgeList
from repro.obs.trace import NO_SPAN, TRACER

__all__ = [
    "DSSSGraph",
    "PackedSweep",
    "build_dsss",
    "SubShard",
    "next_bucket",
    "choose_tile_edges",
    "cut_runs_into_tiles",
    "tile_candidates",
    "tile_source_spans",
    "active_tile_mask",
    "packed_run_ends",
]


def next_bucket(e: int, minimum: int = 8) -> int:
    """Smallest power-of-two bucket >= e (jit shape-bucketing for blocks)."""
    b = minimum
    while b < e:
        b *= 2
    return b


# Smallest tile size the adaptive chooser will consider on non-trivial
# graphs: one TPU lane row of edges. Smaller tiles can pack marginally
# tighter on low-skew graphs but fragment the scan into more steps than
# the padding saved is worth.
TILE_EDGES_FLOOR = 128


def cut_runs_into_tiles(bounds: np.ndarray, tile_edges: int) -> list[tuple[int, int]]:
    """Greedy destination-aligned cut: pack runs into ``tile_edges`` tiles.

    ``bounds`` is the (num_runs + 1,) array of cumulative run boundaries
    (edge offsets); returns ``(r0, r1)`` run-index spans, each spanning at
    most ``tile_edges`` edges, cutting only between runs. Requires
    ``tile_edges >= max run length`` (else a run is force-placed alone in
    an overfull tile — callers choose ``tile_edges`` to avoid this).
    """
    n_runs = len(bounds) - 1
    tiles: list[tuple[int, int]] = []
    r = 0
    while r < n_runs:
        limit = bounds[r] + tile_edges
        k = int(np.searchsorted(bounds, limit, side="right")) - 1
        k = min(max(k, r + 1), n_runs)
        tiles.append((r, k))
        r = k
    return tiles


def tile_candidates(m: int, max_run: int) -> list[int]:
    """Power-of-two tile sizes the adaptive chooser considers.

    From ``max(TILE_EDGES_FLOOR, bucket(max_run))`` — a run must fit one
    tile, or the cut rule would have to split a destination's fold — up to
    ``bucket(m)`` (a single tile). Shared with the external-memory builder
    (``repro.storage.build``), whose streaming greedy counters must pick
    the exact tile size :func:`choose_tile_edges` would, so a stored graph
    is layout-identical to an in-memory :meth:`DSSSGraph.packed_sweep`.
    """
    if m == 0:
        return [8]
    lo = max(min(TILE_EDGES_FLOOR, next_bucket(m)), next_bucket(max_run))
    hi = max(lo, next_bucket(m))
    out = []
    T = lo
    while T <= hi:
        out.append(T)
        T *= 2
    return out


def choose_tile_edges(run_lengths: np.ndarray) -> int:
    """Pick the tile size minimising total padded slots for these runs.

    Candidates come from :func:`tile_candidates`. Each candidate's exact
    padded footprint ``num_tiles · T`` is evaluated with the real greedy
    cut; ties prefer the *smaller* tile (finer granularity for budget
    pinning and chunked host streaming, at identical padding). This is
    what bounds the padded-edge ratio on power-law graphs, where the
    legacy max-sub-shard tile width is hub-degree-bound.
    """
    m = int(run_lengths.sum()) if len(run_lengths) else 0
    if m == 0:
        return 8
    bounds = np.concatenate([[0], np.cumsum(run_lengths)])
    best_T, best_slots = None, None
    for T in tile_candidates(m, int(run_lengths.max())):
        slots = len(cut_runs_into_tiles(bounds, T)) * T
        if best_slots is None or slots < best_slots:
            best_T, best_slots = T, slots
    return best_T


@dataclasses.dataclass(frozen=True)
class SubShard:
    """A view of one sub-shard SS[i, j] (all arrays are slices, zero-copy).

    ``src_local``/``dst_local`` are offsets within the source / destination
    interval (so the engine's working set per block is two interval-sized
    arrays — the locality property).
    """

    i: int
    j: int
    src_local: np.ndarray  # int32 (e,)
    dst_local: np.ndarray  # int32 (e,)
    weights: np.ndarray | None  # float32 (e,) or None
    hub_dst: np.ndarray  # int32 (u,) unique local destinations (sorted)
    hub_inv: np.ndarray  # int32 (e,) edge -> hub slot
    src_sorted: bool = False  # True for the GraphChi-like baseline layout

    @property
    def num_edges(self) -> int:
        return int(self.src_local.shape[0])

    @property
    def num_unique_dst(self) -> int:
        return int(self.hub_dst.shape[0])


@dataclasses.dataclass(frozen=True)
class PackedSweep:
    """Destination-aligned tile packing of one full update sweep.

    The flat DSSS edge array is already the whole sweep in execution
    order: row-major ``(i, j)`` sub-shards, destination-sorted inside
    each. This layout cuts that stream into uniform ``(num_tiles,
    tile_edges)`` windows so the executor can run the entire gather-reduce
    phase as a single ``jax.lax.scan`` (or stream tile chunks host→device)
    — one XLA dispatch instead of one host round-trip per sub-shard.

    **Cut rule (mode="adaptive"):** tiles are cut *only at destination-run
    boundaries* — a run being one sub-shard's maximal span of edges
    sharing a destination, i.e. exactly one hub slot. Large sub-shards
    therefore split across tiles and small consecutive sub-shards coalesce
    into shared tiles, but a destination's per-sub-shard edge run is never
    divided, so a run-partial reduce (the per-block executor's) sees
    whole runs, with near-uniform tile occupancy (``padding_ratio`` stays
    small on power-law graphs instead of being bound by the largest
    sub-shard). ``tile_edges`` is chosen per graph to minimise total
    padded slots (see :func:`choose_tile_edges`).

    **mode="subshard"** reproduces the legacy one-tile-per-sub-shard
    packing (tiles never cross or split sub-shards, ``tile_edges`` = the
    largest sub-shard bucket) in the same schema — kept because it is the
    only packing whose per-run reduce is also valid for ``src_sorted``
    (GraphChi-like) layouts, where a destination's edges are not
    contiguous and only whole-sub-shard windows group them correctly.

    **Execution schema** (per tile):

    * ``src`` / ``dst`` — global endpoint ids (vertex id == padded
      position, since intervals are the contiguous ranges
      ``[i·interval_size, …)``): the scan gathers attributes and aux
      directly from the flat ``(n_pad,)`` arrays, and the per-edge fold
      scatters each edge's contribution into the flat accumulator at
      ``dst`` (padding past ``e_valid`` redirected to the ``n_pad`` drop
      sentinel), so a tile needs no single source/destination interval
      and coalescing is free.
    * ``run_local`` — per-edge hub slot *within the tile's slot window*
      (global hub slot − ``base_slot``): the per-tile segment reduce over
      ``run_local`` is precisely the ToHub windowed-partial formulation of
      ``kernels/dsss_spmv.py``, which is why tiles are also valid Pallas
      kernel inputs (:func:`repro.kernels.ops.prepare_from_packed_tile`).
      The run fold reads it for its segment starts.
    * ``run_dst`` — per run-slot global destination id (``n_pad`` sentinel
      past ``u``): where the run fold scatters each run's partial in the
      flat accumulator.
    * ``run_end`` — derived, not stored: per run slot, the tile position
      of the run's last edge (0 past ``u``), ``(NT, R)`` with ``R`` the
      smallest multiple of 128 at least ``max(u)``; ``None`` where the
      run fold does not apply (:func:`packed_run_ends`).
    * ``e_valid`` — real edges; trailing padding is masked to exact
      ⊕-identities.

    Fold order. Where ``run_end`` exists the scan's run fold reduces each
    run with a segmented scan (a tree within the run), then scatters one
    partial per run, left to right across runs and tiles in stream order;
    otherwise it scatters each edge straight to ``dst``, left to right in
    stream order. XLA serialises conflicting scatter updates in order on
    CPU and TPU (implementation-defined on GPU).
    The per-block executor folds run partials in ascending
    source-interval order instead. Min and max are order-free,
    so every backend and both folds give bit-identical results; a float
    ``sum`` is reassociated across backends and agrees to float32
    rounding. One backend run twice over the same tiles, whatever the
    residency or chunking, folds in the same order and is bit-identical:
    a tile's fold depends only on the tile.

    ``src_interval`` / ``dst_interval`` / ``base_slot`` / ``row_offset`` /
    ``u`` are the per-tile metadata (intervals of the first edge, global
    hub-slot base, offset of the first edge in the flat DSSS edge array,
    run count) that drive meter recomputation, chunked host streaming and
    the kernel staging; they stay host-side.
    """

    mode: str  # "adaptive" | "subshard"
    m: int  # real edges covered (== graph.m)
    n_pad: int  # padded vertex count (the run_dst scatter sentinel)
    tile_edges: int  # T: padded edge capacity of every tile
    src: np.ndarray  # int32 (NT, T) global source ids (0-padded)
    dst: np.ndarray  # int32 (NT, T) global destination ids (0-padded)
    run_local: np.ndarray  # int32 (NT, T) edge -> run slot within the tile
    run_dst: np.ndarray  # int32 (NT, T) run slot -> global dst (n_pad pad)
    weights: np.ndarray | None  # float32 (NT, T) or None
    e_valid: np.ndarray  # int32 (NT,) real edge count per tile
    src_interval: np.ndarray  # int32 (NT,) i of the tile's first edge
    dst_interval: np.ndarray  # int32 (NT,) j of the tile's first edge
    base_slot: np.ndarray  # int64 (NT,) global hub slot of the first run
    u: np.ndarray  # int32 (NT,) runs (unique (sub-shard, dst)) per tile
    row_offset: np.ndarray  # int64 (NT,) flat edge offset of the first edge

    @property
    def num_tiles(self) -> int:
        return int(self.e_valid.shape[0])

    @property
    def padded_edge_slots(self) -> int:
        """Total edge slots the packing allocates (``num_tiles·tile_edges``)."""
        return self.num_tiles * self.tile_edges

    @property
    def padding_ratio(self) -> float:
        """Padded-slots / real-edges — 1.0 is a perfect packing."""
        return self.padded_edge_slots / max(self.m, 1)

    @functools.cached_property
    def run_end(self) -> np.ndarray | None:
        """(NT, R) int32 last-edge position per run slot, derived once
        (:func:`packed_run_ends`); ``None`` keeps the per-edge fold."""
        return packed_run_ends(self.run_local, self.e_valid, self.u)

    @property
    def run_slots(self) -> int:
        """``R``, the run fold's static run-slot width (0 without it)."""
        return 0 if self.run_end is None else int(self.run_end.shape[1])


# Run slots are padded to whole TPU lane rows.
RUN_SLOT_ALIGN = 128
# Tiles per block of the host derivation (bounds its temporaries).
_RUN_END_BLOCK = 128


def packed_run_ends(
    run_local: np.ndarray, e_valid: np.ndarray, u: np.ndarray
) -> np.ndarray | None:
    """Each run's last edge position per tile, for the scan's run fold.

    Returns an int32 ``(NT, R)`` array: ``run_end[t, r]`` is the last slot
    ``k < e_valid[t]`` with ``run_local[t, k] == r``, and 0 for ``r`` past
    the tile's ``u`` runs. ``R`` is the smallest multiple of
    :data:`RUN_SLOT_ALIGN` at least ``max(u)``, static per packing.

    Returns ``None`` — the scan then keeps the per-edge fold — unless
    both hold:

    * ``run_local`` is non-decreasing over every tile's real slots, so a
      run's edges are contiguous (DSSS packings; a ``src_sorted``
      layout's sub-shard tiles are not);
    * ``2·R ≤ T``. The run fold issues one gather and one scatter per run
      slot, ``R·(7.1 + 8.8)`` ns of indexed work per tile on one TPU v5e
      (Graph500 scale 21: ~7.1 ns a gather index, ~8.8 ns a scatter
      index), against the per-edge fold's ``T·8.8`` ns: it breaks even
      near ``R = 0.55·T``, and ``2·R ≤ T`` leaves the rest for its dense
      segmented scan. Graphs whose runs are mostly one edge long fail it.
    """
    nt, T = run_local.shape
    if nt == 0:
        return None
    R = -(-int(np.max(u)) // RUN_SLOT_ALIGN) * RUN_SLOT_ALIGN
    if R == 0 or 2 * R > T:
        return None
    run_end = np.zeros((nt, R), np.int32)
    pos = np.arange(T, dtype=np.int32)
    for lo in range(0, nt, _RUN_END_BLOCK):
        rl = np.asarray(run_local[lo : lo + _RUN_END_BLOCK])
        ev = np.asarray(e_valid[lo : lo + _RUN_END_BLOCK])[:, None]
        next_live = pos[1:] < ev
        if (next_live & (rl[:, 1:] < rl[:, :-1])).any():
            return None
        # A real slot ends its run unless the next slot is real and in it.
        end = pos < ev
        end[:, :-1] &= ~(next_live & (rl[:, 1:] == rl[:, :-1]))
        t, k = np.nonzero(end)
        run_end[lo + t, rl[t, k]] = k
    return run_end


def tile_source_spans(
    packed: PackedSweep, interval_size: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-tile source-interval span ``[first_i, last_i]`` (inclusive).

    ``src_interval`` records the interval of a tile's *first* edge; a
    coalesced tile can span several consecutive source intervals (the
    stream is row-major, so the span is always contiguous). The last
    interval is recovered from the tile's last real edge's source id.
    Empty tiles (``e_valid == 0`` cannot occur in a build, but a
    compacted gather may zero them) degenerate to ``last == first``.

    These spans drive frontier-aware selective execution: a tile can be
    skipped iff no source interval in its span is active — see
    :func:`active_tile_mask`.
    """
    nt = packed.num_tiles
    first = packed.src_interval.astype(np.int64)
    if nt == 0:
        return first, first.copy()
    last_edge = np.maximum(packed.e_valid.astype(np.int64), 1) - 1
    last_src = packed.src[np.arange(nt), last_edge].astype(np.int64)
    return first, np.maximum(first, last_src // interval_size)


def active_tile_mask(
    row_active: np.ndarray, first: np.ndarray, last: np.ndarray
) -> np.ndarray:
    """``(NT,)`` bool: does tile t contain any edge from an active interval?

    ``row_active`` is the (P,) per-interval activity bitmap from the
    previous sweep's ``changed`` output; ``first``/``last`` are the
    inclusive per-tile spans from :func:`tile_source_spans`. Computed as
    a prefix-sum range query so the whole map costs O(P + NT).

    For monotone programs, a False tile contributes only exact
    ⊕-identities (every source attribute in it is unchanged since last
    gathered), so skipping it preserves bit-identity with the full sweep.
    """
    row = np.asarray(row_active, dtype=np.int64)
    cum = np.concatenate([[0], np.cumsum(row)])
    return (cum[last + 1] - cum[first]) > 0


@dataclasses.dataclass(frozen=True)
class DSSSGraph:
    """The sharded graph: P intervals × P² destination-sorted sub-shards."""

    n: int  # number of vertices (dense ids)
    m: int  # number of edges
    P: int  # number of intervals
    interval_size: int  # ceil(n / P); last interval padded
    src: np.ndarray  # int32 (m,) global ids, sorted by (j, i, dst, src)
    dst: np.ndarray  # int32 (m,)
    weights: np.ndarray | None
    offsets: np.ndarray  # int64 (P, P + 1): offsets[i, j] .. offsets[i, j+1]
    out_degree: np.ndarray  # int32 (n_pad,)
    in_degree: np.ndarray  # int32 (n_pad,)
    hub_dst_flat: np.ndarray  # int32: concatenated unique-dst lists
    hub_inv_flat: np.ndarray  # int32 (m,): edge -> slot within its hub
    hub_offsets: np.ndarray  # int64 (P, P + 1) into hub_dst_flat
    edgelist: EdgeList  # the pre-shard this was built from
    src_sorted: bool = False  # True when built with the baseline ordering

    # -- derived sizes ------------------------------------------------------
    @property
    def n_pad(self) -> int:
        return self.P * self.interval_size

    def interval_bounds(self, i: int) -> tuple[int, int]:
        lo = i * self.interval_size
        return lo, min(lo + self.interval_size, self.n)

    def subshard(self, i: int, j: int) -> SubShard:
        lo = int(self.offsets[i, j])
        hi = int(self.offsets[i, j + 1])
        hlo = int(self.hub_offsets[i, j])
        hhi = int(self.hub_offsets[i, j + 1])
        isz = self.interval_size
        return SubShard(
            i=i,
            j=j,
            src_local=(self.src[lo:hi] - i * isz).astype(np.int32),
            dst_local=(self.dst[lo:hi] - j * isz).astype(np.int32),
            weights=None if self.weights is None else self.weights[lo:hi],
            hub_dst=self.hub_dst_flat[hlo:hhi],
            hub_inv=self.hub_inv_flat[lo:hi],
            src_sorted=self.src_sorted,
        )

    def subshard_edge_count(self, i: int, j: int) -> int:
        return int(self.offsets[i, j + 1] - self.offsets[i, j])

    def padded_subshard(self, i: int, j: int) -> dict | None:
        """Host-side staging of SS[i, j] in the engine's 'shard file' format.

        Edge arrays are padded to a power-of-two bucket (so jit compiles one
        executable per bucket size, not per sub-shard) and the hub slot list
        to its own bucket. Returns ``None`` for empty sub-shards. The device
        upload happens once per graph in :class:`repro.core.session.
        GraphSession`; this method owns only the numpy-side layout.
        """
        e = self.subshard_edge_count(i, j)
        if e == 0:
            return None
        ss = self.subshard(i, j)
        pad = next_bucket(e) - e
        ub = next_bucket(max(ss.num_unique_dst, 1))
        blk = {
            "src_local": np.pad(ss.src_local, (0, pad)),
            "dst_local": np.pad(ss.dst_local, (0, pad)),
            "hub_inv": np.pad(ss.hub_inv, (0, pad)),
            "hub_dst": np.pad(ss.hub_dst, (0, ub - ss.num_unique_dst)),
            "e": e,
            "u": ss.num_unique_dst,
            "u_bucket": ub,
            "weights": (
                None
                if ss.weights is None
                else np.pad(ss.weights, (0, pad)).astype(np.float32)
            ),
        }
        return blk

    def host_blocks(self) -> dict[tuple[int, int], dict]:
        """All non-empty sub-shards as padded host buffers, keyed ``(i, j)``.

        This is the slow-tier image of the graph: the session keeps these
        numpy buffers pinned on the host and either mirrors them to the
        device once (``residency="device"``) or streams them per sweep
        (``residency="host"``). No device arrays are created here.
        """
        blocks: dict[tuple[int, int], dict] = {}
        for i in range(self.P):
            for j in range(self.P):
                blk = self.padded_subshard(i, j)
                if blk is not None:
                    blocks[(i, j)] = blk
        return blocks

    def global_hub_slots(self) -> np.ndarray:
        """int64 (m,): each edge's *global* hub slot (run id).

        ``hub_inv_flat`` is local to its sub-shard; adding the sub-shard's
        cumulative slot base makes slot ids global and — because slot
        numbering follows the same row-major, destination-sorted order as
        the flat edge array — non-decreasing along the edge stream for the
        DSSS layout (``src_sorted`` graphs scramble them within blocks).
        """
        counts = np.diff(
            np.concatenate([[0], self.offsets[:, 1:].ravel()])
        )
        bases = np.repeat(self.hub_offsets[:, :-1].ravel(), counts)
        return bases + self.hub_inv_flat

    def packed_sweep(self, mode: str = "adaptive") -> PackedSweep:
        """Tile-pack the whole sweep for the compiled executor (pure numpy).

        ``mode="adaptive"`` (default, DSSS layout only): fixed-size tiles
        cut at destination-run boundaries, tile size chosen by
        :func:`choose_tile_edges`. ``mode="subshard"``: the legacy
        one-tile-per-sub-shard packing (required for ``src_sorted``
        graphs). Device upload happens once in
        ``repro.core.session._StagedGraph``.
        """
        if mode not in ("adaptive", "subshard"):
            raise ValueError(f"packing mode must be 'adaptive' or 'subshard', got {mode!r}")
        if mode == "adaptive" and self.src_sorted:
            raise ValueError(
                "adaptive tile packing needs destination-sorted sub-shards; "
                "src_sorted graphs must use mode='subshard' (a destination's "
                "edges are not contiguous, so only whole-sub-shard windows "
                "group its partial reduce correctly)"
            )
        m = self.m
        gslot = self.global_hub_slots()
        if mode == "adaptive":
            if m == 0:
                starts = np.zeros(0, np.int64)
            else:
                change = np.ones(m, dtype=bool)
                change[1:] = gslot[1:] != gslot[:-1]
                starts = np.flatnonzero(change).astype(np.int64)
            bounds = np.concatenate([starts, [m]])  # run r spans bounds[r:r+2]
            run_len = np.diff(bounds)
            T = choose_tile_edges(run_len)
            tile_runs = cut_runs_into_tiles(bounds, T)
        else:
            # One tile per non-empty sub-shard: forced cuts at block
            # boundaries, T = the largest sub-shard bucket (legacy packing).
            blk_bounds = self.offsets[:, 1:].ravel()
            blk_lo = np.concatenate([[0], blk_bounds[:-1]])
            nonempty = blk_bounds > blk_lo
            lo, hi = blk_lo[nonempty], blk_bounds[nonempty]
            T = next_bucket(int((hi - lo).max()) if len(lo) else 8)
            # Runs double as blocks here: each tile is one whole block.
            bounds = None
            tile_runs = [(int(a), int(b)) for a, b in zip(lo, hi)]
        nt = len(tile_runs)
        src = np.zeros((nt, T), np.int32)
        dst = np.zeros((nt, T), np.int32)
        run_local = np.zeros((nt, T), np.int32)
        run_dst = np.full((nt, T), self.n_pad, np.int32)
        weights = None if self.weights is None else np.zeros((nt, T), np.float32)
        e_valid = np.zeros(nt, np.int32)
        src_iv = np.zeros(nt, np.int32)
        dst_iv = np.zeros(nt, np.int32)
        base_slot = np.zeros(nt, np.int64)
        u = np.zeros(nt, np.int32)
        row_offset = np.zeros(nt, np.int64)
        isz = self.interval_size
        for t, span in enumerate(tile_runs):
            if mode == "adaptive":
                r0, r1 = span  # run index range
                lo_e, hi_e = int(bounds[r0]), int(bounds[r1])
                base = int(gslot[lo_e])
                nu = r1 - r0
            else:
                lo_e, hi_e = span  # edge range of one whole block
                base = int(gslot[lo_e] - self.hub_inv_flat[lo_e])
                nu = int(self.hub_inv_flat[lo_e:hi_e].max()) + 1
            e = hi_e - lo_e
            src[t, :e] = self.src[lo_e:hi_e]
            dst[t, :e] = self.dst[lo_e:hi_e]
            run_local[t, :e] = (gslot[lo_e:hi_e] - base).astype(np.int32)
            # Run slot -> global destination: the destination of any edge in
            # the run (scatter target of the FromHub fold).
            run_dst[t, :e][run_local[t, :e]] = dst[t, :e]
            if weights is not None:
                weights[t, :e] = self.weights[lo_e:hi_e]
            e_valid[t] = e
            src_iv[t] = self.src[lo_e] // isz
            dst_iv[t] = self.dst[lo_e] // isz
            base_slot[t] = base
            u[t] = nu
            row_offset[t] = lo_e
        return PackedSweep(
            mode=mode,
            m=m,
            n_pad=self.n_pad,
            tile_edges=T,
            src=src,
            dst=dst,
            run_local=run_local,
            run_dst=run_dst,
            weights=weights,
            e_valid=e_valid,
            src_interval=src_iv,
            dst_interval=dst_iv,
            base_slot=base_slot,
            u=u,
            row_offset=row_offset,
        )

    def total_edge_bytes(self, Be: int) -> int:
        """Model bytes of the whole edge topology (``m·Be``) — the quantity
        a ``memory_budget`` must exceed for 100% edge residency."""
        return self.m * Be

    def mean_hub_in_degree(self) -> float:
        """The paper's ``d``: average in-degree of sub-shard destinations.

        ``d = m / Σ_{i,j} |unique dst in SS[i,j]|`` — the hub compression
        factor in the DPU I/O model (paper reports 10–20 for Yahoo-web).
        """
        # hub_offsets holds *cumulative* offsets into hub_dst_flat; the
        # global total is the final offset, not a column sum.
        total_unique = int(self.hub_offsets[-1, -1])
        return self.m / max(total_unique, 1)

    def density_matrix(self) -> np.ndarray:
        """(P, P) edge counts per sub-shard — used by schedulers/benchmarks."""
        return (self.offsets[:, 1:] - self.offsets[:, :-1]).astype(np.int64)


def _hub_slots(
    dst_s: np.ndarray,
    flat_offsets: np.ndarray,
    P: int,
    isz: int,
    src_sorted: bool,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Hub (unique destination) compression per sub-shard.

    Returns ``(hub_dst_flat, hub_inv_flat, hub_counts)``: the concatenated
    local unique destinations, each edge's slot within its sub-shard's
    hub list, and the (P·P,) hub counts. Because edges are
    destination-sorted inside each sub-shard, uniques are found with one
    vectorized pass: a new hub slot opens wherever dst changes or a new
    sub-shard begins.
    """
    m = dst_s.shape[0]
    if src_sorted:
        # Destinations are not sorted inside a block; fall back to per-block
        # np.unique (the baseline pays this cost, as in the paper).
        hub_dst_parts: list[np.ndarray] = []
        hub_inv_flat = np.zeros(m, dtype=np.int32)
        hub_counts = np.zeros(P * P, dtype=np.int64)
        for b in range(P * P):
            lo, hi = int(flat_offsets[b]), int(flat_offsets[b + 1])
            if hi == lo:
                hub_dst_parts.append(np.zeros(0, dtype=np.int32))
                continue
            u, inv = np.unique(dst_s[lo:hi], return_inverse=True)
            hub_dst_parts.append((u - (b % P) * isz).astype(np.int32))
            hub_inv_flat[lo:hi] = inv.astype(np.int32)
            hub_counts[b] = len(u)
        hub_dst_flat = (
            np.concatenate(hub_dst_parts) if hub_dst_parts else np.zeros(0, np.int32)
        )
        return hub_dst_flat, hub_inv_flat, hub_counts
    if not m:
        return (
            np.zeros(0, np.int32),
            np.zeros(0, np.int32),
            np.zeros(P * P, dtype=np.int64),
        )
    starts = flat_offsets[:-1]
    is_block_start = np.zeros(m, dtype=bool)
    is_block_start[starts[starts < m]] = True
    new_slot = np.ones(m, dtype=bool)
    new_slot[1:] = (dst_s[1:] != dst_s[:-1]) | is_block_start[1:]
    slot_global = np.cumsum(new_slot) - 1
    hub_dst_flat = (
        dst_s[new_slot] - (dst_s[new_slot] // isz) * isz
    ).astype(np.int32)
    # per-block slot base = slot_global at block start
    blk_of_slot = np.repeat(np.arange(P * P), np.diff(flat_offsets))[new_slot]
    hub_counts = np.bincount(blk_of_slot, minlength=P * P)
    slot_base = np.zeros(P * P, dtype=np.int64)
    np.cumsum(hub_counts[:-1], out=slot_base[1:])
    hub_inv_flat = (
        slot_global - np.repeat(slot_base, np.diff(flat_offsets))
    ).astype(np.int32)
    return hub_dst_flat, hub_inv_flat, hub_counts


def build_dsss(
    el: EdgeList,
    P: int,
    *,
    src_sorted: bool = False,
) -> DSSSGraph:
    """The sharding pass (paper §III-A).

    Args:
      el: degreed (dense-id) edge list.
      P: number of intervals. The paper uses equal-sized vertex ranges and
        relies on fine-grained parallelism to absorb sub-shard imbalance.
      src_sorted: build the *GraphChi-like* layout instead (edges sorted by
        source within each sub-shard) — the ablation baseline of paper
        Table IV. Engine behaviour is identical; only memory-access order
        and the parallel reduction granularity change.

    Traced as ``preprocess.build_dsss`` with the sub-spans
    ``build_dsss.sort`` (the block-order ``lexsort`` and permutation),
    ``build_dsss.blocks`` (the sub-shard offsets) and ``build_dsss.hubs``
    (hub compression).
    """
    if P < 1:
        raise ValueError("P must be >= 1")
    tracing = TRACER.enabled
    with TRACER.span("preprocess.build_dsss") if tracing else NO_SPAN:
        n, m = el.n, el.m
        interval_size = -(-n // P)  # ceil
        src = el.src.astype(np.int64)
        dst = el.dst.astype(np.int64)
        si = src // interval_size  # source interval of each edge
        dj = dst // interval_size  # destination interval
        # Order edges by (source interval, dest interval) block, then by the
        # in-block DSSS order: destination id, then source id. np.lexsort
        # keys are *last-key-major*.
        with TRACER.span("build_dsss.sort") if tracing else NO_SPAN:
            if src_sorted:
                order = np.lexsort((dst, src, dj, si))
            else:
                order = np.lexsort((src, dst, dj, si))
            src_s = src[order].astype(np.int32)
            dst_s = dst[order].astype(np.int32)
            w_s = None if el.weights is None else el.weights[order]

        # offsets[i, j] via 2-D histogram of block ids.
        with TRACER.span("build_dsss.blocks") if tracing else NO_SPAN:
            block = si[order] * P + dj[order]
            counts = np.bincount(block, minlength=P * P).reshape(P, P)
            flat_offsets = np.zeros(P * P + 1, dtype=np.int64)
            np.cumsum(counts.ravel(), out=flat_offsets[1:])
            offsets = np.zeros((P, P + 1), dtype=np.int64)
            offsets[:, 0] = flat_offsets[:-1].reshape(P, P)[:, 0]
            offsets[:, 1:] = flat_offsets[1:].reshape(P, P)

        with TRACER.span("build_dsss.hubs") if tracing else NO_SPAN:
            hub_dst_flat, hub_inv_flat, hub_counts = _hub_slots(
                dst_s, flat_offsets, P, interval_size, src_sorted
            )
            hub_offsets = np.zeros((P, P + 1), dtype=np.int64)
            hub_cum = np.zeros(P * P + 1, dtype=np.int64)
            np.cumsum(hub_counts, out=hub_cum[1:])
            hub_offsets[:, 0] = hub_cum[:-1].reshape(P, P)[:, 0]
            hub_offsets[:, 1:] = hub_cum[1:].reshape(P, P)

        n_pad = P * interval_size
        out_deg = np.zeros(n_pad, dtype=np.int32)
        out_deg[:n] = el.out_degree
        in_deg = np.zeros(n_pad, dtype=np.int32)
        in_deg[:n] = el.in_degree

        return DSSSGraph(
            n=n,
            m=m,
            P=P,
            interval_size=interval_size,
            src=src_s,
            dst=dst_s,
            weights=w_s,
            offsets=offsets,
            out_degree=out_deg,
            in_degree=in_deg,
            hub_dst_flat=hub_dst_flat,
            hub_inv_flat=hub_inv_flat,
            hub_offsets=hub_offsets,
            edgelist=el,
            src_sorted=src_sorted,
        )
