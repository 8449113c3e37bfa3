"""Closed-form slow-tier I/O models — paper Table II + §III-B/§III-C.

These formulas drive two things:

1. The **adaptive strategy selection** the paper describes ("NXgraph can
   adaptively choose the fastest strategy ... according to the graph size
   and the available memory resources"): given ``(n, m, Ba, Be, Bv, d,
   B_M, P)`` pick SPU / MPU(Q) / DPU by modelled total I/O.
2. The **property-test oracle**: the engine's byte meters must reproduce
   these closed forms (tests/test_iomodel_property.py), which is the
   paper-faithfulness proof of the I/O analysis. The meters are charged
   per *schedule event*, not per jit dispatch, so they are independent of
   the execution mode: the per-block executor charges them at the block
   fetcher and the packed compiled-sweep executor recomputes the same
   charges from the packed tile metadata — tests/test_packed_sweep.py
   pins field-for-field equality between the two.

On TPU the "slow tier" is HBM (single chip) or remote chips (pod); the same
formulas apply with ``B_M`` = fast-tier budget (VMEM / local HBM).
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "IOParams",
    "spu_io",
    "dpu_io",
    "mpu_io",
    "turbograph_like_io",
    "mpu_q",
    "select_strategy",
    "StrategyChoice",
    "modelled_io",
    "IOComparison",
    "compare_measured",
    "calibrate_edge_bytes",
    "packed_h2d_bytes",
    "packed_disk_bytes",
    "disk_read_bytes",
    "selective_streamed_tiles",
    "streamed_block_bytes",
    "selective_edge_bytes",
    "PACKED_SLOT_BYTES",
]


@dataclasses.dataclass(frozen=True)
class IOParams:
    """Byte-size parameters of the I/O model (paper Table I)."""

    n: int  # vertices
    m: int  # edges
    Ba: int = 8  # bytes per vertex attribute
    Bv: int = 4  # bytes per vertex id
    Be: int = 8  # bytes per edge
    d: float = 15.0  # mean in-degree of sub-shard destinations (hub factor)
    P: int = 16  # number of intervals


def spu_io(p: IOParams, B_M: int) -> tuple[float, float]:
    """SPU (paper §III-B1): requires ``B_M > 2n·Ba``.

    read  = m·Be + 2n·Ba − B_M   (clamped to [0, m·Be])
    write = 0
    """
    read = p.m * p.Be + 2 * p.n * p.Ba - B_M
    return float(min(max(read, 0), p.m * p.Be)), 0.0


def dpu_io(p: IOParams, B_M: int = 0) -> tuple[float, float]:
    """DPU (paper §III-B2): independent of B_M and P.

    read  = m·Be + m(Ba+Bv)/d + n·Ba
    write = m(Ba+Bv)/d + n·Ba
    """
    hub = p.m * (p.Ba + p.Bv) / p.d
    return float(p.m * p.Be + hub + p.n * p.Ba), float(hub + p.n * p.Ba)


def mpu_q(p: IOParams, B_M: int) -> int:
    """Paper §III-B3: ``Q ≤ B_M / (2 n Ba / P)`` ping-pong-resident intervals."""
    per_interval = 2 * -(-p.n // p.P) * p.Ba  # 2 · ceil(n/P) · Ba (ping-pong)
    return max(0, min(p.P, int(B_M // per_interval)))


def mpu_io(p: IOParams, B_M: int, *, continuous: bool = False) -> tuple[float, float]:
    """MPU (paper §III-B3). Q=P ⇒ SPU-like; Q=0 ⇒ DPU.

    read  = m·Be + ((P−Q)/P)·n·Ba + ((P−Q)²/P²)·m·(Ba+Bv)/d
    write =        ((P−Q)/P)·n·Ba + ((P−Q)²/P²)·m·(Ba+Bv)/d

    (The paper's §III-B3 display omits the 1/d hub compression it carries
    everywhere else — §III-C's B_MPU restores it; we keep 1/d throughout.)

    ``continuous=True`` uses the unquantized Q = (B_M/2n·Ba)·P that the
    paper's Fig. 6 comparison implicitly assumes (valid in the large-P
    limit). With integer Q and small P, MPU quantizes down to DPU and the
    Fig. 6 dominance over TurboGraph-like need not hold — see
    tests/test_engine_strategies.py.
    """
    if continuous:
        qfrac = min(1.0, B_M / max(2 * p.n * p.Ba, 1))
        cold = 1.0 - qfrac
    else:
        Q = mpu_q(p, B_M)
        cold = (p.P - Q) / p.P
    hub = cold * cold * p.m * (p.Ba + p.Bv) / p.d
    iv = cold * p.n * p.Ba
    return float(p.m * p.Be + iv + hub), float(iv + hub)


def turbograph_like_io(p: IOParams, B_M: int) -> tuple[float, float]:
    """TurboGraph/GridGraph-style block-load strategy (paper §III-C).

    With the I/O-optimal partitioning ``P* = 2n·Ba/B_M``:
      read  = m·Be + 2(n·Ba)²/B_M + n·Ba
      write = n·Ba
    """
    read = p.m * p.Be + 2 * (p.n * p.Ba) ** 2 / max(B_M, 1) + p.n * p.Ba
    return float(read), float(p.n * p.Ba)


@dataclasses.dataclass(frozen=True)
class StrategyChoice:
    strategy: str  # "spu" | "mpu" | "dpu"
    Q: int
    modelled_read: float
    modelled_write: float

    @property
    def modelled_total(self) -> float:
        return self.modelled_read + self.modelled_write


def modelled_io(p: IOParams, B_M: int | None, strategy: str) -> tuple[float, float]:
    """Closed-form (read, write) for one strategy — the property-test oracle.

    ``B_M=None`` means unlimited fast tier (SPU with everything resident).
    """
    if strategy == "spu":
        if B_M is None:
            return 0.0, 0.0
        return spu_io(p, B_M)
    if strategy == "dpu":
        return dpu_io(p)
    if strategy == "mpu":
        # No budget ⇒ Q = mpu_q(p, 0) = 0, matching the engine's explicit
        # "mpu" resolution (session._resolve_choice uses `memory_budget or 0`).
        return mpu_io(p, B_M if B_M is not None else 0)
    if strategy == "turbograph-like":
        # The baseline's formula needs a B_M for its P* partitioning term;
        # treat "unlimited" as both attribute copies fitting.
        return turbograph_like_io(p, B_M if B_M is not None else 2 * p.n * p.Ba)
    raise ValueError(f"no closed form for strategy {strategy!r}")


@dataclasses.dataclass(frozen=True)
class IOComparison:
    """Measured engine meters vs. the Table II closed forms, per iteration.

    ``slack_bytes`` is the documented discretization slack the measured
    numbers may deviate by:

    * SPU: residency is block-granular, so the resident prefix can undershoot
      the budget by at most one (largest) sub-shard — ≤ ``max_block·Be``.
    * DPU/MPU: the engine loads/saves *padded* intervals (``n_pad`` vs the
      formula's ``n``) — ≤ ``(n_pad − n)·Ba`` per read and per write; for
      monotone programs cold intervals are read once more than the
      PageRank-style accounting assumes (a documented deviation).
    """

    strategy: str
    modelled_read: float
    modelled_write: float
    measured_read: float
    measured_write: float
    slack_bytes: float

    @property
    def within_slack(self) -> bool:
        return (
            abs(self.measured_read - self.modelled_read) <= self.slack_bytes + 1e-6
            and abs(self.measured_write - self.modelled_write)
            <= self.slack_bytes + 1e-6
        )


def compare_measured(
    per_iteration_meters,
    p: IOParams,
    strategy: str,
    B_M: int | None,
    *,
    slack_bytes: float = 0.0,
) -> IOComparison:
    """Compare a run's per-iteration byte meters against the closed forms.

    ``per_iteration_meters`` is any object with ``bytes_read`` /
    ``bytes_written`` (i.e. ``Meters.per_iteration()``). This is the
    measured-vs-modelled hook the out-of-core executor is validated with:
    under ``residency="host"`` the measured edge bytes are real
    host→device transfers, so a pass here certifies the paper's I/O
    analysis against *performed*, not simulated, traffic.
    """
    read, write = modelled_io(p, B_M, strategy)
    return IOComparison(
        strategy=strategy,
        modelled_read=read,
        modelled_write=write,
        measured_read=float(per_iteration_meters.bytes_read),
        measured_write=float(per_iteration_meters.bytes_written),
        slack_bytes=float(slack_bytes),
    )


# Raw bytes per tile edge slot the packed host-streaming path ships: four
# int32 leaves (src, dst, run_local, run_dst) — plus float32 weights on
# weighted graphs, one int32 e_valid scalar per tile and, where the run
# fold applies, one int32 run_end entry per run slot.
PACKED_SLOT_BYTES = 16


def packed_h2d_bytes(
    streamed_tiles: int, tile_edges: int, *, weighted: bool = False,
    run_slots: int = 0,
) -> float:
    """Closed-form raw host→device bytes per sweep for packed streaming.

    Packed host execution ships every non-pinned tile each sweep — dense
    index/run leaves, so the volume is a pure function of the layout:
    ``streamed_tiles · (tile_edges · slot_bytes + 4 · run_slots + 4)``,
    with ``run_slots`` the packing's ``R`` (0 without the run fold). This is the packed
    counterpart of the per-block path's bucket-padded block bytes and is
    asserted to match ``Meters.bytes_h2d`` exactly in
    tests/test_packed_sweep.py — padding inflation (the adaptive packer's
    ``padding_ratio``) is therefore also the physical h2d inflation, which
    is why bounding it matters out-of-core (GraphMP-style semi-external
    streaming pays for every padded slot on the wire).
    """
    per_tile = (
        tile_edges * (PACKED_SLOT_BYTES + (4 if weighted else 0))
        + 4 * run_slots + 4
    )
    return float(streamed_tiles * per_tile)


def packed_disk_bytes(
    streamed_tiles: int, tile_edges: int, *, weighted: bool = False
) -> float:
    """Closed-form disk-tier bytes per sweep for packed disk streaming.

    Under ``residency="disk"`` the packed executor ships the same dense
    tile leaves as the host path, but sourced from the mmap'd ``.dsss``
    tile section, so the per-sweep disk volume is the same pure function
    of the layout as :func:`packed_h2d_bytes` — less the run fold's
    ``run_end``, which is derived in RAM and not stored — over only the
    tiles that are neither device-pinned nor RAM-cached
    (``num_tiles − pin_tiles − host_tiles`` of the session's
    :class:`~repro.core.session.PackedStreamPlan`). Asserted to match
    ``Meters.bytes_disk_read`` exactly in tests and the storage
    benchmark.
    """
    return packed_h2d_bytes(streamed_tiles, tile_edges, weighted=weighted)


def disk_read_bytes(
    block_nbytes, resident, host_cached, *, active_rows=None
) -> float:
    """Closed-form per-sweep disk reads of the per-block disk executor.

    ``block_nbytes`` maps sub-shard key ``(i, j)`` → raw bytes of its
    padded block arrays (the mmap'd segments the fetch touches); a sweep
    fetches each processed block exactly once, and only blocks that are
    neither device-pinned (``resident``) nor RAM-cached (``host_cached``)
    hit the disk tier. ``active_rows`` is the sweep's (P,) per-interval
    activity bitmap (``Result.activity_log`` entries) — under selective
    execution only blocks whose source interval is active are fetched at
    all, so the oracle stays exact for monotone programs too; ``None``
    means a full sweep (the non-monotone / ``activity="off"`` case).
    """
    return float(
        sum(
            b
            for k, b in block_nbytes.items()
            if k not in resident
            and k not in host_cached
            and (active_rows is None or active_rows[k[0]])
        )
    )


def selective_streamed_tiles(
    tile_active, pin_tiles: int, chunk_tiles: int
) -> int:
    """Streamed tile count of one frontier-aware packed sweep.

    The packed streaming loop walks the fixed chunk grid
    ``[lo, lo+chunk_tiles)`` for ``lo in range(pin_tiles, num_tiles,
    chunk_tiles)`` and, under selective execution, skips the fetch of any
    chunk containing no active tile (``tile_active`` from
    :func:`repro.core.dsss.active_tile_mask`). Chunks are fetched whole —
    partial-chunk gathers would break the prefetch pipeline — so the
    streamed count is the sum of full chunk sizes over active chunks.
    ``packed_h2d_bytes(selective_streamed_tiles(...), tile_edges)`` is
    the exact per-sweep ``bytes_h2d`` oracle; with ``pin_tiles`` set to
    the pin+host-cache boundary it is the ``bytes_disk_read`` oracle
    (both boundaries lie on the chunk grid by construction).
    """
    act = np.asarray(tile_active, dtype=bool)
    nt = int(act.shape[0])
    streamed = 0
    for lo in range(pin_tiles, nt, chunk_tiles):
        hi = min(lo + chunk_tiles, nt)
        if act[lo:hi].any():
            streamed += hi - lo
    return streamed


def streamed_block_bytes(block_nbytes, resident, active_rows=None) -> float:
    """Closed-form per-sweep ``bytes_h2d`` of the per-block host executor.

    ``block_nbytes`` maps sub-shard key ``(i, j)`` → raw bytes of its
    bucket-padded device arrays; a sweep ships every processed non-pinned
    block host→device once. ``active_rows`` restricts the sweep to active
    source intervals exactly as :func:`disk_read_bytes` does.
    """
    return float(
        sum(
            b
            for k, b in block_nbytes.items()
            if k not in resident and (active_rows is None or active_rows[k[0]])
        )
    )


def selective_edge_bytes(block_edges, resident, active_rows, Be) -> float:
    """Modelled edge-byte charge (``Be`` per edge) of one selective sweep.

    The model-side counterpart of :func:`streamed_block_bytes`:
    ``block_edges`` maps sub-shard key ``(i, j)`` → real edge count, and
    the charge covers every processed non-resident block. This is the
    activity term of the Table II read formulas — with ``active_rows``
    all-True it reduces to the full-sweep ``m·Be`` minus the resident
    prefix, which is what the original closed forms charge.
    """
    return float(
        sum(
            e * Be
            for k, e in block_edges.items()
            if k not in resident and (active_rows is None or active_rows[k[0]])
        )
    )


def calibrate_edge_bytes(p: IOParams, meters) -> float:
    """Physical bytes per modelled edge byte, from actual transfers.

    The model charges ``Be`` per edge; the machine ships bucket-padded
    int32 index buffers (+weights). ``meters.bytes_h2d /
    meters.bytes_read_edges`` is the measured inflation factor; multiply
    ``p.Be`` by it to predict wall-clock transfer volume from the closed
    forms. Returns ``p.Be`` unchanged when nothing was physically
    streamed (device residency).
    """
    if getattr(meters, "bytes_h2d", 0.0) <= 0.0 or meters.bytes_read_edges <= 0.0:
        return float(p.Be)
    return float(p.Be) * meters.bytes_h2d / meters.bytes_read_edges


def select_strategy(
    p: IOParams, B_M: int | None, *, host_B_M: int | None = None
) -> StrategyChoice:
    """Adaptive selection (paper abstract / §III-B).

    SPU whenever both ping-pong interval copies fit; otherwise MPU with the
    largest feasible Q (which degenerates to DPU at Q == 0). MPU's modelled
    I/O is monotone in Q, so no search is needed.

    ``host_B_M`` extends the two-level model to the three-tier
    disk/host/device hierarchy of ``residency="disk"`` (the session
    passes ``host_memory_budget`` here for disk-backed compiles):
    ``B_M`` remains the fast-tier (device) budget that drives the
    SPU/MPU/DPU split, and ``host_B_M`` is the mid-tier (host RAM)
    budget. Edge topology that fits neither the device pins nor the host
    cache re-streams from disk every sweep, adding
    ``max(0, m·Be − device_pinned − host_B_M)`` to the modelled read — a
    strategy-independent-shaped term except that SPU's device pins (its
    budget leftover after both *padded* attribute copies, ``2·n_pad·Ba``,
    matching ``GraphSession._resolve_residency``) also shelter edges
    from the disk tier. Like SPU residency itself, the enforcement is
    block-granular, so the continuous term here may undershoot the
    enforced traffic by up to one (largest) sub-shard — the same
    documented slack as :class:`IOComparison`.
    """
    if B_M is None:
        choice = StrategyChoice("spu", p.P, 0.0, 0.0)
    elif B_M >= 2 * p.P * -(-p.n // p.P) * p.Ba:  # 2 · n_pad · Ba
        r, w = spu_io(p, B_M)
        choice = StrategyChoice("spu", p.P, r, w)
    else:
        Q = mpu_q(p, B_M)
        r, w = mpu_io(p, B_M)
        choice = StrategyChoice("dpu" if Q == 0 else "mpu", Q, r, w)
    if host_B_M is not None:
        if choice.strategy == "spu":
            n_pad = p.P * -(-p.n // p.P)
            pinned = (
                p.m * p.Be
                if B_M is None
                else max(0, B_M - 2 * n_pad * p.Ba)
            )
        else:
            pinned = 0
        disk = max(0.0, p.m * p.Be - min(pinned, p.m * p.Be) - host_B_M)
        choice = StrategyChoice(
            choice.strategy,
            choice.Q,
            choice.modelled_read + disk,
            choice.modelled_write,
        )
    return choice
