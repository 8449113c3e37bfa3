"""Execution plans — frozen, hashable job descriptions for a GraphSession.

An :class:`ExecutionPlan` is *what to run*: a vertex program, a strategy
name, iteration limits and tolerances, plus the program's Initialize
kwargs (e.g. a BFS root). It deliberately contains no device state — the
staged graph lives in :class:`repro.core.session.GraphSession` — so one
plan can be compiled against many sessions and one session can execute
many plans. Because plans are hashable they key the session's compile
cache directly, and because the engine's jitted block primitives take the
(frozen) program as a static argument, jit executables persist across
plans that share a program.

Program kwargs may contain numpy/JAX arrays (the SCC driver passes label
and mask vectors); they are frozen into content-hashed
:class:`FrozenArray` wrappers so the plan stays hashable with value
semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import numpy as np

from repro.core.vertex_programs import VertexProgram
from repro.obs.trace import TraceSpec
from repro.reliability.checkpoint import CheckpointSpec

__all__ = ["CheckpointSpec", "ExecutionPlan", "FrozenArray", "TraceSpec"]


@dataclasses.dataclass(frozen=True)
class FrozenArray:
    """An immutable, content-hashed snapshot of an array-valued kwarg."""

    data: bytes
    shape: tuple[int, ...]
    dtype: str

    @classmethod
    def freeze(cls, value) -> "FrozenArray":
        arr = np.asarray(value)
        return cls(data=arr.tobytes(), shape=arr.shape, dtype=str(arr.dtype))

    def thaw(self) -> np.ndarray:
        return np.frombuffer(self.data, dtype=np.dtype(self.dtype)).reshape(
            self.shape
        )


def _freeze_value(v):
    if isinstance(v, FrozenArray):
        return v
    if isinstance(v, (np.ndarray,)) or type(v).__module__.startswith("jax"):
        return FrozenArray.freeze(v)
    if isinstance(v, (list, tuple)):
        return tuple(_freeze_value(x) for x in v)
    if isinstance(v, np.generic):
        return v.item()
    return v


def _thaw_value(v):
    if isinstance(v, FrozenArray):
        return v.thaw()
    if isinstance(v, tuple):
        return tuple(_thaw_value(x) for x in v)
    return v


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """One job against a staged graph.

    Args:
      program: the vertex program (frozen dataclass — hashable).
      strategy: "auto" | "spu" | "dpu" | "mpu" | "fused" | a registered
        custom strategy name. "auto" resolves against the session's
        memory budget at compile time (paper's adaptive selection).
      max_iters: update-sweep budget.
      tol: convergence tolerance handed to ``program.changed``.
      residency: per-plan override of the session's residency axis —
        ``None`` (inherit), "device", "host", "disk" (disk-backed
        sessions only — blocks/tiles stream from the mmap'd ``.dsss``
        store) or "auto" (disk for disk-backed sessions, else host iff
        the session has a memory budget). See
        :class:`repro.core.session.GraphSession` for the semantics.
      execution: per-plan override of the session's execution axis —
        ``None`` (inherit), "per_block", "packed" or "auto". "per_block"
        is the host-scheduled legacy path (one jit dispatch per
        sub-shard); "packed" runs each update sweep as one compiled scan
        over the destination-aligned tile layout — under host residency
        the tile chunks are streamed with double-buffered prefetch, so
        out-of-core runs stay packed. "packed" is SPU/DPU/MPU
        only; fused/custom schedules downgrade to "per_block". "auto"
        picks "packed" wherever it applies. Modelled meters are identical
        in every case, and results within the fold contract. See
        :class:`repro.core.session.GraphSession`.
      activity: frontier-aware selective execution — ``"auto"`` (default)
        lets monotone programs (BFS/SSSP/WCC — ``program.monotone``) skip
        inactive source intervals, inactive packed tiles and inactive
        streamed chunks, so compute *and* physical
        ``bytes_h2d``/``bytes_disk_read`` shrink with the frontier;
        ``"off"`` forces full sweeps (the A/B baseline — every interval is
        processed and every chunk is streamed every sweep). Results are
        bit-identical either way: skipped work contributes exact
        ⊕-identities by the monotone contract. Non-monotone programs
        (PageRank) always run full sweeps regardless of this axis.
      checkpoint: sweep-level checkpoint/resume
        (:class:`repro.reliability.CheckpointSpec`) — ``None`` (default)
        disables snapshots; otherwise the engine atomically snapshots
        vertex state + activity bitmaps + cumulative meters to
        ``checkpoint.directory`` every ``checkpoint.every`` sweeps
        (keep-N pruned), and ``session.run(plan, resume_from=...)``
        restores one and continues, bit-identical to an uninterrupted
        run.
      trace: structured tracing (:class:`repro.obs.TraceSpec`) — ``None``
        (default) records nothing beyond what a globally enabled
        ``repro.obs.TRACER`` captures; a spec turns the span recorder on
        for this run (staging, per-sweep byte deltas, checkpoint writes)
        and, when ``trace.path`` is set, exports the run's spans as
        Perfetto-loadable Chrome ``trace_event`` JSON on completion.
        Observational only: deliberately *excluded* from
        :meth:`batch_key`, so traced and untraced requests still fuse (a
        fused batch traces under its first member's spec).
      program_kwargs: Initialize kwargs (e.g. ``{"root": 3}``). Arrays are
        frozen by content; pass a mapping, it is normalized to a sorted
        tuple in ``__post_init__``. Names are validated against
        ``program.accepted_kwargs()`` — an unknown name raises
        :class:`TypeError` here instead of being silently swallowed by the
        lifecycle methods' ``**kw`` catch-alls.
    """

    program: VertexProgram
    strategy: str = "auto"
    max_iters: int = 200
    tol: float = 1e-10
    residency: str | None = None
    execution: str | None = None
    activity: str = "auto"
    checkpoint: CheckpointSpec | None = None
    trace: TraceSpec | None = None
    program_kwargs: Any = ()

    def __post_init__(self):
        if self.checkpoint is not None and not isinstance(
            self.checkpoint, CheckpointSpec
        ):
            raise TypeError(
                "checkpoint must be a repro.reliability.CheckpointSpec or "
                f"None, got {type(self.checkpoint).__name__}"
            )
        if self.trace is not None and not isinstance(self.trace, TraceSpec):
            raise TypeError(
                "trace must be a repro.obs.TraceSpec or None, "
                f"got {type(self.trace).__name__}"
            )
        if self.residency not in (None, "device", "host", "disk", "auto"):
            raise ValueError(
                "residency must be None, 'device', 'host', 'disk' or 'auto', "
                f"got {self.residency!r}"
            )
        if self.execution not in (None, "per_block", "packed", "auto"):
            raise ValueError(
                "execution must be None, 'per_block', 'packed' or 'auto', "
                f"got {self.execution!r}"
            )
        if self.activity not in ("auto", "off"):
            raise ValueError(
                f"activity must be 'auto' or 'off', got {self.activity!r}"
            )
        kw = self.program_kwargs
        if isinstance(kw, Mapping):
            items = kw.items()
        else:
            items = tuple(kw)
        frozen = tuple(sorted((str(k), _freeze_value(v)) for k, v in items))
        accepted = self.program.accepted_kwargs()
        unknown = sorted(k for k, _ in frozen if k not in accepted)
        if unknown:
            if accepted:
                hint = f"accepted kwargs: {sorted(accepted)}"
            else:
                hint = "it accepts no program_kwargs"
            raise TypeError(
                f"unknown program_kwargs {unknown} for program "
                f"{self.program.name!r}; {hint}"
            )
        object.__setattr__(self, "program_kwargs", frozen)

    # -- accessors -----------------------------------------------------------
    def kwargs_dict(self) -> dict[str, Any]:
        """Thawed Initialize kwargs, ready for ``program.init_attrs(...)``."""
        return {k: _thaw_value(v) for k, v in self.program_kwargs}

    def with_kwargs(self, **kw) -> "ExecutionPlan":
        """A copy of this plan with updated program kwargs (e.g. new root)."""
        merged = self.kwargs_dict()
        merged.update(kw)
        return dataclasses.replace(self, program_kwargs=merged)

    def batch_key(self) -> tuple:
        """Plans sharing a batch_key can fuse into one streamed pass.

        This is the grouping key of both :meth:`GraphSession.run_batch`
        and the serving micro-batcher
        (:class:`repro.serving.server.GraphServer` buckets queued requests
        by ``(graph, batch_key())``): program, strategy, iteration limits
        and the residency/execution/activity axes must agree — Initialize
        kwargs
        (BFS roots, SSSP sources, seeds) may differ. It is a *necessary*
        condition; fusion additionally requires identical aux arrays,
        which ``run_batch`` re-verifies before fusing (and falls back to
        sequential execution when violated, e.g. two PageRank programs
        frozen with different damping).
        """
        return (
            self.program,
            self.strategy,
            self.max_iters,
            self.tol,
            self.residency,
            self.execution,
            self.activity,
            self.checkpoint,
        )

    def compatible_with(self, other: "ExecutionPlan") -> bool:
        """True iff the two plans may fuse into one streamed pass."""
        return self.batch_key() == other.batch_key()
