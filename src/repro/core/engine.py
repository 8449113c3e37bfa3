"""Back-compat engine facade over the Session/Plan execution API.

The NXgraph update engine (SPU / DPU / MPU schedules, paper §III-B) now
lives in :mod:`repro.core.session`: a :class:`~repro.core.session.
GraphSession` owns the device-staged DSSS blocks and executes
:class:`~repro.core.plan.ExecutionPlan` jobs against them, including
batched multi-query passes (``session.run_batch``).

:class:`NXGraphEngine` is kept as a thin shim for existing callers: it
binds one (graph, program) pair to a private session and forwards
``run()`` to ``session.run(plan)``. Direct engine construction is
**deprecated** for new code — it re-stages the graph per program, which is
exactly the coupling the session API removes. Prefer::

    session = GraphSession(graph, memory_budget=...)
    result  = session.run(ExecutionPlan(PageRank(), max_iters=20, tol=0.0))

``Meters`` / ``Result`` are re-exported unchanged.
"""
from __future__ import annotations

from repro.core.dsss import DSSSGraph
from repro.core.plan import ExecutionPlan
from repro.core.session import GraphSession, Meters, Result

__all__ = ["NXGraphEngine", "Meters", "Result"]


class NXGraphEngine:
    """Host-scheduled NXgraph engine over a :class:`DSSSGraph` (shim).

    Args:
      graph: sharded graph.
      program: vertex program (semiring decomposition of Update).
      strategy: "auto" | "spu" | "dpu" | "mpu" | "fused" | a registered
        custom strategy. "auto" applies the paper's adaptive selection
        from ``memory_budget``.
      memory_budget: bytes of fast-tier memory (B_M). ``None`` = unlimited.
      residency: "device" | "host" | "disk" | "auto" — whether the budget
        is merely modelled (device-staged blocks, seed behaviour) or
        enforced by host- or disk-streamed execution ("disk" needs a
        disk-backed shared ``session`` opened via
        :meth:`GraphSession.open`). See :class:`GraphSession`. ``None``
        defaults to "auto" (host streaming iff a budget is set).
      execution: "per_block" | "packed" | "auto" —
        host-scheduled dispatch-per-sub-shard vs. one compiled scan per
        update sweep (chunk-streamed under host residency). See
        :class:`GraphSession`. ``None`` defaults to "auto" ("packed"
        wherever it applies); model meters are identical.
      packing: "adaptive" | "subshard" | "auto" tile layout for packed
        execution (see :class:`GraphSession`). ``None`` defaults to
        "auto".
      Be: bytes per edge in the I/O model (8 = two int32 ids).
      Bv: bytes per vertex id.
      session: share an existing staged session instead of staging a new
        one (the upgrade path to the Session/Plan API).
    """

    def __init__(
        self,
        graph: DSSSGraph,
        program,
        *,
        strategy: str = "auto",
        memory_budget: int | None = None,
        residency: str | None = None,
        execution: str | None = None,
        packing: str | None = None,
        Be: int | None = None,
        Bv: int | None = None,
        session: GraphSession | None = None,
    ):
        if session is None:
            session = GraphSession(
                graph,
                memory_budget=memory_budget,
                residency="auto" if residency is None else residency,
                packing="auto" if packing is None else packing,
                Be=8 if Be is None else Be,
                Bv=4 if Bv is None else Bv,
            )
        else:
            # A shared session already fixes the staging + I/O-model
            # configuration; reject silently-ignored conflicting arguments.
            if session.graph is not graph:
                raise ValueError(
                    "session was staged for a different graph object than `graph`"
                )
            if residency is not None and session.resolved_residency(
                residency
            ) != session.resolved_residency():
                raise ValueError(
                    f"residency={residency!r} conflicts with the shared "
                    f"session's residency ({session.residency!r}); configure "
                    "it on the GraphSession"
                )
            if memory_budget is not None and memory_budget != session.memory_budget:
                raise ValueError(
                    f"memory_budget={memory_budget} conflicts with the shared "
                    f"session's budget ({session.memory_budget}); configure the "
                    "budget on the GraphSession"
                )
            expect_Be = None if Be is None else Be + (4 if session.has_weights else 0)
            if expect_Be is not None and expect_Be != session.Be:
                raise ValueError(
                    f"Be={Be} conflicts with the shared session's edge size; "
                    "configure Be on the GraphSession"
                )
            if Bv is not None and Bv != session.Bv:
                raise ValueError(
                    f"Bv={Bv} conflicts with the shared session's vertex-id "
                    "size; configure Bv on the GraphSession"
                )
            if (
                packing is not None
                and packing != "auto"
                and packing != session.packing
            ):
                raise ValueError(
                    f"packing={packing!r} conflicts with the shared session's "
                    f"tile packing ({session.packing!r}); configure it on the "
                    "GraphSession"
                )
        self.session = session
        self.g = graph
        self.program = program
        self.memory_budget = session.memory_budget
        self._strategy = strategy
        # Per-plan override: a shared session keeps its own default and
        # other engines on the same session are unaffected.
        self._execution = execution
        compiled = session.compile(
            ExecutionPlan(program, strategy=strategy, execution=execution)
        )
        self.params = compiled.params
        self.choice = compiled.choice
        self.resident = compiled.resident
        self.execution = compiled.execution

    # -- staged state (delegated to the shared session) ----------------------
    @property
    def blocks(self):
        return self.session.blocks

    @property
    def Be(self) -> int:
        return self.session.Be

    @property
    def Bv(self) -> int:
        return self.session.Bv

    @property
    def has_weights(self) -> bool:
        return self.session.has_weights

    # -- public API ----------------------------------------------------------
    def run(
        self,
        max_iters: int = 200,
        tol: float = 1e-10,
        checkpoint=None,
        resume_from=None,
        cancel=None,
        **program_kwargs,
    ) -> Result:
        """Forward to ``session.run``.

        ``checkpoint`` (a :class:`repro.reliability.CheckpointSpec`),
        ``resume_from`` and ``cancel`` pass straight through to the
        Session/Plan reliability machinery — see
        :meth:`GraphSession.run`.
        """
        plan = ExecutionPlan(
            self.program,
            strategy=self._strategy,
            max_iters=max_iters,
            tol=tol,
            execution=self._execution,
            checkpoint=checkpoint,
            program_kwargs=program_kwargs,
        )
        return self.session.run(plan, resume_from=resume_from, cancel=cancel)
