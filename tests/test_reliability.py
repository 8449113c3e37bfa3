"""The reliability layer (repro.reliability) — chaos matrix and regressions.

Everything here runs on tiny graphs so the lane stays fast, but the
assertions are the strong ones the subsystem promises:

* **Crash/resume**: a PageRank/BFS run killed by an injected crash at
  sweep N and resumed from its latest snapshot produces *bit-identical*
  results and *field-identical* meters (minus wall clock) vs the same
  run never interrupted — across residency {device, host, disk} ×
  execution {per_block, packed};
* **Self-healing reads**: an injected-corrupt segment is retried with
  backoff and healed, or quarantined behind a structured
  ``DegradedReadError`` naming the exact segment and tile range — the
  engine never computes on garbage. ``verify --repair`` rebuilds a
  really-byte-flipped container from its raw edge source;
* **Serving degradation**: past-deadline requests are shed or cancelled
  cooperatively at a sweep boundary (other in-flight requests
  unaffected), transient faults retry with backoff, a persistently
  failing graph trips its circuit breaker and recovers half-open, and a
  faulted wave completes bit-identical with its scraped ``/metrics``
  counters equal to ``ServerStats``;
* **Pool regressions**: pinned sessions are never evicted, deferred
  eviction on release drops stale staged bytes, acquire/evict races are
  atomic under the pool lock;
* **CheckpointManager hardening**: crash debris (orphan tmp dirs,
  truncated step dirs) is never offered for restore and is swept by GC;
* the ``repro.runtime.fault`` shim keeps exporting the legacy names.
"""
import asyncio
import dataclasses
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core import BFS, ExecutionPlan, GraphSession, PageRank, build_dsss
from repro.core.plan import CheckpointSpec
from repro.graph.generators import erdos_renyi, rmat
from repro.graph.preprocess import degree_and_densify
from repro.reliability import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
    InjectedCrash,
    SnapshotError,
    TransientFault,
    latest_snapshot,
    list_snapshots,
)
from repro.obs import parse_prometheus
from repro.serving import (
    CircuitOpenError,
    DeadlineExceeded,
    GraphServer,
    QueryRequest,
    SessionPool,
)
from repro.storage import DegradedReadError, ReadPolicy, write_dsss

pytestmark = pytest.mark.chaos

RESIDENCIES = ["device", "host", "disk"]
EXECUTIONS = ["per_block", "packed"]


def _graph(n=120, m=700, seed=11, P=4):
    src, dst = erdos_renyi(n, m, seed=seed)
    el = degree_and_densify(src, dst, drop_self_loops=True)
    return build_dsss(el, P)


@pytest.fixture(scope="module")
def graph():
    return _graph()


@pytest.fixture(scope="module")
def dsss_path(graph, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("dsss") / "g.dsss")
    write_dsss(graph, path)
    return path


def _session(graph, dsss_path, residency, execution, **kw):
    if residency == "disk":
        return GraphSession.open(dsss_path, execution=execution, **kw)
    return GraphSession(graph, residency=residency, execution=execution, **kw)


def _meters_dict(meters, *, ignore_wall=True):
    d = {f.name: getattr(meters, f.name) for f in dataclasses.fields(meters)}
    if ignore_wall:
        d.pop("wall_seconds")
    return d


# ---------------------------------------------------------------------------
# FaultPlan / FaultInjector unit contract
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(site="nope")
        with pytest.raises(ValueError):
            FaultSpec(site="h2d", kind="meteor")
        with pytest.raises(ValueError):
            FaultSpec(site="h2d", rate=1.5)
        with pytest.raises(TypeError):
            FaultPlan(specs=[1, 2])

    def test_crash_budget_spent_once(self):
        inj = FaultPlan.crash_at_sweep(2).injector()
        inj.check("sweep", 0)
        inj.check("sweep", 1)
        with pytest.raises(InjectedCrash):
            inj.check("sweep", 2)
        # the budget is spent: a resumed run passes the same boundary
        inj.check("sweep", 2)
        assert inj.fired("sweep") == 1

    def test_rate_coin_is_deterministic(self):
        def decisions(seed):
            inj = FaultPlan.h2d_transient(rate=0.4, times=None, seed=seed).injector()
            out = []
            for i in range(64):
                try:
                    inj.check("h2d", f"id:{i}")
                    out.append(0)
                except TransientFault:
                    out.append(1)
            return out

        assert decisions(7) == decisions(7)
        assert decisions(7) != decisions(8)

    def test_times_budget_bounds_rate_faults(self):
        inj = FaultPlan.h2d_transient(rate=1.0, times=3, seed=0).injector()
        fired = 0
        for i in range(10):
            try:
                inj.check("h2d", i)
            except TransientFault:
                fired += 1
        assert fired == 3
        assert inj.fired() == 3

    def test_merge_keeps_both_specs(self):
        plan = FaultPlan.crash_at_sweep(1).merge(FaultPlan.storage_corrupt("p_src"))
        assert len(plan.specs) == 2
        assert isinstance(plan.injector(), FaultInjector)


# ---------------------------------------------------------------------------
# Crash → snapshot → resume: bit-identity + meter identity across the matrix
# ---------------------------------------------------------------------------
class TestCrashResume:
    @pytest.mark.parametrize("residency", RESIDENCIES)
    @pytest.mark.parametrize("execution", EXECUTIONS)
    @pytest.mark.parametrize("program", ["pagerank", "bfs"])
    def test_resume_bit_identical(
        self, graph, dsss_path, tmp_path, program, residency, execution
    ):
        # Each crash lands after the sweep-4 snapshot and before the run
        # converges (BFS from root 3 takes five sweeps on this graph).
        run_kw, crash = {
            "pagerank": (dict(max_iters=6, tol=0.0), 5),
            "bfs": (dict(program_kwargs={"root": 3}), 4),
        }[program]
        prog = PageRank() if program == "pagerank" else BFS()
        plan = ExecutionPlan(
            prog,
            checkpoint=CheckpointSpec(
                directory=str(tmp_path / "snaps"), every=2, keep=2
            ),
            **run_kw,
        )
        ref = _session(graph, dsss_path, residency, execution).run(
            dataclasses.replace(plan, checkpoint=None)
        )

        sess = _session(graph, dsss_path, residency, execution)
        sess.inject_faults(FaultPlan.crash_at_sweep(crash))
        with pytest.raises(InjectedCrash):
            sess.run(plan)
        snaps = list_snapshots(str(tmp_path / "snaps"))
        assert [s.split("/")[-1] for s in snaps] == [
            "sweep_00000002.npz",
            "sweep_00000004.npz",
        ]

        resumed = sess.run(plan, resume_from=str(tmp_path / "snaps"))
        assert (
            np.asarray(resumed.output) == np.asarray(ref.output)
        ).all(), "resumed result is not bit-identical"
        assert _meters_dict(resumed.meters) == _meters_dict(ref.meters)

    def test_bfs_resume_on_disk(self, graph, dsss_path, tmp_path):
        plan = ExecutionPlan(
            BFS(),
            program_kwargs={"root": 3},
            checkpoint=CheckpointSpec(directory=str(tmp_path / "s"), every=1),
        )
        ref = GraphSession.open(dsss_path, execution="packed").run(
            dataclasses.replace(plan, checkpoint=None)
        )
        sess = GraphSession.open(dsss_path, execution="packed")
        sess.inject_faults(FaultPlan.crash_at_sweep(2))
        with pytest.raises(InjectedCrash):
            sess.run(plan)
        resumed = sess.run(plan, resume_from=True)  # True → plan's directory
        assert (np.asarray(resumed.output) == np.asarray(ref.output)).all()
        assert _meters_dict(resumed.meters) == _meters_dict(ref.meters)

    def test_resume_rejects_mismatched_plan(self, graph, tmp_path):
        ck = CheckpointSpec(directory=str(tmp_path), every=1)
        sess = GraphSession(graph)
        sess.run(ExecutionPlan(PageRank(), max_iters=2, tol=0.0, checkpoint=ck))
        with pytest.raises(SnapshotError):
            sess.run(
                ExecutionPlan(BFS(), program_kwargs={"root": 0}),
                resume_from=latest_snapshot(str(tmp_path)),
            )

    def test_resume_from_empty_dir_is_fresh_start(self, graph, tmp_path):
        ref = GraphSession(graph).run(ExecutionPlan(PageRank(), max_iters=3, tol=0.0))
        got = GraphSession(graph).run(
            ExecutionPlan(PageRank(), max_iters=3, tol=0.0),
            resume_from=str(tmp_path),  # exists, holds no snapshots
        )
        assert (np.asarray(got.output) == np.asarray(ref.output)).all()

    def test_checkpoint_in_plan_key(self, tmp_path):
        a = ExecutionPlan(PageRank())
        b = ExecutionPlan(
            PageRank(), checkpoint=CheckpointSpec(directory=str(tmp_path))
        )
        assert a.batch_key() != b.batch_key()
        with pytest.raises(TypeError):
            ExecutionPlan(PageRank(), checkpoint=str(tmp_path))


# ---------------------------------------------------------------------------
# Self-healing storage reads
# ---------------------------------------------------------------------------
class TestSelfHealingReads:
    def test_transient_corruption_heals(self, graph, dsss_path):
        plan = ExecutionPlan(PageRank(), max_iters=4, tol=0.0)
        ref = GraphSession.open(dsss_path).run(plan)
        sess = GraphSession.open(
            dsss_path,
            verify=False,
            read_policy=ReadPolicy(max_retries=3, backoff_s=0.0),
            fault_plan=FaultPlan.storage_corrupt("p_dst", times=2),
        )
        got = sess.run(plan)
        assert sess.store.healed_reads >= 1
        assert not sess.store.quarantined
        assert (np.asarray(got.output) == np.asarray(ref.output)).all()

    def test_persistent_corruption_quarantines(self, dsss_path):
        sess = GraphSession.open(
            dsss_path,
            verify=False,
            read_policy=ReadPolicy(max_retries=2, backoff_s=0.0),
            fault_plan=FaultPlan.storage_corrupt("p_dst", times=None),
        )
        plan = ExecutionPlan(PageRank(), max_iters=3)
        with pytest.raises(DegradedReadError) as ei:
            sess.run(plan)
        err = ei.value
        assert err.segment == "p_dst"
        assert err.attempts == 3  # 1 + max_retries
        assert err.tile_range is not None
        assert "p_dst" in sess.store.quarantined
        # quarantine short-circuits: the same structured error, instantly
        with pytest.raises(DegradedReadError):
            sess.run(plan)

    def test_short_read_quarantines(self, dsss_path):
        sess = GraphSession.open(
            dsss_path,
            verify=False,
            read_policy=ReadPolicy(max_retries=1, backoff_s=0.0),
            fault_plan=FaultPlan.storage_short("blk_", times=None),
        )
        with pytest.raises(DegradedReadError):
            sess.run(ExecutionPlan(PageRank(), max_iters=3, execution="per_block"))

    def test_no_policy_keeps_failfast_contract(self, dsss_path):
        from repro.storage import ChecksumError

        sess = GraphSession.open(
            dsss_path,
            verify=False,
            fault_plan=FaultPlan.storage_corrupt("p_dst", times=None),
        )
        sess.store.attach_faults(sess.fault_injector)
        with pytest.raises(ChecksumError):
            sess.store.verify()

    def test_cli_repair_rebuilds_flipped_container(self, tmp_path):
        from repro.storage.__main__ import main as storage_main

        edges = tmp_path / "edges.txt"
        rng = np.random.default_rng(5)
        lines = [
            f"{a} {b}"
            for a, b in zip(rng.integers(0, 60, 400), rng.integers(0, 60, 400))
        ]
        edges.write_text("\n".join(lines) + "\n")
        out = str(tmp_path / "g.dsss")
        assert storage_main(["build", str(edges), out, "--P", "4"]) == 0

        from repro.storage import open_dsss

        seg = next(iter(open_dsss(out, verify=False).segments.values()))
        with open(out, "r+b") as f:  # real media damage, not an injector
            f.seek(seg.offset + 1)
            byte = f.read(1)
            f.seek(seg.offset + 1)
            f.write(bytes([byte[0] ^ 0xFF]))
        assert storage_main(["verify", out]) == 1
        assert storage_main(["verify", out, "--repair"]) == 1  # no --source
        assert (
            storage_main(["verify", out, "--repair", "--source", str(edges)]) == 0
        )
        assert storage_main(["verify", out]) == 0  # clean after the swap

    def test_repair_noop_on_clean_container(self, dsss_path):
        from repro.reliability.repair import repair_dsss

        report = repair_dsss(dsss_path)
        assert report["damaged"] == []
        assert report["repaired"] is False


# ---------------------------------------------------------------------------
# Serving: deadlines, retries, circuit breaker
# ---------------------------------------------------------------------------
SERVE_KW = dict(residency="host", execution="per_block", memory_budget=4096)


class TestServingDegradation:
    def test_expired_request_is_shed(self, graph):
        pool = SessionPool()
        key = pool.ensure(graph, **SERVE_KW)
        srv = GraphServer(pool)
        with pytest.raises(DeadlineExceeded):
            srv.serve(
                [
                    QueryRequest(
                        key,
                        ExecutionPlan(PageRank(), max_iters=50, tol=0.0),
                        deadline_s=1e-6,
                    )
                ]
            )
        st = srv.stats()
        assert st.timeouts == 1
        assert st.failed == 0  # a timeout is a shed, not a failure

    def test_midrun_cancel_leaves_others_unaffected(self, graph):
        pool = SessionPool()
        key = pool.ensure(graph, **SERVE_KW)

        async def go():
            async with GraphServer(pool, max_batch=1, max_wait_ms=0.0) as srv:
                doomed = await srv.submit(
                    QueryRequest(
                        key,
                        ExecutionPlan(PageRank(), max_iters=5000, tol=0.0),
                        deadline_s=0.05,
                    )
                )
                fine = await srv.submit(
                    QueryRequest(key, ExecutionPlan(BFS(), program_kwargs={"root": 0}))
                )
                got = await asyncio.gather(doomed, fine, return_exceptions=True)
                return got, srv.stats()

        (doomed, fine), st = asyncio.run(go())
        assert isinstance(doomed, DeadlineExceeded)
        assert not isinstance(fine, Exception)
        ref = GraphSession(graph, **SERVE_KW).run(
            ExecutionPlan(BFS(), program_kwargs={"root": 0})
        )
        assert (np.asarray(fine.result.output) == np.asarray(ref.output)).all()
        assert st.timeouts == 1 and st.failed == 0

    def test_transient_fault_retries_to_identical_result(self, graph):
        plan = ExecutionPlan(PageRank(), max_iters=4, tol=0.0)
        ref = GraphSession(graph, **SERVE_KW).run(plan)
        pool = SessionPool()
        key = pool.ensure(graph, **SERVE_KW)
        # burst bigger than the fetch layer's own retry budget → escapes
        # to the serving retry loop
        pool.session(key).inject_faults(
            FaultPlan.h2d_transient(rate=1.0, times=5, seed=3)
        )
        srv = GraphServer(pool)
        out = srv.serve([QueryRequest(key, plan, max_retries=3)])
        st = srv.stats()
        assert st.retries >= 1 and st.completed == 1 and st.failed == 0
        assert (np.asarray(out[0].result.output) == np.asarray(ref.output)).all()

    def test_retry_budget_exhaustion_fails(self, graph):
        pool = SessionPool()
        key = pool.ensure(graph, **SERVE_KW)
        pool.session(key).inject_faults(
            FaultPlan.h2d_transient(rate=1.0, times=None, seed=1)
        )
        srv = GraphServer(pool)
        with pytest.raises(TransientFault):
            srv.serve(
                [QueryRequest(key, ExecutionPlan(PageRank(), max_iters=3), max_retries=1)]
            )
        st = srv.stats()
        assert st.retries == 1 and st.failed == 1

    def test_circuit_breaker_trips_and_recovers(self, graph):
        pool = SessionPool(breaker_threshold=2, breaker_cooldown_s=0.15)
        key = pool.ensure(graph, **SERVE_KW)
        sess = pool.session(key)
        sess.inject_faults(FaultPlan.h2d_transient(rate=1.0, times=None, seed=1))
        srv = GraphServer(pool)
        plan = ExecutionPlan(PageRank(), max_iters=3)
        for _ in range(2):
            with pytest.raises(TransientFault):
                srv.serve([QueryRequest(key, plan)])
        assert pool.breaker_open(key)
        with pytest.raises(CircuitOpenError):
            srv.serve([QueryRequest(key, plan)])
        assert srv.stats().breaker_sheds == 1
        assert pool.stats().breakers_open == 1
        time.sleep(0.2)
        sess.inject_faults(None)  # the graph "recovers"
        out = srv.serve([QueryRequest(key, plan)])  # half-open trial
        assert len(out) == 1
        assert pool.stats().breakers_open == 0

    def test_failed_halfopen_trial_retrips(self, graph):
        pool = SessionPool(breaker_threshold=2, breaker_cooldown_s=0.05)
        key = pool.ensure(graph, **SERVE_KW)
        pool.session(key).inject_faults(
            FaultPlan.h2d_transient(rate=1.0, times=None, seed=1)
        )
        srv = GraphServer(pool)
        plan = ExecutionPlan(PageRank(), max_iters=3)
        for _ in range(2):
            with pytest.raises(TransientFault):
                srv.serve([QueryRequest(key, plan)])
        time.sleep(0.08)  # cooldown expires → half-open
        with pytest.raises(TransientFault):
            srv.serve([QueryRequest(key, plan)])  # trial fails...
        assert pool.breaker_open(key)  # ...and re-trips instantly

    def test_deadline_validation(self):
        with pytest.raises(ValueError):
            QueryRequest("g", ExecutionPlan(PageRank()), deadline_s=0.0)
        with pytest.raises(ValueError):
            QueryRequest("g", ExecutionPlan(PageRank()), max_retries=-1)


@pytest.fixture(scope="module")
def faulted_wave():
    """Eight BFS queries served, in batches of four, from an R-MAT graph
    (scale 9, P = 8) streamed under a quarter of its edge bytes, through a
    deterministic burst of transient H2D faults larger than the fetch
    layer's retry budget on top of background transient noise; then one
    scrape of the server's ``/metrics`` endpoint."""
    el = degree_and_densify(*rmat(9, 16, seed=0), drop_self_loops=True)
    g = build_dsss(el, 8)
    kw = dict(
        memory_budget=int(g.total_edge_bytes(8) * 0.25),
        residency="host", execution="per_block",
    )
    plans = [
        ExecutionPlan(
            BFS(), strategy="spu", max_iters=g.n + 1,
            program_kwargs={"root": r},
        )
        for r in range(8)
    ]
    solo = [GraphSession(g, **kw).run(p) for p in plans]
    pool = SessionPool(breaker_threshold=16)
    pool.register("g", g, **kw)
    pool.session("g").inject_faults(
        FaultPlan.h2d_transient(rate=1.0, times=5, seed=7).merge(
            FaultPlan.h2d_transient(rate=0.02, times=None, seed=11)
        )
    )
    server = GraphServer(pool, max_batch=4, max_wait_ms=2.0, telemetry_port=0)
    try:
        served = server.serve(
            [QueryRequest("g", p, max_retries=4) for p in plans]
        )
        stats = server.stats()
        text = urllib.request.urlopen(
            server.telemetry.url("/metrics"), timeout=10
        ).read().decode()
    finally:
        server.shutdown_telemetry()
    return solo, served, stats, parse_prometheus(text)


class TestFaultedServingWave:
    def test_wave_recovers_bit_identical(self, faulted_wave):
        solo, served, st, _ = faulted_wave
        assert st.failed == 0 and st.timeouts == 0
        assert st.completed == len(solo) == len(served)
        assert st.retries >= 1, "the burst never reached the serving retry loop"
        for s, q in zip(solo, served):
            np.testing.assert_array_equal(s.attrs, q.result.attrs)

    def test_scraped_counters_equal_stats(self, faulted_wave):
        _, _, st, scraped = faulted_wave
        for f in ("completed", "retries", "timeouts", "breaker_sheds", "failed"):
            assert scraped[(f"repro_serving_{f}_total", ())] == getattr(st, f), f
        transient = scraped.get(
            ("repro_transient_retries_total", (("site", "h2d"),)), 0
        )
        assert transient >= 1, "fetch-layer retry counter never moved"


# ---------------------------------------------------------------------------
# SessionPool regressions: pinning, deferred eviction, race atomicity
# ---------------------------------------------------------------------------
class TestPoolRegressions:
    def test_pinned_session_never_evicted(self, graph):
        pool = SessionPool(max_open=1)
        a = pool.ensure(graph, residency="host")
        b = pool.ensure(_graph(seed=12), residency="host")
        sess_a = pool.acquire(a)
        pool.session(b)  # over max_open, but `a` is pinned
        assert pool._entries[a].session is sess_a  # survived
        pool.release(a)
        # a is now the idle LRU victim; the deferred eviction on release
        # restored the bound
        assert pool.stats().open_sessions == 1
        assert pool._entries[a].session is None

    def test_release_drops_stale_staged_bytes(self, graph):
        # Both graphs pinned with max_open=1: bounds temporarily exceeded.
        pool = SessionPool(max_open=1)
        a = pool.ensure(graph, residency="host")
        b = pool.ensure(_graph(seed=13), residency="host")
        pool.acquire(a)
        pool.acquire(b)
        assert pool.stats().open_sessions == 2  # nothing evictable yet
        pool.release(a)
        stats = pool.stats()
        assert stats.open_sessions == 1  # stale bytes dropped on release
        assert pool._entries[b].session is not None  # still-pinned survivor

    def test_double_release_raises(self, graph):
        pool = SessionPool()
        a = pool.ensure(graph)
        pool.acquire(a)
        pool.release(a)
        with pytest.raises(RuntimeError):
            pool.release(a)

    def test_evict_respects_pin(self, graph):
        pool = SessionPool()
        a = pool.ensure(graph)
        pool.acquire(a)
        assert pool.evict(a) is False
        pool.release(a)
        assert pool.evict(a) is True
        assert pool.evict(a) is False  # already cold

    def test_acquire_evict_race_is_atomic(self, graph):
        pool = SessionPool(max_open=1)
        keys = [pool.ensure(_graph(seed=20 + i), residency="host") for i in range(3)]
        errors = []

        def hammer(key):
            try:
                for _ in range(25):
                    s = pool.acquire(key)
                    assert s is not None
                    assert pool._entries[key].session is s  # pin held it open
                    pool.release(key)
            except Exception as e:  # pragma: no cover - failure capture
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(k,)) for k in keys]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        assert all(e.in_use == 0 for e in pool._entries.values())
        assert pool.stats().open_sessions <= 1


# ---------------------------------------------------------------------------
# CheckpointManager hardening
# ---------------------------------------------------------------------------
class TestCheckpointManagerHardening:
    def _state(self, v):
        return {"w": np.full((4,), float(v)), "b": np.arange(3.0) * v}

    def test_crash_debris_never_offered_for_restore(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
        mgr.save(1, self._state(1))
        # Simulate a crash mid-save of step 2: orphan tmp dir, and a
        # published dir whose payload never landed.
        (tmp_path / ".tmp_step_2").mkdir()
        (tmp_path / ".tmp_step_2" / "arrays.npz").write_bytes(b"partial")
        (tmp_path / "step_0000000003").mkdir()
        (tmp_path / "step_0000000003" / "manifest.json").write_text("{}")
        assert mgr.all_steps() == [1]  # debris invisible
        restored, step = mgr.restore(self._state(0))
        assert step == 1
        assert (np.asarray(restored["w"]) == 1.0).all()
        mgr.save(2, self._state(2))  # next save sweeps the debris
        assert not (tmp_path / ".tmp_step_2").exists()
        assert not (tmp_path / "step_0000000003").exists()
        assert mgr.all_steps() == [1, 2]

    def test_resave_same_step_never_loses_the_copy(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
        mgr.save(5, self._state(1))
        mgr.save(5, self._state(2))  # supersede in place
        restored, step = mgr.restore(self._state(0))
        assert step == 5
        assert (np.asarray(restored["w"]) == 2.0).all()
        assert not (tmp_path / ".trash_step_5").exists()

    def test_injected_crash_during_publish(self, tmp_path, monkeypatch):
        """Crash after the old step is renamed aside but before the new
        one lands: the trash copy still exists → nothing was lost; the
        next save completes and sweeps it."""
        import os as _os

        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
        mgr.save(7, self._state(1))
        real_rename = _os.rename
        calls = {"n": 0}

        def crashy(src, dst):
            calls["n"] += 1
            if calls["n"] == 2:  # first = aside, second = publish
                raise OSError("injected crash at publish")
            real_rename(src, dst)

        monkeypatch.setattr("repro.checkpoint.manager.os.rename", crashy)
        with pytest.raises(OSError):
            mgr.save(7, self._state(2))
        monkeypatch.undo()
        assert mgr.all_steps() == []  # step 7 is mid-swap...
        assert (tmp_path / ".trash_step_7").exists()  # ...but not lost
        mgr.save(8, self._state(3))  # recovery save sweeps the debris
        assert mgr.all_steps() == [8]
        assert not (tmp_path / ".trash_step_7").exists()

    def test_keep_n_pruning(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in range(1, 6):
            mgr.save(s, self._state(s))
        assert mgr.all_steps() == [4, 5]


# ---------------------------------------------------------------------------
# Legacy shim
# ---------------------------------------------------------------------------
def test_runtime_fault_shim_reexports():
    import repro.reliability.faults as canonical
    import repro.runtime.fault as shim

    for name in (
        "FailureInjector",
        "SimulatedFailure",
        "StepTimer",
        "StragglerWatchdog",
        "elastic_device_count",
    ):
        assert getattr(shim, name) is getattr(canonical, name)
