"""The benchmark harness: one cell, one seed, one process.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Everything a cell needs is found by name from ``BENCHMARK.json``, so a
new cell, family or metric is new files and entries only:

- the cell's configuration file (``configs[].file``): the generator that
  draws its graph and that generator's sizes, the engine's interval count
  ``P``, and the memory tier it is served from;
- the generator ``bench/generators/<generator>.py``, with
  ``edges(seed, config) -> (src, dst, num_labels)``;
- the tier ``bench/tiers/<tier>.py``, with
  ``open_session(graph, config, workdir)``; its name is the residency the
  engine must resolve;
- the traffic file ``bench/traffic/<traffic>.json``: a job kind and its
  parameters and limits; the kind ``bench/jobs/<job>.py`` holds a class
  ``Jobs`` (interface in ``bench/jobs/pagerank.py``);
- each per-layer metric's reader ``bench/metrics/<name>.py``, a module
  with ``read(run) -> float | None`` over a :class:`RunRecord`.

A name with no file is refused before anything runs on the device.

A run: require the chips, generate the graph on the device from the seed,
hand it to the engine's preprocessing, warm up, run whole jobs back to back
until the first one that ends at or after ``--seconds`` (the window), read
peak device memory, free the engine, check the answers against the plain
reference, and print one JSON line last on stdout.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from bench import reference
from bench import trace as trace_mod

__all__ = ["main", "Done", "RunRecord", "CompileClock"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CompileClock:
    """Sums JAX backend-compile time (cache retrievals included)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Done:
    """One finished job: its answer (dense ids) and its sweeps."""

    sweeps: int
    output: np.ndarray


@dataclasses.dataclass
class RunRecord:
    """What the per-layer metric readers read."""

    traffic: object  # the cell's ``Jobs`` (bench/jobs/<kind>.py)
    device_kind: str
    build_s: float
    compile_s: float
    n: int  # vertices of the benchmark's own cleaned graph
    m: int  # edges of the same
    sweeps: int  # sweeps of the jobs in the window
    trace: dict | None  # bench.trace.reduce_trace of the window, traced runs


def log(msg: str) -> None:
    print(msg, flush=True)


def require_chip(chips: int):
    """JAX's devices must be TPUs, at least ``chips`` of them; else exit."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(
            f"bench: no TPU — JAX's first device is {devs[0].platform!r}; "
            "this benchmark never runs elsewhere"
        )
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def load_part(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``, as a module."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no {kind} file for {name!r} ({path.relative_to(root)})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """The cell, its configuration entry and file, and its traffic file."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    for kind, part in (("generators", config["generator"]), ("tiers", config["tier"]),
                       ("jobs", traffic["job"])):
        load_part(root, kind, part)
    for m in spec["per_layer"]:
        load_part(root, "metrics", m["name"])
    return spec, cell, config, traffic


def generate(root: Path, config: dict, seed: int):
    """The configuration's raw graph, drawn by its generator from the seed."""
    return load_part(root, "generators", config["generator"]).edges(seed, config)


def deploy(root: Path, config: dict, src, dst, workdir: Path):
    """The engine's preprocessing of the raw edges, and the session to serve from."""
    from repro.core import build_dsss
    from repro.graph.preprocess import degree_and_densify

    el = degree_and_densify(src, dst, drop_self_loops=True)
    g = build_dsss(el, int(config["P"]))
    del el
    return load_part(root, "tiers", config["tier"]).open_session(g, config, workdir)


def make_traffic(root: Path, params: dict, graph, seed: int):
    """The jobs a traffic file describes, for one engine graph."""
    return load_part(root, "jobs", params["job"]).Jobs(params, graph, seed)


def _metric_applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _peak_memory(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def run_cell(args, root: Path, t_start: float, clock: CompileClock) -> dict:
    spec, cell, config, traffic_params = load_cell(root, args.workload)
    devices = require_chip(int(cell["chips"]))
    dev = devices[0]
    from repro import compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={compile_cache.enable()}")
    workdir = BENCH_DIR / "work" / f"{cell['name']}-{args.seed}"
    shutil.rmtree(workdir, ignore_errors=True)
    workdir.mkdir(parents=True)
    try:
        return _run(args, root, spec, cell, config, traffic_params, devices,
                    workdir, t_start, clock)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _run(args, root, spec, cell, config, traffic_params, devices, workdir,
         t_start, clock) -> dict:
    dev = devices[0]
    t = time.perf_counter()
    src, dst, num_labels = generate(root, config, args.seed)
    gen_s = time.perf_counter() - t
    t = time.perf_counter()
    session = deploy(root, config, src, dst, workdir)
    build_s = time.perf_counter() - t
    tier = config["tier"]
    graph = session.graph
    id_to_index = np.array(graph.edgelist.id_to_index)  # a copy: the store is closed before the check
    traffic = make_traffic(root, traffic_params, graph, args.seed)
    log(f"setup: gen_s={gen_s:.3f} build_s={build_s:.3f} n={graph.n} m={graph.m} "
        f"P={graph.P} tier={tier}")
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        traffic.warmup(session)
    resolved = session.resolved_residency()
    if resolved != tier:
        raise RuntimeError(f"the engine resolved residency {resolved!r}, config says {tier!r}")
    compile_s, compiles0 = clock.seconds, clock.compiles
    log(f"setup: warmup_s={time.perf_counter() - t:.3f} compile_s={compile_s:.3f} "
        f"compiles={clock.compiles} cache_hits={clock.cache_hits}")

    trace_dir = workdir / "trace"
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    done: list[Done] = []
    failed = 0
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    while True:
        i = len(done) + failed
        try:
            with jax.profiler.TraceAnnotation("bench.job", index=i):
                d = traffic.run(session, i)
        except Exception:  # a failed job is counted and reported, then the window ends
            traceback.print_exc()
            failed += 1
            break
        done.append(d)
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = clock.compiles - compiles0
    log(f"window: jobs={len(done)} failed={failed} seconds={window_s:.6f} "
        f"compiles={window_compiles}")
    memory_peak = _peak_memory(devices)
    del session, graph
    gc.collect()

    with jax.profiler.TraceAnnotation("bench.check"):
        t = time.perf_counter()
        ref = reference.clean_edges(src, dst, num_labels)
        del src, dst
        if done:
            work, checks = traffic.check(ref, id_to_index, done)
        else:
            work, checks = 0, {}
        log(f"check: seconds={time.perf_counter() - t:.3f} ref_n={ref.n} ref_m={ref.m}")
    correct = bool(done) and not failed and window_compiles == 0 and all(
        v <= lim for v, lim in checks.values()
    )
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    out = {"correct": correct, "attempted": len(done) + failed, "failed": failed}
    if args.trace:
        reduced = trace_mod.reduce_trace(trace_mod.read_trace(trace_dir))
        record = RunRecord(
            traffic=traffic, device_kind=dev.device_kind,
            build_s=build_s, compile_s=compile_s, n=ref.n, m=ref.m,
            sweeps=sum(d.sweeps for d in done), trace=reduced,
        )
        metrics = {}
        for m in spec["per_layer"]:
            if not _metric_applies(m, cell["name"]):
                continue
            value = load_part(root, "metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    else:
        e2e = {"teps": work / window_s, "setup_s": setup_s}
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in spec["end_to_end"]
            if _metric_applies(m, cell["name"])
        }
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {
        k: {"value": _finite(v), "limit": lim} for k, (v, lim) in checks.items()
    }
    return out


def _finite(x: float):
    return x if x == x and abs(x) != float("inf") else None


def main(argv=None, *, root: Path = ROOT, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    clock = CompileClock()
    out = run_cell(args, Path(root), t_start, clock)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
