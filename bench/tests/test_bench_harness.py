"""The harness end to end on the CPU at a tiny size, with the chip check skipped.

Every cell here is made of files in a temporary checkout, as a later change
would add them: a configuration, a traffic file, a generator and a metric
reader that the harness has never seen, found by the names in that
checkout's BENCHMARK.json.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import control

REPO = Path(__file__).resolve().parents[2]
TINY = {"generator": "graph500", "scale": 9, "edge_factor": 8,
        "initiator": [0.57, 0.19, 0.19], "undirected": True, "P": 4, "tier": "device"}
PARTS = ("generators", "tiers", "jobs", "metrics")
PAGERANK = json.loads((REPO / "bench" / "traffic" / "pagerank.json").read_text())


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the chip check and keep the compile cache off, as tests must."""
    from repro import compile_cache

    monkeypatch.setattr(harness, "require_chip", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(compile_cache, "enable", lambda: "off")


def make_checkout(tmp_path, config=None, metric_source=None, generator_source=None):
    """A checkout holding one new cell ``tiny.x`` and, optionally, a new metric
    and a new generator."""
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic"):
        (root / "bench" / sub).mkdir(parents=True)
    for sub in PARTS:
        shutil.copytree(REPO / "bench" / sub, root / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(dict(config or TINY, name="tiny")))
    (root / "bench/traffic/x.json").write_text(json.dumps(PAGERANK))
    if generator_source is not None:
        (root / "bench/generators/ring.py").write_text(generator_source)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": "tiny.x", "config": "tiny", "traffic": "x", "chips": 1,
                          "why": "test"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    if metric_source is not None:
        spec["per_layer"].append({"name": "jobs_seen", "unit": "jobs", "better": "higher",
                                  "source": "host_clock", "layer": "test", "moves": "teps"})
        (root / "bench/metrics/jobs_seen.py").write_text(metric_source)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root, capsys, trace=0, seed=2**31 + 7):
    argv = ["--workload", "tiny.x", "--seed", str(seed), "--seconds", "0.01",
            "--trace", str(trace)]
    assert harness.main(argv, root=root) == 0
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert err.strip().splitlines()[-1] == f"correct: {last['correct']}"
    return last, out


def test_new_files_are_found_and_the_line_meets_the_contract(on_cpu, tmp_path, capsys):
    root = make_checkout(
        tmp_path, metric_source="def read(run):\n    return float(run.sweeps)\n"
    )
    line, out = run(root, capsys)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"teps", "setup_s"}
    assert line["metrics"]["teps"]["unit"] == "edges/s"
    assert line["metrics"]["teps"]["value"] > 0
    assert line["device"]["count"] == len(jax.devices())
    assert "compiles=0" in [ln for ln in out.splitlines() if ln.startswith("window:")][0]
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]

    traced, _ = run(root, capsys, trace=1)
    assert traced["correct"] is True
    assert traced["metrics"]["jobs_seen"]["value"] == 10.0 * traced["attempted"]
    assert traced["metrics"]["build_s"]["value"] > 0
    assert not list((harness.BENCH_DIR / "work").glob("tiny.x-*"))  # the run's files are gone


RING = """
import numpy as np


def edges(seed, config):
    n = int(config["n"])
    i = np.arange(n, dtype=np.int32)
    src = np.concatenate([i, i[::3]])
    dst = np.concatenate([(i + 1) % n, (7 * i[::3] + seed % n) % n]).astype(np.int32)
    return src, dst, n
"""


def test_a_new_generator_is_a_new_file(on_cpu, tmp_path, capsys):
    config = {"generator": "ring", "n": 300, "P": 4, "tier": "device"}
    line, _ = run(make_checkout(tmp_path, config=config, generator_source=RING), capsys)
    assert line["correct"] is True
    assert line["metrics"]["teps"]["value"] > 0


@pytest.mark.parametrize("key", ["generator", "tier", "job"])
def test_a_name_with_no_file_is_refused(on_cpu, tmp_path, capsys, key):
    config = dict(TINY)
    if key != "job":
        config[key] = "nonesuch"
    root = make_checkout(tmp_path, config=config)
    if key == "job":
        (root / "bench/traffic/x.json").write_text(json.dumps(dict(PAGERANK, job="nonesuch")))
    with pytest.raises(SystemExit, match="nonesuch"):
        harness.main(["--workload", "tiny.x", "--seed", "1", "--seconds", "0.01"], root=root)
    assert '"correct"' not in capsys.readouterr().out


def _broken(monkeypatch, alter):
    real = harness.make_traffic

    def make_traffic(root, params, graph, seed):
        traffic = real(root, params, graph, seed)
        real_run = traffic.run

        def run(session, i):
            done = real_run(session, i)
            done.output = alter(done.output)
            return done

        traffic.run = run
        return traffic

    monkeypatch.setattr(harness, "make_traffic", make_traffic)


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(on_cpu, tmp_path, capsys, monkeypatch, fault):
    if fault == "state_unchanged":
        _broken(monkeypatch, lambda r: np.full_like(r, 1.0 / r.size))
    else:
        def alter(r):
            r = r.copy()
            r[np.argmax(r)] *= 1.001
            return r
        _broken(monkeypatch, alter)
    line, _ = run(make_checkout(tmp_path), capsys)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_the_bf16_control_fails_the_limits(on_cpu, tmp_path, capsys, monkeypatch, seed):
    """The control in the timed path, judged by the harness, at scale 12."""
    control.install(monkeypatch.setattr)
    line, _ = run(make_checkout(tmp_path, config=dict(TINY, scale=12)), capsys, seed=seed)
    assert line["correct"] is False and line["failed"] == 0
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _subprocess_env():
    env = {k: v for k, v in os.environ.items() if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.parametrize("layout", ["checkout", "bench_only"])
def test_no_result_line_without_a_chip_or_the_program(tmp_path, layout):
    root = REPO
    if layout == "bench_only":
        root = tmp_path / "bare"
        shutil.copytree(REPO / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__", "work"))
        shutil.copy(REPO / "BENCHMARK.json", root)
    cell = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=_subprocess_env(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
