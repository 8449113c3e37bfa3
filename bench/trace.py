"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

The benchmark wraps every job, warm-up and check in a
``jax.profiler.TraceAnnotation`` named ``bench.<phase>``. The traced
window runs from the start of the first ``bench.job`` to the end of the
last. Device time is the union of the op intervals on each device's
``XLA Ops`` line, clipped to the window and averaged over the devices;
an idle gap is a stretch of the window in which no op runs, labelled by
the benchmark phase around it and the shortest host event that spans its
middle (what the host was doing meanwhile).

On a TPU an op event is named by its whole HLO instruction; it is
reported as ``<executable>: <instruction name>``, the executable taken from
the ``XLA Modules`` event that spans the op (its hash suffix dropped).
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from pathlib import Path

__all__ = ["Event", "TraceEvents", "read_trace", "reduce_trace", "ANNOTATION_PREFIX"]

ANNOTATION_PREFIX = "bench."
JOB_ANNOTATION = ANNOTATION_PREFIX + "job"
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class TraceEvents:
    device_ops: dict[str, list[Event]]  # device plane name -> op events
    host: list[Event]  # every host event, benchmark annotations included


def read_trace(log_dir: str | Path) -> TraceEvents:
    """Load the one ``.xplane.pb`` that ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {len(files)}")
    data = ProfileData.from_file(str(files[0]))
    device_ops: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.end_ns, e.name.split("(")[0])
                for e in lines.get(MODULES_LINE, [])
            )
            starts = [m[0] for m in modules]
            evs = []
            for e in lines.get(OPS_LINE, []):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                module = modules[k][2] if k >= 0 and modules[k][1] >= e.end_ns else "?"
                evs.append(Event(f"{module}: {e.name.split(' = ')[0]}", e.start_ns, e.end_ns))
            if evs:
                device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend(
                Event(e.name, e.start_ns, e.end_ns)
                for line in plane.lines
                for e in line.events
            )
    return TraceEvents(device_ops, host)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _label(host: list[Event], lo: float, hi: float) -> str:
    mid = (lo + hi) / 2
    around = [e for e in host if e.start_ns <= mid <= e.end_ns]
    phase = [e for e in around if e.name.startswith(ANNOTATION_PREFIX)]
    inner = [e for e in around if not e.name.startswith(ANNOTATION_PREFIX)]
    parts = [min(phase, key=lambda e: e.end_ns - e.start_ns).name] if phase else []
    if inner:
        parts.append(min(inner, key=lambda e: e.end_ns - e.start_ns).name)
    return " / ".join(parts) or "no host event"


def reduce_trace(ev: TraceEvents) -> dict | None:
    """Busy and window seconds, top device ops and longest idle gaps.

    Returns None when the trace holds no job annotation or no device op.
    """
    jobs = [e for e in ev.host if e.name == JOB_ANNOTATION]
    if not jobs or not ev.device_ops:
        return None
    w0 = min(e.start_ns for e in jobs)
    w1 = max(e.end_ns for e in jobs)
    busy_ns = []
    op_ns: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for plane, ops in sorted(ev.device_ops.items()):
        clipped = [
            (max(e.start_ns, w0), min(e.end_ns, w1), e.name)
            for e in ops
            if e.end_ns > w0 and e.start_ns < w1
        ]
        for lo, hi, name in clipped:
            op_ns[name] += hi - lo
        merged = _union([(lo, hi) for lo, hi, _ in clipped])
        busy_ns.append(sum(hi - lo for lo, hi in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend(
            (lo, hi) for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo
        )
    gaps.sort(key=lambda g: g[0] - g[1])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "devices": len(busy_ns),
        "device_ops": [[name, ns / 1e9] for name, ns in top_ops],
        "idle_gaps": [
            [_label(ev.host, lo, hi), (hi - lo) / 1e9] for lo, hi in gaps[:TOP]
        ],
    }
