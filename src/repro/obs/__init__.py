"""repro.obs — zero-dependency observability: metrics, traces, scraping.

The cross-cutting layer that makes the engine's exact byte accounting
*visible* while it happens:

* :data:`REGISTRY` — process-wide metrics registry (counters / gauges /
  fixed-bucket histograms with labels). ``GraphSession`` runs, the block
  fetcher, the packed chunk streamer, storage self-healing reads,
  checkpoint publishes and the serving server/pool/breaker all publish
  into it at the same lines that charge ``Meters`` — registry deltas
  across a run recombine field-for-field with ``Result.meters``.
  Rendered as Prometheus text exposition by :meth:`MetricsRegistry.
  render`; disable everything with ``REPRO_OBS=0``.
* :data:`TRACER` — bounded ring recorder of structured spans (staging,
  each sweep with its physical byte deltas, checkpoint writes, serving
  batch cuts), exportable as Chrome/Perfetto ``trace_event`` JSON, and
  mirrored as ``repro.<name>`` annotations on a JAX profile's host plane
  while one is taken. Off by default; enable process-wide via
  :func:`enable_tracing` or per run via the :class:`TraceSpec` plan knob.
* :class:`TelemetryServer` — stdlib HTTP endpoint serving ``/metrics``
  and ``/healthz`` (attached to ``GraphServer`` via
  ``telemetry_port=...``).
* ``python -m repro.obs export-trace spans.jsonl -o trace.json`` —
  offline converter from raw span dumps to Perfetto-loadable JSON.
"""
from repro.obs.http import TelemetryServer
from repro.obs.registry import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    HistogramValue,
    MetricsRegistry,
    REGISTRY,
    parse_prometheus,
)
from repro.obs.trace import (
    NO_SPAN,
    Span,
    TraceSpec,
    Tracer,
    TRACER,
    disable_tracing,
    enable_tracing,
)

__all__ = [
    "DEFAULT_LATENCY_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramValue",
    "MetricsRegistry",
    "NO_SPAN",
    "REGISTRY",
    "Span",
    "TelemetryServer",
    "TraceSpec",
    "Tracer",
    "TRACER",
    "disable_tracing",
    "enable_tracing",
    "parse_prometheus",
]
