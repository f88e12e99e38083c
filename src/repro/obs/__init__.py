"""Unified observability layer (ROADMAP "Observability").

Layout:
  telemetry.py       process-global counters/gauges/histograms + nested
                     host-side spans, each also a profiler TraceAnnotation
                     (disabled by default, zero-cost off)
  sink.py            JSONL event sink + RunManifest (run identity stamped
                     into bench rows, checkpoint meta, serve stats)
  serve_metrics.py   per-request lifecycle metrics: queue wait, TTFT,
                     per-bucket prefill histograms, occupancy/backlog
  compile_events.py  the one jax.monitoring backend-compile subscription,
                     attributing each XLA compile to the enclosing span
  profiler.py        --profile wiring for jax.profiler.trace

Everything here is host-side by contract: instrumented jitted callers never
trace through this package (quantlint QL103/QL106 + the tier-1 no_retrace
assertion enforce it).
"""
from repro.obs.sink import (  # noqa: F401
    SCHEMA_VERSION,
    JsonlSink,
    ListSink,
    RunManifest,
    current_manifest,
)
from repro.obs.telemetry import (  # noqa: F401
    TELEMETRY,
    Counter,
    Gauge,
    Histogram,
    Stopwatch,
    counter,
    gauge,
    histogram,
    span,
)
from repro.obs.serve_metrics import ServeMetrics  # noqa: F401
