"""End-to-end serving observability: span tracing and a metrics
registry.

Two planes, one clock:

  * :mod:`~repro.serving.observability.tracing` — per-request nested
    spans (``decide`` → ``tune`` → ``dispatch`` → ``retire`` →
    ``refine``, the phases inside them, and the engine's pool drains)
    stamped from the owning scheduler's injected clock, so the
    virtual-clock trace harness and the real concurrent engine share one
    instrumentation path; exported as JSONL or Chrome trace-event JSON
    (Perfetto-loadable), and mirrored into any ``jax.profiler`` capture.
  * :mod:`~repro.serving.observability.metrics` — process-wide named
    counters / gauges / histograms with deterministic ``snapshot()``
    and a Prometheus text exporter.

Everything defaults off: the schedulers ship with :data:`NULL_TRACER` /
:data:`NULL_METRICS`, whose hot-path operations are shared no-op
singletons.
"""
from repro.serving.observability.metrics import (DEFAULT_BUCKETS,
                                                 Counter, Gauge,
                                                 Histogram,
                                                 MetricsRegistry,
                                                 NULL_METRICS,
                                                 NullMetrics)
from repro.serving.observability.tracing import (NULL_TRACER, STAGES,
                                                 NullTracer, SpanRecord,
                                                 Tracer, stage_of)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "SpanRecord", "STAGES",
    "stage_of",
    "MetricsRegistry", "NullMetrics", "NULL_METRICS",
    "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
]
