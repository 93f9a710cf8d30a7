"""Streaming observability for the fold-schedule serving stack
(DESIGN.md §11).

The paper's validation is a *profiling* story — per-layer PE utilization
(Fig 9), fold reuse (Table 3), end-to-end KIPS — and the serving runtime
adds a request lifecycle on top.  This package makes both continuously
observable:

* ``obs.metrics``  — a bounded metrics registry: counters, gauges, and
  fixed-memory log-bucketed latency histograms (HDR-style), with
  Prometheus text exposition and a JSON snapshot.
* ``obs.trace``    — structured request-lifecycle tracing: one span per
  stage (submit/admit/form/dispatch/kernel/epilogue/degrade/complete)
  plus per compiled-layer spans, recorded through an injectable clock
  with deterministic span IDs and exported as Chrome trace-event JSON
  (loadable in Perfetto); ``validate_trace`` checks its schema and
  ``check_trace_outcomes`` the zero-loss invariant.

Everything defaults to a no-op recorder (``trace.NULL_TRACER``) so the
instrumented hot paths cost one attribute check when observability is
off.
"""
from repro.obs.metrics import (Counter, Gauge, LogHistogram,
                               MetricsRegistry, validate_metrics_snapshot)
from repro.obs.trace import (NULL_TRACER, NullTracer, Tracer,
                             validate_trace)

__all__ = [
    "Counter", "Gauge", "LogHistogram", "MetricsRegistry",
    "validate_metrics_snapshot",
    "Tracer", "NullTracer", "NULL_TRACER", "validate_trace",
]
