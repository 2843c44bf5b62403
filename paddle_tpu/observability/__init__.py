"""Observability subsystem: metrics registry, span tracing, exposition.

The production-telemetry counterpart of profiler.py's trace tooling:
answering "what is this trainer/server doing right now" with scrapeable
counters/gauges/histograms instead of a trace viewer.

Layers:

- :mod:`metrics` — process-wide, thread-safe registry of ``Counter`` /
  ``Gauge`` / ``Histogram`` (label support, bounded buckets).
- :mod:`tracing` — ``span("executor.run")`` context managers feeding the
  registry, recording into the timeline ring (with the id of the span
  around them) *and* annotating XLA traces
  (jax.profiler.TraceAnnotation).
- :mod:`timeline` — the step-timeline flight recorder: ONE bounded ring
  of spans and, when armed, per-step phase events
  (feed/compile/dispatch/update/prefetch), exported as Chrome
  ``trace_event`` JSON (``PADDLE_TPU_TRACE_DIR``, Perfetto-loadable)
  with last-N-steps crash dumps (``PADDLE_TPU_TRACE_DUMP_ON_ERROR``).
  profiler.py's RecordEvent records into the same ring.
- :mod:`exporters` — Prometheus text exposition + JSON snapshot.
- :mod:`http` — opt-in stdlib ``/metrics`` + ``/healthz`` endpoint
  (``serve_metrics(port)``, gated by ``PADDLE_TPU_METRICS_PORT``).

Instrumented layers: core/executor.py (plan-cache hits/misses, compile
wall time, run/run_steps latency, feed + donated-state bytes),
inference/batching.py (queue depth, occupancy, request latency),
inference/serving.py, inference/decode.py (tick, request, admit, prefill
and step spans), reader decorators (samples, buffer depth).

Everything is zero-cost when disabled (``PADDLE_TPU_METRICS_ENABLED=0``):
instrument sites guard on :func:`enabled` and spans collapse to a shared
no-op.  Instrumentation is host-side only — nothing here runs under a
jit trace.
"""
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_COMPILE_BUCKETS, DEFAULT_LATENCY_BUCKETS,
                      enabled, registry, reload_enabled, set_enabled)
from .tracing import record_span, span
from .exporters import json_snapshot, prometheus_text
from .http import (MetricsHTTPServer, healthz_report,
                   maybe_serve_from_env, register_healthz,
                   serve_metrics, unregister_healthz)
from . import timeline

__all__ = [
    'Counter', 'Gauge', 'Histogram', 'MetricsRegistry',
    'DEFAULT_COMPILE_BUCKETS', 'DEFAULT_LATENCY_BUCKETS',
    'enabled', 'set_enabled', 'reload_enabled', 'registry', 'span',
    'record_span',
    'prometheus_text', 'json_snapshot', 'snapshot',
    'MetricsHTTPServer', 'serve_metrics', 'maybe_serve_from_env',
    'register_healthz', 'unregister_healthz', 'healthz_report',
    'counter', 'gauge', 'histogram', 'timeline',
]


def counter(name, help='', labelnames=()):
    """Get-or-create a Counter in the global registry."""
    return registry().counter(name, help, labelnames)


def gauge(name, help='', labelnames=()):
    """Get-or-create a Gauge in the global registry."""
    return registry().gauge(name, help, labelnames)


def histogram(name, help='', labelnames=(),
              buckets=DEFAULT_LATENCY_BUCKETS):
    """Get-or-create a Histogram in the global registry."""
    return registry().histogram(name, help, labelnames, buckets=buckets)


def snapshot():
    """JSON-serializable snapshot of the global registry (the dict the
    JSON exporter serializes; BENCH runs embed it verbatim)."""
    return registry().snapshot()
