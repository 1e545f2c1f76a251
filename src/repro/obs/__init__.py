"""Observability: tracing spans, a metrics registry, a per-step phase log.

The package has two halves:

* :mod:`repro.obs.tracer` — a ``Tracer`` that records context-manager
  spans, instant events, and counter samples into a bounded ring buffer
  and exports Chrome trace-event JSON (loadable in Perfetto or
  ``chrome://tracing``).  A process-global tracer is installed with
  :func:`install_tracer`; the default is ``NullTracer``, which records
  nothing itself.  Spans of either tracer also enter a
  ``jax.profiler.TraceAnnotation``, so they land on the profiler's host
  plane beside the device's events whenever ``jax.profiler`` traces.
* :mod:`repro.obs.metrics` — a ``Metrics`` registry of counters, gauges,
  and fixed log-bucket ``Histogram`` objects with p50/p90/p99 summaries,
  and ``Metrics.steps``, a bounded ``StepLog`` of per-step phase records.
  ``Metrics.stats_view()`` exposes the counter table as a plain mutable
  mapping so existing ``stats`` dicts can migrate onto it unchanged.

``metrics`` is stdlib-only; ``tracer`` imports ``jax.profiler``.  See
``docs/observability.md`` for the span/track taxonomy, the step log and
the metric glossary.
"""

from repro.obs.metrics import Histogram, Metrics, StepLog, StepRecord
from repro.obs.tracer import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    get_tracer,
    install_tracer,
)

__all__ = [
    "Histogram",
    "Metrics",
    "NULL_TRACER",
    "NullTracer",
    "StepLog",
    "StepRecord",
    "Tracer",
    "get_tracer",
    "install_tracer",
]
