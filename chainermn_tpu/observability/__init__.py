"""Unified runtime observability (ISSUE 14).

Two host-side pieces every subsystem shares:

* :mod:`~chainermn_tpu.observability.tracing` — structured span
  tracing: onto the ``jax.profiler`` trace whenever one is recording
  (the device's clock and file), and into a bounded ring exported as
  Chrome-trace-event JSONL (Perfetto-loadable; rank shards merge via
  ``tools/trace_merge.py``) under ``CHAINERMN_TPU_TRACE=off|events``;
* :mod:`~chainermn_tpu.observability.metrics` — a mergeable registry
  of counters/gauges/fixed-bucket histograms, joined across ranks over
  the object collectives and rendered in Prometheus text format.

Span classification, knob ladder, and the merge workflow:
``docs/observability.md``.
"""

from .tracing import (MODES, TRACE_ENV, Span, SpanTracer, complete,
                      enabled, instant, mode, read_jsonl, repair_balance,
                      reset_tracer, ring_enabled, set_mode, span, tracer,
                      validate_events)
from .metrics import (DEFAULT_TIME_BUCKETS_MS, Counter, Gauge, Histogram,
                      MetricsRegistry, registry, reset_registry)

__all__ = [
    "Span", "SpanTracer", "tracer", "span", "instant", "complete", "mode",
    "enabled", "ring_enabled", "set_mode", "reset_tracer",
    "validate_events", "repair_balance",
    "read_jsonl", "TRACE_ENV", "MODES",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "reset_registry", "DEFAULT_TIME_BUCKETS_MS",
]
