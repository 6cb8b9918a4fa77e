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

And one piece that lands in the compiled programs:

* :mod:`~chainermn_tpu.observability.scopes` — the ROLES, a fixed
  vocabulary of ``jax.named_scope`` names (``~attn``, ``~mlp``, ...)
  that the models and ``ops/`` entry points open, so that the profiler's
  device operations carry the part of the model they belong to.

Span classification, knob ladder, the roles and the merge workflow:
``docs/observability.md``.
"""

from .tracing import (MODES, TRACE_ENV, Span, SpanTracer, complete,
                      enabled, instant, mode, read_jsonl, repair_balance,
                      reset_tracer, ring_enabled, set_mode, span, tracer,
                      validate_events)
from .metrics import (DEFAULT_TIME_BUCKETS_MS, Counter, Gauge, Histogram,
                      MetricsRegistry, registry, reset_registry)
from .scopes import ROLE_MARK, ROLES, role

__all__ = [
    "Span", "SpanTracer", "tracer", "span", "instant", "complete", "mode",
    "enabled", "ring_enabled", "set_mode", "reset_tracer",
    "validate_events", "repair_balance",
    "read_jsonl", "TRACE_ENV", "MODES",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "reset_registry", "DEFAULT_TIME_BUCKETS_MS",
    "ROLES", "ROLE_MARK", "role",
]
