"""Observability: profiler spans + one metrics registry.

- :class:`~repro.obs.tracing.Tracer` — ``repro:`` spans written as
  ``jax.profiler.TraceAnnotation``s, so a profiler capture holds them
  on the same clock as the runtime's host events and the device's
  operations; the spans ``tracing.COUNTED`` names also feed registry
  counters.
- :class:`~repro.obs.metrics.MetricsRegistry` — counters / gauges /
  histograms behind ``engine.metrics_snapshot()`` and a Prometheus text
  exporter.
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import Tracer

__all__ = ["MetricsRegistry", "Tracer"]
