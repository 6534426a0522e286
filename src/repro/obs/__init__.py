"""Unified telemetry plane (docs/OBSERVABILITY.md).

- ``registry`` — typed counters/gauges/histograms with a bounded
  streaming quantile sketch; the stats dataclasses are views over it.
- ``trace`` — per-frame span tracing on the injected clock,
  deterministic sampling, zero-cost when off; and ``Phases``, the
  always-on per-tick phase spans and counters that also reach the
  profiler's trace.
- ``recorder`` — bounded flight recorder for anomalies + recent spans,
  auto-dumped on cluster failover.
- ``export`` — Prometheus text format and JSONL snapshots.
"""
from .export import (registry_snapshot, to_prometheus,
                     validate_prometheus, write_jsonl)
from .recorder import EVENT_KINDS, FlightRecorder
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       QuantileSketch)
from .trace import SLOW_PHASE_S, FrameTrace, Phases, Tracer, sampled

__all__ = [
    "Counter", "EVENT_KINDS", "FlightRecorder", "FrameTrace", "Gauge",
    "Histogram", "MetricsRegistry", "Phases", "QuantileSketch",
    "SLOW_PHASE_S", "Tracer",
    "registry_snapshot", "sampled", "to_prometheus",
    "validate_prometheus", "write_jsonl",
]
