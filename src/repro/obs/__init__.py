"""Structured observability: tracing spans + perf-model drift.

The serving stack has good *aggregate* metrics (ServiceMetrics
percentiles, Prometheus counters) but aggregates can't answer "where
did THIS request's 900 ms go?". This package adds:

* :mod:`~repro.obs.trace` — a lock-guarded :class:`Tracer` producing
  nested :class:`Span` records with thread-local context propagation,
  explicit carriers across thread/process boundaries, and
  Chrome-trace/Perfetto JSON export.
* :mod:`~repro.obs.drift` — :class:`DriftAccumulator`, aggregating
  measured-vs-model-estimated lane times into the per-pipeline-kind
  drift report that device-spec recalibration (ROADMAP item 1) needs.
* :mod:`~repro.obs.profile` — the pipeline utilization profiler:
  analytic per-lane byte/FLOP footprints (:class:`LaneFootprint`)
  combined with measured lane times into achieved GB/s, arithmetic
  intensity and %-of-peak (:class:`UtilizationAccumulator`).
* :mod:`~repro.obs.jitcount` — per-thread and process-wide counts of
  jaxpr traces, backend compiles and persistent-cache hits/misses
  from one ``jax.monitoring`` listener, installed on import.

See docs/OBSERVABILITY.md for the span taxonomy and usage.
"""
from . import jitcount
from .drift import DriftAccumulator
from .jitcount import JitCounts
from .profile import (LaneFootprint, UtilizationAccumulator,
                      jaxpr_lane_bytes, lane_footprint, lane_footprints)
from .trace import (Span, SpanContext, Tracer, current, current_ctx,
                    current_tracer, span)

jitcount.install()

__all__ = [
    "DriftAccumulator", "JitCounts", "LaneFootprint", "Span",
    "SpanContext", "Tracer", "UtilizationAccumulator", "current",
    "current_ctx", "current_tracer", "jaxpr_lane_bytes", "jitcount",
    "lane_footprint", "lane_footprints", "span",
]
