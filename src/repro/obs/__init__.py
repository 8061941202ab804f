"""repro.obs — the BSP cost-model observatory.

Three pieces, all host-side (no jax imports — nothing here can perturb a
compiled program):

* :class:`MetricsRegistry` (``registry.py``) — process-wide labeled
  counters/gauges/histograms with one ``snapshot()``/``reset()``; the
  scattered telemetry of ``TierStats``, the service dispatcher, the
  capacity planner and the serve engine now lives here, with the old
  attributes kept as thin property views.
* :class:`Tracer` (``trace.py``) — spans recorded at the sort drivers'
  launch/wait boundaries, the service's pending→queue→form→launch→flight
  pipeline and its lock waits, exported as Chrome ``trace_event`` JSON
  and mappable onto the profiler's device trace (README.md). Off by
  default; enable per run via ``SortConfig(obs=tracer)`` /
  ``ServiceConfig(obs=tracer)``.
* the fitted machine profile (``profile.py``) — least-squares (g, L) over
  the traced h sizes and measured superstep walls, plus the per-run cost
  report (``w + g·h + L`` predicted vs measured) and the load-imbalance
  metric that tests the paper's balance claim.

``metrics()`` returns the process-wide default registry;
``next_instance("svc")`` hands out stable instance labels so several
services/planners in one process keep distinct metric keys.
"""
from __future__ import annotations

import itertools

from .profile import GLFit, cost_report, fit_gl, imbalance_of
from .registry import Counter, Gauge, Histogram, MetricsRegistry, metric_key
from .trace import (
    Tracer,
    resolve_tracer,
    validate_chrome_trace,
    validate_spans,
)

#: the process-wide default registry (one per process, like the default
#: SortExecutor) — owners cache metric handles from it at construction.
REGISTRY = MetricsRegistry()


def metrics() -> MetricsRegistry:
    return REGISTRY


_instance_ids = itertools.count()


def next_instance(prefix: str) -> str:
    """A process-unique instance label (``svc0``, ``planner1``, ...)."""
    return f"{prefix}{next(_instance_ids)}"


__all__ = [
    "Counter",
    "Gauge",
    "GLFit",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "Tracer",
    "cost_report",
    "fit_gl",
    "imbalance_of",
    "metric_key",
    "metrics",
    "next_instance",
    "resolve_tracer",
    "validate_chrome_trace",
    "validate_spans",
]
