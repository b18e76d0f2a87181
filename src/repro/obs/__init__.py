"""repro.obs — unified observability for the serving + BSP path.

One layer replaces the ad-hoc deques/dicts/``perf_counter`` deltas
that grew across ``solver.py``, ``serve.py`` and ``autotune.py``
(DESIGN.md §13):

* :mod:`repro.obs.metrics` — thread-safe ``Registry`` of ``Counter``
  / ``Gauge`` / log2-bucket ``Histogram`` families with labels and an
  injectable clock.
* :mod:`repro.obs.trace` — ``Span`` context managers into a bounded
  ``TraceLog`` ring (optional JSONL sink), thread-local parentage.
* :mod:`repro.obs.export` — JSON snapshot, Prometheus text rendering,
  and ``MetricsServer`` (the ``serve.py --metrics-port`` endpoint).

Deliberately dependency-free (stdlib only) and importable without
jax, like ``repro.analysis.lint``.
"""
from .export import MetricsServer, render_prometheus, snapshot
from .metrics import (Counter, Family, Gauge, Histogram, Registry,
                      default_registry)
from .trace import NullTraceLog, Span, TraceLog, default_tracelog

__all__ = [
    "Counter", "Family", "Gauge", "Histogram", "Registry",
    "default_registry",
    "Span", "TraceLog", "NullTraceLog", "default_tracelog",
    "MetricsServer", "render_prometheus", "snapshot",
]
