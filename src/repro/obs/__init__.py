"""Observability for the join pipeline: program spans on the profiler's
clock (obs/trace.py) and the metrics registry (obs/metrics.py).

``trace.span(name)`` opens a ``jax.profiler.TraceAnnotation`` (recorded
only under a profiler session); ``metrics.metrics()`` is the
process-global registry. See the submodule docstrings and
ARCHITECTURE.md §6 for the span names and transfer-class byte
accounting.

``metrics`` and ``trace`` are exported as submodules (the accessor
functions keep their short names inside each submodule), so consumers
import ``from repro.obs import metrics, trace`` and call
``metrics.metrics()`` / ``trace.span(...)``.
"""
from repro.obs import metrics, trace
from repro.obs.metrics import (LATENCY_BUCKETS, POW2_BUCKETS, Counter,
                               Gauge, Histogram, Metrics)

__all__ = [
    "metrics", "trace",
    "Counter", "Gauge", "Histogram", "Metrics",
    "POW2_BUCKETS", "LATENCY_BUCKETS",
]
