"""Unified observability plane: metrics registry + DAG-run span tracing.

Zero-dependency substrate for the §4.4 monitoring loop and the serving
benchmarks' tail-latency reporting:

* :mod:`repro.obs.metrics` — named counters, gauges and log-bucketed
  histograms with streaming p50/p95/p99, collected in a
  :class:`MetricsRegistry` with one consistent snapshot/reset story.
  The engine/KVS/cache ad-hoc counters are all registry-backed (thin
  property shims keep the existing attribute APIs working).
* :mod:`repro.obs.trace` — per-DAG-run span tracing threaded through
  ``Cluster.step`` → ``Scheduler.schedule_ready`` → executor invoke →
  ``ExecutorCache.read_many`` → ``AnnaKVS`` plane launches, carrying
  each run's virtual clock; exports JSONL and Chrome ``trace_event``
  format (load in chrome://tracing / https://ui.perfetto.dev); and
  ``Tracer.phase``, the always-on wall-clock phases at the layer
  boundaries, counted in the registry and annotated (``cb.<name>``)
  into a ``jax.profiler`` trace.
* :mod:`repro.obs.host` — process-wide collector pauses and JAX
  compiles (``host.gc.*``, ``host.jit.*``).
"""

from .metrics import (
    CallbackGauge,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter_shim,
)
from .trace import NULL_TRACER, Span, Tracer

__all__ = [
    "CallbackGauge",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "Span",
    "Tracer",
    "counter_shim",
]
