"""Observability layer: device-resident convergence traces, span-tree
wall-clock tracing with profiler hooks, and serving metrics exposition.

Three cooperating pieces (DESIGN.md §13):

- ``obs.trace``   — ``ConvTrace``, a jit-safe ring buffer pytree that solver
  while-loops write per-iteration samples into; fetched once at fit exit.
- ``obs.spans``   — ``span(name)`` context manager building a wall-clock span
  tree (one per thread) over fit and serving-engine phases, mirrored into
  ``jax.profiler.TraceAnnotation`` so XLA/Perfetto profiles carry the same
  names; collector pauses show as ``host/gc``; exports Chrome trace JSON.
- ``obs.metrics`` — streaming log-bucket latency histograms + labeled
  counters with Prometheus-text and JSON exposition for the serving loop.
"""
from repro.obs.trace import (  # noqa: F401
    TRACE_COLS,
    ConvTrace,
    trace_init,
    trace_record,
    trace_fetch,
    trace_summary,
)
from repro.obs.spans import SpanTracer, span  # noqa: F401
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
)
