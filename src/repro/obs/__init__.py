"""Unified observability layer: span tracing, metrics, JAX profiling hooks.

Bottom of the import ladder (everything may import ``repro.obs``; it imports
nothing else of ``repro``, only the stdlib and ``jax``), and off by default:

  * ``repro.obs.trace``   -- thread-safe span tracer exporting Chrome
    trace-event JSON (Perfetto-loadable) + a JSONL structured-event stream;
    each span is also a ``jax.profiler.TraceAnnotation``, so it lands on
    the host plane of any running JAX profiler capture;
  * ``repro.obs.metrics`` -- counters / gauges / windowed histograms
    registry; the single ``IoStats`` implementation every store shares;
  * ``repro.obs.jaxprof`` -- ``named_scope``, opt-in ``jax.profiler.trace``
    capture, and the recompile watcher that flags silent jit retraces.

Enable per run with ``obs.configure(trace_dir=...)`` (the launchers expose
this as ``--trace-dir``); summarize a run with ``tools/trace_report.py``.
"""
from repro.obs.trace import (NULL_SPAN, Tracer, configure, counter, enabled,
                             get_tracer, instant, shutdown, span)
from repro.obs.metrics import (Counter, Gauge, Histogram, IoStats,
                               MetricsRegistry, get_registry)
from repro.obs.jaxprof import (RecompileEvent, RecompileWatcher, get_watcher,
                               jit_cache_size, named_scope, profiler_trace)

__all__ = [
    "NULL_SPAN", "Tracer", "configure", "counter", "enabled", "get_tracer",
    "instant", "shutdown", "span",
    "Counter", "Gauge", "Histogram", "IoStats", "MetricsRegistry",
    "get_registry",
    "RecompileEvent", "RecompileWatcher", "get_watcher",
    "jit_cache_size", "named_scope", "profiler_trace",
]
