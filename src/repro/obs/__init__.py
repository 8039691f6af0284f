"""Zero-dependency fleet telemetry: metrics, span tracing, recompile
watchdog, exporters.

Everything here is stdlib-only except :func:`serving_watchdog`, which
lazily imports the jitted serving executables it guards, and the spans,
which lazily import ``jax.profiler.TraceAnnotation`` so that they also
land in a running profiler's trace (:func:`span` is that annotation
alone, for code that takes no tracer).  The serving stack takes
``metrics=``/``tracer=``/``watchdog=`` keyword arguments and defaults to
the no-op implementations, so telemetry is strictly opt-in and costs one
attribute lookup per instrumented site when off.
"""
from .export import MetricsServer, start_metrics_server
from .metrics import (
    Counter,
    DEFAULT_LATENCY_BUCKETS,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL,
    NullRegistry,
    get_default,
    set_default,
)
from .trace import NULL_TRACER, NullTracer, SPAN_SCHEMA_KEYS, Tracer, span
from .watchdog import RecompileError, RecompileWatchdog, serving_watchdog

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "NULL",
    "get_default",
    "set_default",
    "DEFAULT_LATENCY_BUCKETS",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "SPAN_SCHEMA_KEYS",
    "span",
    "RecompileError",
    "RecompileWatchdog",
    "serving_watchdog",
    "MetricsServer",
    "start_metrics_server",
]
