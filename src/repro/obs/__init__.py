"""repro.obs — serving observability: structured tracing with Perfetto
export (trace), a typed metrics registry with Prometheus exposition
(registry), and a jax.profiler hook (profile)."""
from .profile import profile_trace
from .registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .trace import (
    NULL_TRACER,
    TRACE_SCHEMA_VERSION,
    NullTracer,
    TraceRecord,
    Tracer,
    get_tracer,
    records_to_perfetto,
    self_times,
    set_tracer,
)

__all__ = [
    "Counter",
    "DEFAULT_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "TRACE_SCHEMA_VERSION",
    "TraceRecord",
    "Tracer",
    "get_tracer",
    "profile_trace",
    "records_to_perfetto",
    "self_times",
    "set_tracer",
]
