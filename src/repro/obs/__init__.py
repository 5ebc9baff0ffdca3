"""Unified telemetry: tracing, metric registry, profiling hooks.

See DESIGN.md §13 for the span taxonomy and metric naming conventions.
"""
from repro.obs.profile import (annotate, clock_anchor, profile_trace,
                               stage_map, trace_clock)
from repro.obs.registry import DEFAULT_BUCKETS, Metric, Registry
from repro.obs.trace import (NULL_TRACER, NullTracer, Span, Tracer,
                             attribution, format_trace)

__all__ = [
    "annotate", "clock_anchor", "profile_trace", "stage_map", "trace_clock",
    "DEFAULT_BUCKETS", "Metric", "Registry",
    "NULL_TRACER", "NullTracer", "Span", "Tracer",
    "attribution", "format_trace",
]
