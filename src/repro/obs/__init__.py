"""Unified telemetry plane: metrics registry, request-lifecycle tracer,
Chrome-trace/Perfetto export.

See ARCHITECTURE.md "Telemetry plane" for the span taxonomy, the
registry merge semantics, and the disabled-mode guarantees.
"""
from .metrics import (
    Histogram, MetricsRegistry, absorb_engine_stats, absorb_gossip_stats,
    absorb_online_stats, absorb_residual_stats, absorb_span_stats,
)
from .trace import NULL, NullTracer, Tracer
from .export import (
    reconstruct_request, text_timeline, to_chrome_trace,
    validate_chrome_trace, write_chrome_trace,
)

__all__ = [
    "Histogram",
    "MetricsRegistry",
    "NULL",
    "NullTracer",
    "Tracer",
    "absorb_engine_stats",
    "absorb_gossip_stats",
    "absorb_online_stats",
    "absorb_residual_stats",
    "absorb_span_stats",
    "reconstruct_request",
    "text_timeline",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
]
