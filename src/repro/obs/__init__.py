"""repro.obs — unified tracing, metrics & profiling across train/serve/defense.

One :class:`Recorder` is threaded through every instrumented path (the
four Topology plugins, ServeEngine, RobustDecoder, the launch CLIs); it
fans records out to the legacy JSONL format, mirrors scalars into a
Prometheus-exportable metrics registry, and keeps spans of the host's work
on the profiler's clock without ever blocking on the device.  See
DESIGN.md §12 for the architecture.
"""
from repro.obs.metrics import (
    DEFAULT_MS_BUCKETS,
    DISABLED,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ObsConfig,
    Recorder,
    as_recorder,
    make_recorder,
)
from repro.obs.schema import ENVELOPE, SCHEMA, check_kind, validate_record
from repro.obs.trace import NULL_SPAN, Span, set_default_recorder, span
from repro.obs.export import parse_exposition, render_prometheus, \
    write_snapshot
from repro.obs.profile import compiled_cost, device_memory_stats, \
    hlo_scopes, profile_trace

__all__ = [
    "DEFAULT_MS_BUCKETS", "DISABLED", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "ObsConfig", "Recorder", "as_recorder",
    "make_recorder", "ENVELOPE", "SCHEMA", "check_kind", "validate_record",
    "NULL_SPAN", "Span", "set_default_recorder", "span",
    "parse_exposition", "render_prometheus", "write_snapshot",
    "compiled_cost", "device_memory_stats", "hlo_scopes", "profile_trace",
]
