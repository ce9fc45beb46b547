"""Shared utilities: observability (metrics, traces, profiling helpers)."""

from adapcc_tpu.utils.observability import (
    AverageMeter,
    CollectiveTrace,
    MetricsRegistry,
    ProgressMeter,
    default_registry,
    parse_track_log,
    profiler_trace,
)

__all__ = [
    "AverageMeter",
    "CollectiveTrace",
    "MetricsRegistry",
    "ProgressMeter",
    "default_registry",
    "parse_track_log",
    "profiler_trace",
]
