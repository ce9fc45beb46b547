"""What the program recorded about itself in the traced window.

``adapcc_tpu`` keeps one process-wide ``MetricsRegistry``; its spans are on
exactly while a profiler session is live, so after a ``--trace 1`` window
its span timings and samples are the window's steps and only those (the
runner deletes the trace itself before any reader runs; the registry is read
in process).  A program from before the registry existed gives ``None``
everywhere, and the result line leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def snapshot() -> Optional[Dict[str, Any]]:
    try:
        from adapcc_tpu.utils.observability import default_registry
    except ImportError:
        return None
    return default_registry().snapshot()


def _entry(kind: str, name: str) -> Optional[Any]:
    snap = snapshot()
    return None if snap is None else snap.get(kind, {}).get(name)


def span_mean_ms(name: str) -> Optional[float]:
    """Mean duration of the span ``name`` in ms, over its own count."""
    t = _entry("timings", name)
    return 1e3 * t["mean_s"] if t else None


def sample_mean(name: str) -> Optional[float]:
    s = _entry("samples", name)
    return s["mean"] if s else None


def gauge(name: str) -> Optional[float]:
    return _entry("gauges", name)
