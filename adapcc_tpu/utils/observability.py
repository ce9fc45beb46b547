"""Observability: structured metrics, collective traces, profiler hooks.

The reference's observability is printf-based throughout (SURVEY.md §5.5):
relay decisions and per-element progress printed from the native layer
(control.cu:79-81, allreduce.cu:541-542), chunk-arrival debug dumps in
log/track.txt, AverageMeter/ProgressMeter training meters
(accuracy_benchmark.py:470-539), and ad-hoc log-scraping post-processors
(process_log.py, process_gns.py).  This module provides the structured
versions: the same meters, a metrics registry with JSON export (one
process-wide default, :func:`default_registry`) whose spans lie on the JAX
profiler's clock and are on exactly while a profile is being taken, a
collective trace that records engine dispatches (the track.txt analog), a
``jax.profiler`` context for Perfetto traces, and a parser for the trace's
dump.  docs/OBSERVABILITY.md lists every span, counter, gauge and sample.
"""

from __future__ import annotations

import contextlib
import json
import random
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence

from jax.profiler import TraceAnnotation

#: every program span's name in a profile starts with this
SPAN_PREFIX = "adapcc."


# --- training meters (accuracy_benchmark.py:470-539) --------------------------


class AverageMeter:
    """Tracks current value, running average, sum, count."""

    def __init__(self, name: str, fmt: str = ":f") -> None:
        self.name = name
        self.fmt = fmt
        self.reset()

    def reset(self) -> None:
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1) -> None:
        self.val = float(val)
        self.sum += float(val) * n
        self.count += n
        self.avg = self.sum / max(self.count, 1)

    def __str__(self) -> str:
        fmtstr = "{name} {val" + self.fmt + "} ({avg" + self.fmt + "})"
        return fmtstr.format(name=self.name, val=self.val, avg=self.avg)


class ProgressMeter:
    """``[ 10/500] loss 0.61 (0.73)  acc 81.2 (76.9)``-style progress lines."""

    def __init__(self, num_batches: int, meters: Sequence[AverageMeter], prefix: str = "") -> None:
        num_digits = len(str(num_batches // 1))
        self._batch_fmt = "[" + "{:" + str(num_digits) + "d}" + "/" + str(num_batches) + "]"
        self.meters = list(meters)
        self.prefix = prefix

    def display(self, batch: int) -> str:
        entries = [self.prefix + self._batch_fmt.format(batch)]
        entries += [str(m) for m in self.meters]
        line = "\t".join(entries)
        print(line)
        return line


# --- metrics registry ---------------------------------------------------------


def nearest_rank_percentile(sorted_samples: Sequence[float], q: float) -> float:
    """THE nearest-rank percentile convention, repo-wide: every consumer
    (the metrics reservoir, the dispatch-trace summary, the tuner
    database, the serving ledger, the queueing model) quotes percentiles
    through this one spelling, so a p99 from any artifact is comparable
    with a p99 from any other.  ``sorted_samples`` must be sorted
    ascending and non-empty."""
    rank = max(0, int(-(-q * len(sorted_samples) // 1)) - 1)
    return sorted_samples[min(rank, len(sorted_samples) - 1)]


class _Series:
    """Running count/total/max of one named series, exactly, plus the
    bounded reservoir its percentiles are read from."""

    __slots__ = ("count", "total", "max", "reservoir", "in_session")

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.max = float("-inf")
        self.reservoir: List[float] = []
        #: recorded under a profiler session, and dropped when the next opens
        self.in_session = False

    def add(self, v: float, size: int, rng: random.Random) -> None:
        self.count += 1
        self.total += v
        self.max = max(self.max, v)
        if len(self.reservoir) < size:
            self.reservoir.append(v)
        else:
            j = rng.randrange(self.count)
            if j < size:
                self.reservoir[j] = v

    def summary(self, suffix: str = "") -> Dict[str, Any]:
        res = sorted(self.reservoir)
        out = {
            "count": self.count,
            "mean" + suffix: self.total / self.count,
            "max" + suffix: self.max,
            "p50" + suffix: nearest_rank_percentile(res, 0.50),
            "p99" + suffix: nearest_rank_percentile(res, 0.99),
        }
        if suffix:
            out["total" + suffix] = self.total
        return out


class MetricsRegistry:
    """Named counters/gauges/timings/samples with JSON export; thread-safe.

    Timings (seconds) and samples (unitless: queue depths, byte sizes) keep
    running count/total/max exactly, plus a **bounded reservoir** (Vitter's
    algorithm R, deterministic seed) so :meth:`snapshot` can report p50/p99
    with O(1) memory per series — a long-running trainer recording per-step
    timings must not grow a list without bound, and tail latency (the p99 a
    straggler policy keys on) is invisible to count/mean/max alone.

    :meth:`span` is the tracer: on exactly while a JAX profiler session is
    live, so an operator turns the spans on by taking a profile and by
    nothing else (docs/OBSERVABILITY.md).
    """

    #: samples retained per series for the percentile estimate; above this
    #: count, reservoir sampling keeps a uniform subset
    RESERVOIR_SIZE = 512
    #: spans kept whole (start, end, meta) per name, the newest last: the
    #: compile path's, a few a program, not a step's
    SPANS_KEPT = 256

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = defaultdict(float)
        self._gauges: Dict[str, float] = {}
        self._timings: Dict[str, _Series] = {}
        self._samples: Dict[str, _Series] = {}
        self._spans: Dict[str, "deque[Dict[str, Any]]"] = {}
        # deterministic reservoir replacement: two identical runs snapshot
        # identical percentiles (the sim-bench byte-stability policy)
        self._rng = random.Random(0x5EED)
        # a profiler session is told from the next by an off check in
        # between: ``_closed`` is the generation an off check last saw (read
        # BEFORE the check, so a thread caught between its check and its
        # store cannot close a session that opened meanwhile)
        self._gen = 0
        self._closed = 0

    def incr(self, name: str, by: float = 1.0) -> None:
        with self._lock:
            self._counters[name] += by

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    @contextlib.contextmanager
    def timer(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, time.perf_counter() - t0)

    def _add(
        self, table: Dict[str, _Series], name: str, value: float, in_session: bool,
        span: Optional[Dict[str, Any]] = None,
    ) -> None:
        with self._lock:
            series = table.get(name)
            if series is None:
                series = table[name] = _Series()
            series.in_session |= in_session
            series.add(float(value), self.RESERVOIR_SIZE, self._rng)
            if span is not None:
                kept = self._spans.get(name)
                if kept is None:
                    kept = self._spans[name] = deque(maxlen=self.SPANS_KEPT)
                kept.append(span)

    def observe(
        self, name: str, seconds: float, end: Optional[float] = None, **meta: Any
    ) -> None:
        """Record an externally measured duration into the ``name`` timing;
        it belongs to no profiler session and outlives them all.  Given its
        ``end`` on ``time.perf_counter``, it is also kept whole, as a span
        (``start_s``, ``end_s`` and ``meta``) under ``snapshot()["spans"]``:
        the last :attr:`SPANS_KEPT` of each name."""
        span = None if end is None else dict(meta, start_s=end - seconds, end_s=end)
        self._add(self._timings, name, seconds, False, span)

    def sample(self, name: str, value: float) -> None:
        """Record one value of the unitless distribution ``name`` (a queue
        depth, a byte size).  Taken under a profiler session it belongs to
        that session, like a span."""
        self._add(self._samples, name, value, self._live())

    def _live(self) -> bool:
        """Whether a profiler session is live; its first yes after a no
        opens the session (drops what the one before it recorded)."""
        gen = self._gen
        if not TraceAnnotation.is_enabled():
            self._closed = gen
            return False
        if self._closed == self._gen:
            with self._lock:
                if self._closed == self._gen:  # else another thread opened it
                    for table in (self._timings, self._samples):
                        for name in [n for n, s in table.items() if s.in_session]:
                            del table[name]
                    self._gen += 1
        return True

    @contextlib.contextmanager
    def span(self, name: str, **meta: Any) -> Iterator[bool]:
        """Time a block on the profiler's clock: a ``TraceAnnotation`` named
        ``adapcc.<name>`` carrying ``meta`` (``step=<n>`` is what the spans
        of one step share), its duration recorded under ``name`` in the
        timings.  Yields whether it is on.

        On exactly while a JAX profiler session is live
        (:func:`profiler_trace`, ``jax.profiler.start_trace``, or a capture
        through ``jax.profiler.start_server``).  Off, it checks that and
        yields: no clock read, no annotation, no lock.  The first span of a
        session drops what the session before it recorded (counters, gauges
        and what was recorded outside any session persist), so a reader
        after a profile sees that profile's spans and only those.
        """
        if not self._live():
            yield False
            return
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(SPAN_PREFIX + name, **meta):
                yield True
        finally:
            self._add(self._timings, name, time.perf_counter() - t0, True)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "timings": {k: t.summary("_s") for k, t in self._timings.items()},
                "samples": {k: t.summary() for k, t in self._samples.items()},
                "spans": {k: list(v) for k, v in self._spans.items()},
            }

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True)


_DEFAULT_REGISTRY = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """THE process-wide registry: what ``DDPTrainer``, ``GradSyncHook``,
    the input pipeline and the codec timings record into when handed no
    registry of their own."""
    return _DEFAULT_REGISTRY


# --- collective dispatch trace (log/track.txt analog) -------------------------


@dataclass
class TraceEvent:
    ts: float
    primitive: str
    impl: str
    nbytes: int
    step: Optional[int] = None
    extra: Dict[str, Any] = field(default_factory=dict)


class CollectiveTrace:
    """Records engine dispatches — which collective ran, with what payload,
    under which implementation.  The reference dumps per-chunk arrival lines
    into log/track.txt from inside the CUDA contexts; under XLA the chunk
    loop lives inside one compiled program, so the traceable boundary is the
    dispatch (one event per collective call), with Perfetto
    (:func:`profiler_trace`) covering intra-program detail.

    Capacity is a bounded **ring**: at capacity the *oldest* event is
    evicted for each new one, so a long run's trace ends with the steady
    state it was running in, not the startup noise it left hours ago.
    ``dropped`` counts evictions.
    """

    def __init__(self, capacity: int = 100_000) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._events: "deque[TraceEvent]" = deque(maxlen=capacity)
        self._dropped = 0

    def record(
        self,
        primitive: str,
        impl: str,
        nbytes: int,
        step: Optional[int] = None,
        **extra: Any,
    ) -> None:
        ev = TraceEvent(time.time(), primitive, impl, nbytes, step, extra)
        with self._lock:
            if len(self._events) >= self.capacity:
                self._dropped += 1  # the deque evicts its oldest on append
            self._events.append(ev)

    def events(self) -> List[TraceEvent]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def dump(self, path: str) -> None:
        """``track.txt``-style lines: ``ts primitive impl nbytes step {extra}``."""
        with open(path, "w") as f:
            for e in self.events():
                f.write(
                    f"{e.ts:.6f} {e.primitive} {e.impl} {e.nbytes} "
                    f"{-1 if e.step is None else e.step} {json.dumps(e.extra)}\n"
                )

    def impl_summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-impl dispatch statistics over the buffered events: count,
        how many carried a measured ``duration_s``, and nearest-rank
        p50/p99 over those durations (None with nothing timed).  The
        aggregation a tail claim needs from a trace — e.g. decode-step
        allreduces under ``rd`` vs ``ring`` — without hand-scraping the
        event list."""
        grouped: Dict[str, List[float]] = {}
        counts: Dict[str, int] = {}
        for e in self.events():
            counts[e.impl] = counts.get(e.impl, 0) + 1
            if "duration_s" in e.extra:
                grouped.setdefault(e.impl, []).append(
                    float(e.extra["duration_s"])
                )
        out: Dict[str, Dict[str, Any]] = {}
        for impl, count in sorted(counts.items()):
            timed = sorted(grouped.get(impl, []))

            def pct(q: float) -> Optional[float]:
                if not timed:
                    return None
                return nearest_rank_percentile(timed, q)

            out[impl] = {
                "count": count,
                "timed": len(timed),
                "p50_s": pct(0.50),
                "p99_s": pct(0.99),
            }
        return out

    def dump_chrome_trace(self, path: str, impl_summary: bool = True) -> str:
        """``chrome://tracing`` / Perfetto JSON: one complete ("X") event
        per dispatch.  Events that carry a measured ``duration_s`` (the
        tuner's record mode) render with real extent; untimed dispatches
        render as instants.  Args carry the plan provenance — impl, bytes,
        wire dtype, and the tuner decision — so a timeline click answers
        "what ran here and who chose it".

        With ``impl_summary`` (default on), one extra slice per impl lands
        on a dedicated ``summary`` track (tid 1), spanning that impl's
        first→last dispatch, with :meth:`impl_summary`'s count/p50/p99 in
        its args — so per-impl tail behavior (the decode-step p99 a
        serving claim keys on) is one timeline click, no hand-aggregation.
        """
        trace_events = []
        for e in self.events():
            dur_us = float(e.extra.get("duration_s", 0.0)) * 1e6
            # timed dispatches are recorded AFTER completion, so e.ts is the
            # slice END; the slice must start duration earlier or every
            # event renders shifted right by its own extent
            args: Dict[str, Any] = {"impl": e.impl, "nbytes": e.nbytes}
            if e.step is not None:
                args["step"] = e.step
            for k in ("chunk_bytes", "stage_bytes", "wire_dtype", "wire_bytes"):
                if k in e.extra:
                    args[k] = e.extra[k]
            tuner = e.extra.get("tuner")
            if isinstance(tuner, dict):
                args["tuner_source"] = tuner.get("source")
                args["tuner_applied"] = tuner.get("applied")
                args["tuner_chosen"] = tuner.get("chosen")
            trace_events.append(
                {
                    "name": e.primitive,
                    "cat": "collective",
                    "ph": "X",
                    "ts": e.ts * 1e6 - dur_us,  # microseconds, start-of-slice
                    "dur": dur_us,
                    "pid": 0,
                    "tid": 0,
                    "args": args,
                }
            )
        if impl_summary:
            spans: Dict[str, List[float]] = {}
            for e in self.events():
                dur_us = float(e.extra.get("duration_s", 0.0)) * 1e6
                start = e.ts * 1e6 - dur_us
                span = spans.setdefault(e.impl, [start, e.ts * 1e6])
                span[0] = min(span[0], start)
                span[1] = max(span[1], e.ts * 1e6)
            for impl, stats in self.impl_summary().items():
                lo, hi = spans[impl]
                args = {
                    "count": stats["count"],
                    "timed": stats["timed"],
                }
                if stats["p50_s"] is not None:
                    args["p50_us"] = stats["p50_s"] * 1e6
                    args["p99_us"] = stats["p99_s"] * 1e6
                trace_events.append(
                    {
                        "name": f"summary:{impl}",
                        "cat": "summary",
                        "ph": "X",
                        "ts": lo,
                        "dur": max(hi - lo, 1.0),
                        "pid": 0,
                        "tid": 1,
                        "args": args,
                    }
                )
            trace_events.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 0,
                    "tid": 1,
                    "args": {"name": "per-impl summary (p50/p99)"},
                }
            )
        with open(path, "w") as f:
            json.dump(
                {"traceEvents": trace_events, "displayTimeUnit": "ms"},
                f,
                sort_keys=True,
            )
        return path


def parse_track_log(path: str) -> List[TraceEvent]:
    """Read a :meth:`CollectiveTrace.dump` file back into events."""
    out: List[TraceEvent] = []
    with open(path) as f:
        for line in f:
            parts = line.split(" ", 5)
            if len(parts) != 6:
                continue
            step = int(parts[4])
            out.append(
                TraceEvent(
                    ts=float(parts[0]),
                    primitive=parts[1],
                    impl=parts[2],
                    nbytes=int(parts[3]),
                    step=None if step < 0 else step,
                    extra=json.loads(parts[5]),
                )
            )
    return out


# --- jax profiler (Perfetto) --------------------------------------------------


@contextlib.contextmanager
def profiler_trace(log_dir: str) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace (XLA ops, transfers, host activity)
    into ``log_dir`` — the TPU answer to the reference's nsys reports
    (nccl-perf/tree/report_allreduce.txt, SURVEY.md §5.1).

    This is also how an operator turns the program's spans on: inside the
    context every :meth:`MetricsRegistry.span` is live, lands in the trace
    as ``adapcc.<name>`` on the device's clock, and is summarised in the
    registry's timings afterwards (docs/OBSERVABILITY.md)."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
