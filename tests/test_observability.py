"""Observability: meters, metrics registry and its spans, collective trace."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.utils import (
    AverageMeter,
    CollectiveTrace,
    MetricsRegistry,
    ProgressMeter,
    default_registry,
    parse_track_log,
)
from adapcc_tpu.utils import observability


def test_average_meter():
    m = AverageMeter("loss", ":.2f")
    m.update(2.0)
    m.update(4.0, n=3)
    assert m.val == 4.0
    assert m.avg == pytest.approx((2 + 12) / 4)
    assert "loss" in str(m)
    m.reset()
    assert m.count == 0


def test_progress_meter(capsys):
    m = AverageMeter("acc", ":.1f")
    m.update(81.25)
    line = ProgressMeter(500, [m], prefix="epoch 1 ").display(10)
    out = capsys.readouterr().out
    assert line in out
    assert "acc" in line and "[ 10/500]" in line


def test_metrics_registry():
    reg = MetricsRegistry()
    reg.incr("collectives")
    reg.incr("collectives", 2)
    reg.gauge("bw_gbps", 3.5)
    with reg.timer("step"):
        pass
    snap = json.loads(reg.to_json())
    assert snap["counters"]["collectives"] == 3
    assert snap["gauges"]["bw_gbps"] == 3.5
    assert snap["timings"]["step"]["count"] == 1
    assert snap["timings"]["step"]["mean_s"] >= 0


def test_metrics_registry_percentiles():
    """observe() keeps count/total exact AND p50/p99 over a bounded
    reservoir: 1..1000ms observed once each must snapshot a median near
    500ms and a p99 near the tail, not just a mean."""
    reg = MetricsRegistry()
    for ms in range(1, 1001):
        reg.observe("sync", ms / 1000.0)
    t = reg.snapshot()["timings"]["sync"]
    assert t["count"] == 1000 and t["max_s"] == 1.0
    assert t["total_s"] == pytest.approx(500.5)
    # the reservoir is a uniform subsample: percentiles are approximate
    assert 0.35 <= t["p50_s"] <= 0.65
    assert t["p99_s"] >= 0.9
    assert t["p50_s"] <= t["p99_s"] <= t["max_s"]


def test_metrics_registry_reservoir_is_bounded_and_deterministic():
    def fill():
        reg = MetricsRegistry()
        for i in range(5 * MetricsRegistry.RESERVOIR_SIZE):
            reg.observe("t", float(i))
        return reg

    a, b = fill(), fill()
    assert len(a._timings["t"].reservoir) == MetricsRegistry.RESERVOIR_SIZE
    # deterministic replacement: identical runs snapshot identical stats
    assert a.snapshot() == b.snapshot()


def test_codec_timings_flow_through_registry():
    """The quant satellite: per-codec quantize/dequantize wall times are
    recorded through MetricsRegistry.observe and surface with percentiles."""
    import jax.numpy as _jnp

    from adapcc_tpu.quant import timed_roundtrip

    reg = MetricsRegistry()
    x = _jnp.ones((4096,), _jnp.float32)
    for _ in range(3):
        out = timed_roundtrip("int8", x, registry=reg)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-2)
    snap = reg.snapshot()["timings"]
    for name in ("quant.int8.quantize", "quant.int8.dequantize"):
        assert snap[name]["count"] == 3
        assert 0 <= snap[name]["p50_s"] <= snap[name]["p99_s"]


def test_collective_trace_roundtrip(tmp_path):
    tr = CollectiveTrace()
    tr.record("allreduce", "psum", 4096, step=3, strategy="ring")
    tr.record("all_to_all", "xla", 128)
    path = str(tmp_path / "track.txt")
    tr.dump(path)
    back = parse_track_log(path)
    assert len(back) == 2
    assert back[0].primitive == "allreduce"
    assert back[0].step == 3
    assert back[0].extra == {"strategy": "ring"}
    assert back[1].step is None


def test_collective_trace_bounded():
    tr = CollectiveTrace(capacity=2)
    for _ in range(5):
        tr.record("allreduce", "psum", 1)
    assert len(tr.events()) == 2
    assert tr.dropped == 3


def test_collective_trace_evicts_oldest_first():
    """At capacity the ring evicts the OLDEST events: a long run's trace
    must end with the steady state, not hours-old startup noise."""
    tr = CollectiveTrace(capacity=3)
    for i in range(10):
        tr.record("allreduce", "psum", i)
    assert [e.nbytes for e in tr.events()] == [7, 8, 9]  # newest retained
    assert tr.dropped == 7
    tr.record("reduce", "psum", 10)
    assert [e.nbytes for e in tr.events()] == [8, 9, 10]
    assert tr.dropped == 8


def test_collective_trace_rejects_degenerate_capacity():
    with pytest.raises(ValueError, match="capacity"):
        CollectiveTrace(capacity=0)


def test_dump_chrome_trace(tmp_path):
    tr = CollectiveTrace()
    tr.record(
        "allreduce", "pallas_ring[hbm-stream]", 1 << 20, step=4,
        chunk_bytes=65536, wire_dtype="off", duration_s=250e-6,
        tuner={"chosen": {"wire_dtype": "off"}, "source": "measured",
               "applied": True},
    )
    tr.record("broadcast", "xla", 4096)  # untimed: renders as an instant
    path = str(tmp_path / "trace.json")
    assert tr.dump_chrome_trace(path) == path
    doc = json.loads(open(path).read())
    evs = [e for e in doc["traceEvents"] if e.get("cat") == "collective"]
    assert len(evs) == 2 and all(e["ph"] == "X" for e in evs)
    timed = evs[0]
    assert timed["name"] == "allreduce"
    assert timed["dur"] == 250e-6 * 1e6  # microseconds
    assert timed["args"]["impl"] == "pallas_ring[hbm-stream]"
    assert timed["args"]["nbytes"] == 1 << 20
    assert timed["args"]["wire_dtype"] == "off"
    assert timed["args"]["tuner_source"] == "measured"
    assert timed["args"]["tuner_applied"] is True
    assert evs[1]["dur"] == 0.0


def test_chrome_trace_per_impl_summary(tmp_path):
    """The export aggregates per-impl p50/p99 onto a dedicated summary
    track (ISSUE 14 satellite): decode-step tail behavior is one Perfetto
    click, no hand-scraping — and ``impl_summary=False`` drops the track
    for the raw view."""
    tr = CollectiveTrace()
    for i in range(10):
        tr.record(
            "allreduce", "rd", 1024,
            duration_s=(0.001 if i % 9 else 0.010),
        )
    tr.record("allreduce", "ring", 1024)  # untimed: counted, no percentiles
    stats = tr.impl_summary()
    assert stats["rd"]["count"] == 10 and stats["rd"]["timed"] == 10
    assert stats["rd"]["p50_s"] == pytest.approx(0.001)
    assert stats["rd"]["p99_s"] == pytest.approx(0.010)
    assert stats["ring"]["timed"] == 0 and stats["ring"]["p50_s"] is None
    path = str(tmp_path / "trace.json")
    tr.dump_chrome_trace(path)
    doc = json.loads(open(path).read())
    summ = {
        e["name"]: e for e in doc["traceEvents"]
        if e.get("cat") == "summary"
    }
    assert set(summ) == {"summary:rd", "summary:ring"}
    assert summ["summary:rd"]["args"]["p99_us"] == pytest.approx(10_000.0)
    assert summ["summary:rd"]["tid"] == 1  # its own track, off the dispatches
    assert "p50_us" not in summ["summary:ring"]["args"]
    tr.dump_chrome_trace(path, impl_summary=False)
    doc = json.loads(open(path).read())
    assert not [e for e in doc["traceEvents"] if e.get("cat") == "summary"]


def test_engine_records_dispatches(mesh4):
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy

    tr = CollectiveTrace()
    eng = CollectiveEngine(mesh4, Strategy.ring(4), trace=tr)
    x = jnp.ones((4, 8))
    eng.all_reduce(x)
    eng.all_reduce(x, active_gpus=[0, 1, 2])
    eng.broadcast(x)  # full world on a fastpath engine → fused xla collective
    eng.broadcast(x, active_gpus=[0, 1, 2, 3])  # pinned schedule path
    eng.all_gather(x)
    prims = [(e.primitive, e.impl) for e in tr.events()]
    assert prims == [
        ("allreduce", "xla"),
        ("allreduce", "schedule"),
        ("broadcast", "xla"),
        ("broadcast", "schedule"),
        ("all_gather", "xla"),
    ]
    assert tr.events()[0].nbytes == 4 * 8 * 4


def test_sample_is_a_unitless_distribution_beside_the_timings():
    reg = MetricsRegistry()
    for v in (1, 2, 2, 7):
        reg.sample("q.depth", v)
    snap = reg.snapshot()
    assert snap["samples"]["q.depth"] == {"count": 4, "mean": 3.0, "max": 7.0, "p50": 2.0, "p99": 7.0}
    assert snap["timings"] == {}
    json.loads(reg.to_json())


def test_span_off_checks_the_profiler_and_does_nothing_else(monkeypatch):
    """No profiler session: no clock read, no annotation object, nothing
    recorded; the block still runs and is told the span is off."""

    class NoAnnotation:
        def __init__(self, *a, **kw):
            raise AssertionError("an off span constructed a TraceAnnotation")

        @staticmethod
        def is_enabled():
            return False

    def no_clock():
        raise AssertionError("an off span read the clock")

    monkeypatch.setattr(observability, "TraceAnnotation", NoAnnotation)
    monkeypatch.setattr(observability.time, "perf_counter", no_clock)
    reg = MetricsRegistry()
    ran = []
    with reg.span("step.prepare", step=3) as live:
        ran.append(live)
    assert ran == [False]
    assert reg.snapshot()["timings"] == {}


def test_span_on_lands_in_the_profile_with_its_metadata(profile):
    import time

    reg = MetricsRegistry()
    with profile() as prof:
        t0 = time.perf_counter()
        for i in range(3):
            with reg.span("unit.work", step=i) as live:
                assert live is True
                time.sleep(0.002)
        host = time.perf_counter() - t0
    t = reg.snapshot()["timings"]["unit.work"]
    assert t["count"] == 3 and 0.006 <= t["total_s"] <= host
    spans = prof.spans()
    assert [(n, stats) for n, _, _, stats in spans] == [
        ("adapcc.unit.work", {"step": i}) for i in range(3)
    ]
    # the annotation and the timing are the same interval
    assert sum(d for _, _, d, _ in spans) / 1e9 == pytest.approx(t["total_s"], rel=0.2)
    # the session over, the span is off again
    with reg.span("unit.work") as live:
        assert live is False
    assert reg.snapshot()["timings"]["unit.work"]["count"] == 3


def test_spans_and_samples_belong_to_their_profiler_session(profile):
    reg = MetricsRegistry()
    reg.incr("c")
    reg.gauge("g", 2.0)
    reg.observe("outside.timing", 0.5)
    reg.sample("outside.sample", 9)
    with profile("a"):
        for _ in range(4):
            with reg.span("w"):
                reg.sample("depth", 1)
    first = reg.snapshot()
    assert first["timings"]["w"]["count"] == 4 and first["samples"]["depth"]["count"] == 4
    with reg.span("w"):  # an off check in between tells the sessions apart
        pass
    with profile("b"):
        with reg.span("v"):
            pass
        second = reg.snapshot()
    # the second session starts from zero; what no session owns persists
    assert set(second["timings"]) == {"v", "outside.timing"}
    assert set(second["samples"]) == {"outside.sample"}
    assert second["counters"] == {"c": 1.0} and second["gauges"] == {"g": 2.0}


def test_spans_from_many_threads_lose_no_update(profile):
    """The feed's producer and the training loop record into one registry
    at once: every span of every thread is counted, and a session is opened
    once however many threads see it first."""
    import sys
    import threading

    reg = MetricsRegistry()
    with reg.span("w"):  # off: the session below is a new one
        pass
    threads, per_thread = 12, 200

    def work():
        for i in range(per_thread):
            with reg.span("w", step=i):
                reg.sample("depth", i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with profile():
            workers = [threading.Thread(target=work) for _ in range(threads)]
            for t in workers:
                t.start()
            for t in workers:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in workers)
    finally:
        sys.setswitchinterval(interval)
    snap = reg.snapshot()
    assert snap["timings"]["w"]["count"] == threads * per_thread
    assert snap["samples"]["depth"]["count"] == threads * per_thread
    assert reg._gen == 1


def test_one_default_registry_and_the_codec_timings_use_it():
    import pathlib
    import re

    from adapcc_tpu.quant import timed_roundtrip

    reg = default_registry()
    assert reg is default_registry() and isinstance(reg, MetricsRegistry)
    before = reg.snapshot()["timings"].get("quant.bf16.quantize", {"count": 0})["count"]
    timed_roundtrip("bf16", jnp.ones((256,), jnp.float32))
    assert reg.snapshot()["timings"]["quant.bf16.quantize"]["count"] == before + 1
    # no second module-level registry anywhere in the package
    root = pathlib.Path(observability.__file__).resolve().parents[1]
    owners = [
        str(p.relative_to(root))
        for p in root.rglob("*.py")
        if re.search(r"^\w+\s*(:[^=]+)?=\s*MetricsRegistry\(", p.read_text(), re.M)
    ]
    assert owners == ["utils/observability.py"]


def test_profiler_trace_writes(tmp_path):
    import os

    from adapcc_tpu.utils import profiler_trace

    with profiler_trace(str(tmp_path / "prof")):
        jnp.sum(jnp.ones((16, 16))).block_until_ready()
    # a trace directory with at least one artifact appears
    entries = []
    for root, _, files in os.walk(tmp_path / "prof"):
        entries.extend(files)
    assert entries
