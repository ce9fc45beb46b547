"""The compile path reports itself (``adapcc_tpu/utils/compile_cache.py``,
``DDPTrainer._first_call``): what JAX traced, lowered and compiled, what each
step program's first call cost and why it was made, and which step compiled
when it should not have.  Everything here reads the default registry, which
other tests of the same process write too: each test looks only at what began
after its own mark on ``time.perf_counter``, the clock the spans are kept on."""

import logging
import threading
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from adapcc_tpu.comm.mesh import build_world_mesh
from adapcc_tpu.ddp import DDPTrainer
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.compile_cache import compile_watch
from adapcc_tpu.utils.observability import MetricsRegistry, default_registry

WORLD = 4
BUILD_PARTS = ("step.build", "step.build.load", "step.build.trace_lower")


def since(mark, name):
    """The kept spans of ``name`` that began after ``mark``."""
    return [s for s in default_registry().snapshot()["spans"].get(name, []) if s["start_s"] >= mark]


def counts():
    snap = default_registry().snapshot()
    out = {name: snap["timings"].get(name, {"count": 0})["count"] for name in BUILD_PARTS}
    out.update({k: snap["counters"].get(k, 0) for k in ("step.recompiles", "step.build.cache_misses")})
    return out


def moved(before):
    return {k: v - before[k] for k, v in counts().items() if v != before[k]}


def build_seconds():
    timings = default_registry().snapshot()["timings"]
    return [timings.get(name, {"total_s": 0.0})["total_s"] for name in BUILD_PARTS]


def tiny_trainer(strategy=None):
    def loss(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    trainer = DDPTrainer(loss, optax.sgd(0.1), build_world_mesh(WORLD), strategy or Strategy.ring(WORLD))
    return trainer, trainer.init_state({"w": jnp.ones((4, 3))}), jnp.ones((2 * WORLD, 4))


@pytest.fixture(scope="module")
def stepped():
    """A trainer after its first two steps, with what they left."""
    mark, before, seconds = time.perf_counter(), counts(), build_seconds()
    trainer, state, batch = tiny_trainer()
    state, _ = trainer.step(state, batch)
    first, seconds = moved(before), [b - a for a, b in zip(seconds, build_seconds())]
    state, _ = trainer.step(state, batch)
    return {
        "trainer": trainer, "state": state, "batch": batch, "mark": mark, "first": first, "both": moved(before),
        "seconds": seconds,
    }


@pytest.mark.parametrize("name", ["compile.trace", "compile.lower", "compile.backend"])
def test_a_fresh_jit_leaves_one_timing_of_each_kind_under_its_name(name):
    compile_watch()

    def only_here_7391(x):
        return x * 2 + 1

    before = default_registry().snapshot()["timings"].get(name, {"count": 0})["count"]
    mark = time.perf_counter()
    jax.jit(only_here_7391)(jnp.ones(3)).block_until_ready()
    after = time.perf_counter()
    mine = [s for s in since(mark, name) if "only_here_7391" in s["fun_name"]]
    assert len(mine) == 1
    # the event carried a duration: its end is the clock's reading when it arrived
    assert mark <= mine[0]["start_s"] < mine[0]["end_s"] <= after
    assert default_registry().snapshot()["timings"][name]["count"] >= before + 1
    jax.jit(only_here_7391)(jnp.ones(3))        # the same shape again: nothing new
    assert len([s for s in since(mark, name) if "only_here_7391" in s["fun_name"]]) == 1


def test_observe_keeps_a_span_whole_when_given_its_end_and_only_the_newest():
    reg = MetricsRegistry()
    reg.observe("plain", 0.5)
    assert reg.snapshot()["spans"] == {} and reg.snapshot()["timings"]["plain"]["total_s"] == 0.5
    for i in range(MetricsRegistry.SPANS_KEPT + 3):
        reg.observe("kept", 2.0, end=10.0 + i, cause="test", step=i)
    kept = reg.snapshot()["spans"]["kept"]
    assert len(kept) == MetricsRegistry.SPANS_KEPT and reg.snapshot()["timings"]["kept"]["count"] == len(kept) + 3
    assert kept[0] == {"start_s": 11.0, "end_s": 13.0, "cause": "test", "step": 3}


def test_the_first_step_leaves_one_build_whose_parts_are_its_duration_and_the_second_none(stepped):
    # one of each timing, one miss (no persistent cache in force here), no recompile; the second step adds nothing
    assert stepped["first"] == {**{name: 1 for name in BUILD_PARTS}, "step.build.cache_misses": 1}
    assert stepped["both"] == stepped["first"]
    (build,) = since(stepped["mark"], "step.build")
    trainer = stepped["trainer"]
    fingerprint, codec, overlap = trainer._program_key()
    assert {k: build[k] for k in ("gen", "fingerprint", "codec", "overlap", "cause", "step")} == {
        "gen": 1, "fingerprint": fingerprint, "codec": codec, "overlap": overlap, "cause": "step", "step": 0,
    }
    # the backend compiles inside the span (the step's, and the slicing of a batch that came unsharded) are its load
    inside = [s for s in since(build["start_s"], "compile.backend") if s["end_s"] <= build["end_s"]]
    assert any("ddp_step" in s["fun_name"] for s in inside)
    span, load, rest = stepped["seconds"]
    assert span == pytest.approx(build["end_s"] - build["start_s"])
    assert load == pytest.approx(sum(s["end_s"] - s["start_s"] for s in inside), abs=1e-6)
    assert rest > 0 and load + rest == pytest.approx(span, abs=1e-6)


@pytest.mark.parametrize("prewarmed", [False, True], ids=["cold", "prewarmed"])
def test_a_rebuild_builds_at_the_step_after_it_unless_the_program_was_prewarmed(prewarmed):
    trainer, state, batch = tiny_trainer()
    for _ in range(3):
        state, _ = trainer.step(state, batch)
    mark, before = time.perf_counter(), counts()
    standby = Strategy.binary(WORLD)
    if prewarmed:
        assert trainer.prewarm(standby, state, batch)
        (warm,) = since(mark, "step.build")
        assert (warm["cause"], warm["step"], warm["gen"], warm["fingerprint"]) == ("prewarm", 3, 2, standby.fingerprint())
        mark, before = time.perf_counter(), counts()
    assert trainer.adopt_strategy(standby) is prewarmed
    state, _ = trainer.step(state, batch)
    state, _ = trainer.step(state, batch)
    if prewarmed:
        assert moved(before) == {} and since(mark, "step.build") == []
    else:
        assert moved(before) == {**{name: 1 for name in BUILD_PARTS}, "step.build.cache_misses": 1}
        (build,) = since(mark, "step.build")
        assert (build["cause"], build["step"], build["gen"], build["fingerprint"]) == ("step", 3, 2, standby.fingerprint())
    assert trainer.recompiles == 2


def test_a_batch_of_another_shape_is_a_recompile_named_by_its_step_and_logged_once(stepped, caplog):
    trainer, state, batch = stepped["trainer"], stepped["state"], stepped["batch"]
    before, at = counts(), trainer._host_step
    with caplog.at_level(logging.WARNING, logger="adapcc_tpu.utils.compile_cache"):
        state, _ = trainer.step(state, batch)                         # nothing
        assert moved(before) == {} and caplog.records == []
        state, _ = trainer.step(state, jnp.ones((4 * WORLD, 4)))      # the same key, another shape
        state, _ = trainer.step(state, jnp.ones((4 * WORLD, 4)))      # that program again
    # the trainer's own count saw nothing, and no build was recorded: the program's key did not change
    assert moved(before) == {"step.recompiles": 1} and trainer.recompiles == 1
    assert default_registry().snapshot()["gauges"]["step.recompiles.last_step"] == at + 1
    (record,) = caplog.records
    assert f"step {at + 1} " in record.getMessage() and "ddp_step" in record.getMessage()
    assert compile_watch().here.step is None    # and the mark is gone with the call


def test_two_trainers_in_one_process_do_not_count_each_others_builds(stepped):
    mark, before = time.perf_counter(), counts()
    other, state, batch = tiny_trainer()
    state, _ = other.step(state, batch)
    state, _ = other.step(state, batch)
    # the second trainer's program is its own first call, not the first trainer's recompile
    assert moved(before) == {**{name: 1 for name in BUILD_PARTS}, "step.build.cache_misses": 1}
    (build,) = since(mark, "step.build")
    assert (build["gen"], build["step"]) == (1, 0) and (stepped["trainer"].recompiles, other.recompiles) == (1, 1)
    stepped["trainer"].step(stepped["state"], stepped["batch"])
    assert moved(before).get("step.recompiles") is None


def test_what_another_thread_compiles_is_neither_this_steps_nor_this_builds():
    watch = compile_watch()
    before = counts()

    def elsewhere():
        jax.jit(lambda x: x - 3)(jnp.ones(5)).block_until_ready()

    watch.here.step = 11                        # as inside DDPTrainer.step, on this thread
    try:
        with watch.building() as build:
            worker = threading.Thread(target=elsewhere)
            worker.start()
            worker.join()
        assert (build.load_s, build.cache_hits) == (0.0, 0)
        worker = threading.Thread(target=elsewhere)
        worker.start()
        worker.join()
    finally:
        watch.here.step = None
    assert moved(before) == {}


def test_a_build_under_a_live_profile_is_an_annotation_too_and_outlives_the_next_session(profile):
    trainer, state, batch = tiny_trainer()
    state, _ = trainer.step(state, batch)
    mark = time.perf_counter()
    trainer.rebuild(Strategy.binary(WORLD))
    with profile("a") as prof:
        state, _ = trainer.step(state, batch)       # step 1 pays for the new program, inside the profile
        state, _ = trainer.step(state, batch)
    (annotation,) = [(stats, dur) for name, _, dur, stats in prof.spans() if name == "adapcc.step.build"]
    (build,) = since(mark, "step.build")
    assert {k: annotation[0][k] for k in ("cause", "step", "gen")} == {"cause": "step", "step": 1, "gen": 2}
    assert annotation[1] / 1e9 == pytest.approx(build["end_s"] - build["start_s"], rel=0.05)
    state, _ = trainer.step(state, batch)           # off, between the sessions
    with profile("b"):
        state, _ = trainer.step(state, batch)
        timings = default_registry().snapshot()["timings"]
    # the second session dropped the first one's step spans, and kept the build
    assert timings["step.enqueue"]["count"] == 1 and since(mark, "step.build") == [build]
