"""The four per-layer metrics of the compile path (``step_trace_lower_s``,
``step_load_s``, ``step_cache_misses``, ``step_recompiles``), end to end at toy
widths on the virtual CPU devices: printed by a traced run and only by a traced
run, and absent over a program that lacks what they read."""

import json

import pytest

from chipbench_tiny import ROOT, make_tree, run_cell, tiny_config

from chipbench import program_registry, run

COMPILE_PATH = ("step_trace_lower_s", "step_load_s", "step_cache_misses", "step_recompiles")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """One cell on one device, a layer narrower than ``chipbench_tiny``'s: the
    suite is near its limit, and nothing here rests on the model's size."""
    tree = make_tree(tmp_path_factory.mktemp("bench"), cells=(("tiny-w1", 1),))
    config = dict(tiny_config(), n_layer=1, d_model=32, vocab_size=128)
    (tree / "chipbench/configs/tiny.json").write_text(json.dumps(config))
    return tree


def read(name):
    return run.load_reader(name, ROOT / "chipbench" / "metrics").read({})


def test_a_traced_run_prints_the_four(tree, capsys):
    # the registry is the process's: other tests of this worker have built steps too, so compare with what was there
    before = {name: read(name) or 0.0 for name in COMPILE_PATH}
    code, line, _ = run_cell(tree, "tiny-w1", capsys, seconds=0.1, trace=1)
    assert code == 0
    got = {name: line["metrics"][name]["value"] - before[name] for name in COMPILE_PATH}
    units = {name: line["metrics"][name]["unit"] for name in COMPILE_PATH}
    assert units == dict(zip(COMPILE_PATH, ("s", "s", "count", "count")))
    # one trainer, no persistent cache off the chip: one build, and XLA compiled it
    assert got["step_cache_misses"] == 1 and got["step_trace_lower_s"] > 0 and got["step_load_s"] > 0
    # the program's count of steps that compiled agrees with the benchmark's own listener
    assert got["step_recompiles"] == line["metrics"]["window_compiles"]["value"] == 0
    # the build is the first of set-up's checked steps, and the two timings are its span
    build = program_registry.snapshot()["spans"]["step.build"][-1]
    assert (build["cause"], build["step"]) == ("step", 0)
    assert build["end_s"] - build["start_s"] == pytest.approx(got["step_trace_lower_s"] + got["step_load_s"], abs=1e-6)


def test_an_untraced_run_prints_none_of_them(tree, capsys, monkeypatch):
    """``run.py`` prints per-layer metrics in a traced run alone; the runner is
    stood in for (its own tests drive it), so that this costs no second compile."""
    from chipbench.runners import train

    end_to_end = {"train_tokens_per_s": 1.0, "train_step_p95_ms": 1.0, "setup_s": 1.0}
    monkeypatch.setattr(train, "run", lambda spec: {
        "correct": True, "attempted": 1, "failed": 0, "end_to_end": end_to_end, "facts": {}, "device": {},
    })
    code, line, _ = run_cell(tree, "tiny-w1", capsys)
    assert code == 0 and set(line["metrics"]) == set(end_to_end)


@pytest.mark.parametrize("name", COMPILE_PATH)
@pytest.mark.parametrize("parent", ["no registry", "a registry without the names"])
def test_over_the_parents_program_the_readers_return_nothing(monkeypatch, name, parent):
    """What the driver does with the parent: these files laid over a program
    that has no registry, or one whose trainer records no build."""
    from adapcc_tpu.utils import observability

    if parent == "no registry":
        monkeypatch.delattr(observability, "default_registry")
    else:
        monkeypatch.setattr(observability, "_DEFAULT_REGISTRY", observability.MetricsRegistry())
    assert read(name) is None
