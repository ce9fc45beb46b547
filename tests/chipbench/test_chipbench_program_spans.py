"""The six per-layer metrics that read the program's own spans, samples and
gauges (``adapcc_tpu.utils.observability.default_registry``), end to end at
toy widths on the virtual CPU devices: printed by a traced run and only by
a traced run, tiling the benchmark's own span around ``trainer.step``, and
equal to what the parameter tree says the hook has to move."""

import json

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT, make_tree, run_cell, tiny_config

from chipbench import program_registry, run, weights

STEP = {"step_enqueue_ms", "step_host_self_ms"}
FEED = {"input_queue_depth", "input_h2d_ms"}
SYNC = {"grad_sync_bytes_per_step", "grad_sync_calls_per_step"}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


def leaves_and_bytes(config):
    """Leaf count and float32 bytes of the configuration's parameter tree."""
    shapes = [
        shape for shape, _ in jax.tree_util.tree_leaves(weights.leaf_table(config), is_leaf=weights._is_leaf)
    ]
    return len(shapes), 4 * sum(int(np.prod(s)) for s in shapes)


def test_a_traced_four_chip_run_prints_all_six_and_they_agree_with_the_benchmarks_own(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-w4", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert STEP | FEED | SYNC <= set(got)
    # the program's three spans tile the benchmark's span around trainer.step
    assert 0 < got["step_enqueue_ms"] + got["step_host_self_ms"] <= got["step_dispatch_ms"]
    assert got["step_host_self_ms"] > 0
    assert 0 <= got["input_queue_depth"] <= 2 and got["input_h2d_ms"] > 0
    leaves, nbytes = leaves_and_bytes(tiny_config())
    assert got["grad_sync_calls_per_step"] == leaves  # one psum per leaf
    assert got["grad_sync_bytes_per_step"] == pytest.approx(nbytes / 1e6)
    assert line["metrics"]["grad_sync_bytes_per_step"]["unit"] == "MB"
    # each step span was taken once per step of the window, and only there
    timings = program_registry.snapshot()["timings"]
    assert {timings[f"step.{part}"]["count"] for part in ("prepare", "enqueue", "finish")} == {line["attempted"]}


def test_a_traced_one_chip_run_prints_the_four_that_apply(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-w1", capsys, trace=1)
    assert code == 0
    assert STEP | FEED <= set(line["metrics"]) and not SYNC & set(line["metrics"])


def test_an_untraced_run_prints_none_of_them(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-w4", capsys)
    assert code == 0
    assert not (STEP | FEED | SYNC) & set(line["metrics"])


def test_what_the_medium_cell_has_to_read():
    medium = json.loads((ROOT / "chipbench/configs/gpt2-medium.json").read_text())
    leaves, nbytes = leaves_and_bytes(medium)
    assert leaves == 292 and round(nbytes / 1e6) == 1419


def test_over_a_program_with_no_default_registry_the_readers_return_nothing(monkeypatch):
    """What the driver does with the parent: these files laid over a program
    from before the registry existed."""
    from adapcc_tpu.utils import observability

    monkeypatch.delattr(observability, "default_registry")
    facts = {"world": 4}
    for name in sorted(STEP | FEED | SYNC):
        assert run.load_reader(name, ROOT / "chipbench" / "metrics").read(facts) is None
