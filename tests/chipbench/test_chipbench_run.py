"""``chipbench/run.py`` end to end at toy widths on the virtual CPU devices:
one chip's worth and four, the result line, the add-files-only rule, the
broken timed path, and the refusal to run without a TPU."""

import json

import numpy as np
import pytest

from chipbench_tiny import make_tree, run_cell, tiny_config, tiny_mix

from chipbench import run


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell,world", [("tiny-w1", 1), ("tiny-w4", 4)])
def test_a_cell_runs_end_to_end_and_prints_the_result_line(tree, capsys, cell, world):
    code, line, out = run_cell(tree, cell, capsys, seed=2**31 + 5)
    assert code == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_step_p95_ms", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # whole steps only: tokens = steps x global batch x T, over the window printed
    window = next(l for l in out.splitlines() if "window:" in l)
    steps, seconds = int(window.split()[2]), float(window.split()[5])
    assert steps == line["attempted"]
    assert line["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
        steps * 4 * world * 64 / seconds, rel=1e-2
    )
    # every number compared is printed beside its limit
    for name in ("loss_gap.step1", "loss_gap.step3", "grad_norm_gap", "update_norm_gap"):
        assert f"correct: {name} = " in out
    assert ("parameters differ between chips = 0" in out) == (world == 4)
    saved = json.loads((tree / "chiprun_out/chipbench" / f"{cell}.seed{2**31 + 5}.trace0/result.json").read_text())
    assert saved == line


def test_new_cell_configuration_mix_and_metric_are_found_by_name_with_no_edit(tree, capsys):
    """What a later PR does: new files and manifest entries only."""
    before = {p: p.read_bytes() for p in (tree / "chipbench").rglob("*") if p.is_file()}
    cfg = tiny_config("tiny-wide")
    cfg.update(d_model=96, n_head=3)
    (tree / "chipbench/configs/tiny-wide.json").write_text(json.dumps(cfg))
    (tree / "chipbench/traffic/tiny-b2.json").write_text(json.dumps(tiny_mix(batch_per_chip=2)))
    (tree / "chipbench/metrics/steps_in_window.py").write_text(
        'UNIT = "count"\nLAYER = "trainer"\nMOVES = "train_tokens_per_s"\nSOURCE = "program_counter"\n\n\n'
        "def read(facts):\n    return facts['steps']\n"
    )
    manifest = json.loads((tree / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-wide", "source": "test", "file": "chipbench/configs/tiny-wide.json",
        "reduced": cfg["reduced"], "why": "added by a later PR",
    })
    manifest["workloads"].append(
        {"name": "tiny-wide-w2", "config": "tiny-wide", "traffic": "tiny-b2", "chips": 1, "why": "added"}
    )
    manifest["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "trainer", "moves": "train_tokens_per_s", "workloads": ["tiny-wide-w2"],
    })
    (tree / "BENCHMARK.json").write_text(json.dumps(manifest))

    code, line, _ = run_cell(tree, "tiny-wide-w2", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    assert line["metrics"]["steps_in_window"] == {"value": float(line["attempted"]), "unit": "count"}
    # the readers that need nothing from a device trace report; those that do return nothing
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert not {"flash_roofline", "grad_sync_exposed_ms", "train_tokens_per_s"} & set(line["metrics"])
    # the metric is the new cell's alone
    code, line, _ = run_cell(tree, "tiny-w1", capsys, trace=1)
    assert "steps_in_window" not in line["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "no file that was there was edited"


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def broken(self, state, batch, *a, **kw):
        _, loss = real(self, state, batch, *a, **kw)
        return state, loss

    monkeypatch.setattr(DDPTrainer, "step", broken)
    code, line, out = run_cell(tree, "tiny-w1", capsys)
    assert code == 0 and line["correct"] is False
    failed = [l for l in out.splitlines() if "FAILED" in l]
    assert any("update_norm_gap" in l for l in failed)


def test_a_part_of_the_batch_left_out_comes_out_not_correct(tree, capsys, monkeypatch):
    """The loss limit is there for this fault: half of every batch's rows are
    replaced by copies of the other half before the step sees them."""
    import jax.numpy as jnp

    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def half(self, state, batch, *a, **kw):
        n = batch.shape[0] // 2
        return real(self, state, jnp.concatenate([batch[:n], batch[:n]]), *a, **kw)

    monkeypatch.setattr(DDPTrainer, "step", half)
    code, line, out = run_cell(tree, "tiny-w1", capsys)
    assert code == 0 and line["correct"] is False
    assert any("loss_gap" in l or "grad_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result(tree, capsys):
    with pytest.raises(SystemExit) as gone:
        run.main(
            ["--workload", "tiny-w1", "--seed", "1", "--seconds", "1", "--trace", "0"],
            root=tree, bench=tree / "chipbench",
        )
    assert gone.value.code not in (0, None)
    assert "needs a TPU" in str(gone.value.code)
    assert "{" not in capsys.readouterr().out


def test_an_unknown_cell_is_refused(tree):
    with pytest.raises(SystemExit):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"], require_chip=False, root=tree,
                 bench=tree / "chipbench")
