"""The yardstick's arithmetic, traffic and weights, at sizes a test holds."""

import json

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT, tiny_config, tiny_mix

from chipbench import arithmetic, correct, weights
from chipbench.runners import train
from chipbench.traffic import generator


def _config(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


@pytest.mark.parametrize("name,expect", [("gpt2-small", 797.8e6), ("gpt2-medium", 2.2719e9)])
def test_train_flops_per_token_counts_causal_attention_at_half(name, expect):
    got = arithmetic.train_flops_per_token(_config(name), 1024)
    assert got == pytest.approx(expect, rel=1e-3)
    cfg = _config(name)
    d, L, V = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    full = 3.0 * (L * (24 * d * d + 4 * 1024 * d) + 2 * d * V)  # bench.py's, full T x T
    assert got == pytest.approx(full - 3.0 * L * 2 * 1024 * d)


def test_flash_roofline_arithmetic_and_which_bound_binds():
    flops = arithmetic.flash_flops(16, 12, 1024, 64)
    nbytes = arithmetic.flash_bytes(16, 12, 1024, 64)
    assert flops["fwd"] == 2 * 2 * 16 * 12 * 1024 * 1024 / 2 * 64
    assert flops["bwd"] == 2.5 * flops["fwd"]
    assert nbytes == {"fwd": 4 * 16 * 12 * 1024 * 64 * 2, "bwd": 8 * 16 * 12 * 1024 * 64 * 2}
    peaks = arithmetic.peaks_for("TPU v5 lite")
    least = arithmetic.roofline_seconds(flops["fwd"], nbytes["fwd"], peaks)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(flops["fwd"] / 197e12)
    assert arithmetic.roofline_seconds(1.0, 819e9, peaks) == {"seconds": 1.0, "bound": "memory"}


def test_a_device_that_is_not_in_the_table_of_peaks_is_an_error():
    with pytest.raises(KeyError):
        arithmetic.peaks_for("cpu")


def test_markov_rows_are_seeded_distinct_and_in_range():
    mix = tiny_mix()
    a = generator.make_rows(mix, 512, 2**31 + 12345)
    b = generator.make_rows(mix, 512, 2**31 + 12345)
    c = generator.make_rows(mix, 512, 3)
    assert a.shape == (64, 64) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 512
    assert len({row.tobytes() for row in a}) == len(a), "rows all differ"
    # a sparse chain: each token is followed by few distinct successors
    follow = {}
    for row in a:
        for x, y in zip(row[:-1], row[1:]):
            follow.setdefault(int(x), set()).add(int(y))
    assert max(len(s) for s in follow.values()) <= mix["branching"]


def test_every_shipped_mix_loads_and_holds_whole_epochs():
    for path in (ROOT / "chipbench/traffic").glob("*.json"):
        mix = generator.load_mix(path.stem)
        assert mix["corpus_rows"] >= 4 * 4 * mix["batch_per_chip"], "an epoch holds steps on four chips"
        assert mix["seq_len"] == 1024 and mix["steps_per_sample"] >= 1
    with pytest.raises(FileNotFoundError):
        generator.load_mix("no-such-mix")


def test_weights_have_the_tree_the_program_reads_and_the_published_scheme():
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = tiny_config()
    model = GPT2(GPT2Config(**{k: cfg[k] for k in ("vocab_size", "max_seq", "n_layer", "n_head", "d_model")}))
    theirs = jax.eval_shape(model.init, jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    ours = weights.make_params(2**31 + 7, cfg)
    assert jax.tree_util.tree_structure(theirs) == jax.tree_util.tree_structure(ours)
    for a, b in zip(jax.tree_util.tree_leaves(theirs), jax.tree_util.tree_leaves(ours)):
        assert a.shape == b.shape and a.dtype == b.dtype
    p = ours["params"]
    assert float(np.std(p["wte"]["embedding"])) == pytest.approx(0.02, rel=0.05)
    assert float(np.std(p["h1"]["proj"]["kernel"])) == pytest.approx(0.02 / 2.0, rel=0.05)
    assert np.all(np.asarray(p["ln_f"]["scale"]) == 1) and np.all(np.asarray(p["h0"]["fc"]["bias"]) == 0)
    again = weights.make_params(2**31 + 7, cfg)
    other = weights.make_params(2**31 + 8, cfg)
    assert np.array_equal(p["wpe"]["embedding"], again["params"]["wpe"]["embedding"])
    assert not np.array_equal(p["wpe"]["embedding"], other["params"]["wpe"]["embedding"])


def test_worst_leaf_gap_is_held_against_the_median_leaf_where_a_leaf_is_all_but_zero():
    ref = [1.0, 2.0, 1e-9, 4.0, 3.0]
    assert correct.worst_leaf_gap(ref, ref) == 0.0
    assert correct.worst_leaf_gap([1.0, 2.0, 1e-3, 4.0, 3.0], ref) == pytest.approx(1e-3 / 2.0, rel=1e-3)
    assert correct.worst_leaf_gap([1.0, 2.2, 1e-9, 4.0, 3.0], ref) == pytest.approx(0.1)
    assert correct.worst_leaf_gap([1.0, float("nan"), 0, 4, 3], ref) == float("inf")


def test_compare_gives_each_number_its_own_limit_and_one_failure_fails():
    ref = {"losses": [10.0, 9.0, 8.0], "grad_norms": [1.0, 2.0, 3.0], "update_norms": [1.0, 1.0, 1.0]}
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 0.01, "update_norm_gap": 0.5}
    rows = correct.compare(ref, ref, limits)
    assert [r["name"] for r in rows] == [
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3", "grad_norm_gap", "update_norm_gap",
    ]
    assert correct.verdict(rows)
    bad = dict(ref, update_norms=[0.0, 0.0, 0.0])  # a step that returned its state unchanged
    rows = correct.compare(bad, ref, limits)
    assert not correct.verdict(rows) and [r["ok"] for r in rows] == [True] * 4 + [False]
    rows = correct.compare(dict(ref, losses=[10.0, 9.0, float("nan")]), ref, limits)
    assert not rows[2]["ok"]
    with pytest.raises(KeyError):
        correct.compare(ref, ref, {"loss_gap": 1.0})


class _FakeLoss:
    def __init__(self, clock, ready_at, value):
        self.clock, self.ready_at, self.value = clock, ready_at, value

    def block_until_ready(self):
        self.clock.now = max(self.clock.now, self.ready_at)


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class _FakeTrainer:
    """A device that takes 0.1 s a step, in order; dispatch costs 1 ms."""

    def __init__(self, clock):
        self.clock, self.free_at, self.steps = clock, 0.0, 0

    def step(self, state, batch):
        self.clock.now += 0.001
        self.free_at = max(self.free_at, self.clock.now) + 0.1
        self.steps += 1
        return state + 1, _FakeLoss(self.clock, self.free_at, float(self.steps))


def test_window_keeps_one_step_in_flight_and_times_completions():
    clock = _Clock()
    trainer = _FakeTrainer(clock)
    batches = iter(range(10_000))
    state, win = train.measure(trainer, 0, batches, 1.0, train.Spans(on=False), clock=clock)
    steps = len(win["done"])
    assert steps == trainer.steps == state == len(win["losses"])
    gaps = np.diff(win["done"])
    assert np.allclose(gaps, 0.1), "the device never waits for the host"
    assert win["done"][-1] - win["start"] == pytest.approx(0.001 + 0.1 * steps)
    assert 1.0 <= win["done"][-1] - win["start"] < 1.0 + 0.3
    samples = train.step_samples_ms(win["start"], win["done"], 1)
    assert len(samples) == steps - 1 and np.allclose(samples, 100.0)
    grouped = train.step_samples_ms(win["start"], win["done"], 3)
    assert len(grouped) == (steps - 1) // 3 and np.allclose(grouped, 100.0)
    assert train.percentile([1, 2, 3, 4, 100], 50) == 3.0
