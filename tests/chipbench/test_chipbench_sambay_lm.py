"""The ``train_sambay_lm`` runner at toy widths on the virtual CPU devices:
one traced run of the cell through ``run.main`` and one run of the readings
tool with float8 and the six changed pieces of the mathematics, each computed
once for the module (PERF.md section 7 item 27); the comparison's two rules
on made numbers; the configuration, mix and metric files the manifest names,
as ISSUE 41 states them; the accepted sixth cell's entries by name; the
readers on a recorded step's kernel names; the arithmetic against hand
counts."""

import json

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT, make_tree

from chipbench import arithmetic_sambay_lm

SAMBAY_METRICS = (
    "phi4_train_mfu", "sscan_time_share", "sscan_roofline", "phi4_attn_time_share", "phi4_attn_roofline",
    "phi4_band_tile_waste",
)
FAULTS = ("no_lambda", "norm_before_diff", "memory_after_gate", "no_skip", "window_off", "kv_own")
# float32 activations on the CPU: sound runs read 1e-7 to 3e-5, each control 4e-3 or more on the gradient
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}
CELL = "phi4-mini-flash-vp8-train"


def real_config() -> dict:
    return json.loads((ROOT / "chipbench/configs/phi4-mini-flash-vp8.json").read_text())


def real_mix() -> dict:
    return json.loads((ROOT / "chipbench/traffic/packed8192-b1-sambay.json").read_text())


def tiny_sambay_config() -> dict:
    real = real_config()
    real.update(
        name="tiny-sambay", vocab_size=256, hidden_size=32, intermediate_size=64, num_hidden_layers=6,
        layers_held=[0, 1, 6, 7, 8, 9], num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
        published={"num_hidden_layers": 12, "vocab_size": 256}, limits=dict(LIMITS),
    )
    real["assumed"]["mamba"] = {"d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 2}
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_sambay_mix() -> dict:
    return {
        "runner": "train_sambay_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_sambay"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-sambay.json").write_text(json.dumps(tiny_sambay_config()))
    (tmp / "chipbench/traffic/tiny-sambay-b2.json").write_text(json.dumps(tiny_sambay_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-sambay", "source": "test", "file": "chipbench/configs/tiny-sambay.json",
        "reduced": tiny_sambay_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-sambay", "config": "tiny-sambay", "traffic": "tiny-sambay-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in SAMBAY_METRICS:
            m["workloads"] = ["tiny-sambay"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def said_by(call) -> tuple:
    """``(what call() returned, all it printed)``: a module's fixture has no ``capsys``."""
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = call()
    return code, out.getvalue()


@pytest.fixture(scope="module")
def traced(tree):
    """One traced run of the cell through ``run.main``, at a seed over 2**31: ``(exit code, result line, all
    it said, the step recompiles the process had counted before it)``."""
    from chipbench import program_registry, run

    # the registry is the process's: other tests of this worker may have recompiled a step, so compare with what was there
    before = program_registry._entry("counters", "step.recompiles") or 0.0
    code, out = said_by(lambda: run.main(
        ["--workload", "tiny-sambay", "--seed", str(2**31 + 11), "--seconds", "0.3", "--trace", "1"],
        require_chip=False, root=tree, bench=tree / "chipbench",
    ))
    return code, json.loads(out.strip().splitlines()[-1]), out, before


@pytest.fixture(scope="module")
def readings(tree):
    """One run of the readings tool, one seed, float8 and the six changed pieces: ``(the seed's line, the
    summary, every side's numbers as --raw keeps them)``."""
    from chipbench import readings_sambay_lm

    code, out = said_by(lambda: readings_sambay_lm.main(
        ["--workload", "tiny-sambay", "--seeds", "5", "--raw", str(tree / "raw.json")], require_chip=False, root=tree,
    ))
    assert code == 0
    seed_line, summary = (json.loads(l) for l in out.splitlines() if l.startswith("{"))
    return seed_line, summary, json.loads((tree / "raw.json").read_text())


def test_the_cell_runs_end_to_end_and_is_correct(traced):
    code, line, out, _ = traced
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    rows = [l.split()[2] for l in out.splitlines() if l.startswith("[chipbench] correct: ") and "_gap" in l]
    assert rows == ["loss_gap.step1", "loss_gap.step2", "loss_gap.step3", "grad_norm_gap", "update_norm_gap"]   # three limits
    # no experts: nothing routed, nothing dropped; the scan was traced at the chunk that holds a row of 48
    assert "assignments of held experts dropped = 0" in out and "gauge sscan.chunk = 48.0" in out
    assert "(bound 0)" in next(l for l in out.splitlines() if "routing:" in l)


def test_a_traced_run_reports_the_programs_gauge_and_no_reader_raises(traced):
    code, line, _, recompiles_before = traced
    assert code == 0 and line["correct"] is True
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles", "step_recompiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert line["metrics"]["step_recompiles"]["value"] - recompiles_before == 0.0
    # the band's waste needs no device; device-trace and chip-only readers return nothing on the CPU
    assert set(SAMBAY_METRICS) & set(line["metrics"]) == {"phi4_band_tile_waste"}
    # a row of 48 under a window of 8 in one tile: the whole triangle visited, 48^2 over 8 * 9 / 2 + 40 * 8
    assert line["metrics"]["phi4_band_tile_waste"]["value"] == pytest.approx(48 * 48 / 356)


def test_the_readings_tool_reads_the_program_and_what_the_comparison_leaves_out(readings, tree):
    """``readings_sambay_lm`` at the toy cell: the program fails none of the
    configuration file's limits, and the two pieces the comparison leaves out
    are read beside the rows."""
    from chipbench import readings_sambay_lm
    from chipbench.runners import train_sambay_lm

    seed_line, summary, _ = readings
    assert seed_line["verdict"]["program"] == [] and "params" in seed_line["worst_leaf"] and "lambda" not in seed_line["worst_leaf"]
    assert set(seed_line["program"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap", "lambda_grad_gap", "key_bias_grad"}
    # the key's bias takes no gradient: rounding on both sides, far under the line that tells it from a leaf with one
    assert seed_line["reference_key_bias_grad"] < train_sambay_lm.NOUGHT / 10
    assert seed_line["program"]["key_bias_grad"] < train_sambay_lm.NOUGHT / 10
    assert summary["reference_key_bias_grad_largest"] == seed_line["reference_key_bias_grad"]
    with pytest.raises(SystemExit, match="controls"):
        readings_sambay_lm.main(["--workload", "tiny-sambay", "--seeds", "5", "--controls", "float4"], require_chip=False, root=tree)


@pytest.mark.parametrize("control", ("float8",) + FAULTS)
def test_each_control_fails_the_cells_own_limits(readings, control):
    """float8 in the reference's place, and the reference with each of the six pieces changed."""
    seed_line, summary, _ = readings
    assert seed_line["verdict"][control]
    assert summary[f"{control}_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(readings):
    """The timed path's own numbers from the tool's run, with no leaf moved: ``update_norm_gap`` alone fails."""
    from chipbench.runners import train_sambay_lm

    raw = readings[2]
    program, reference = (raw["by_seed"]["5"][side] for side in ("program", "reference"))
    assert raw["leaves"] == train_sambay_lm.leaf_names(tiny_sambay_config())
    sides = dict(program, lambda_leaves=raw["lambda_leaves"])
    assert all(r["ok"] for r in train_sambay_lm.compare(sides, reference, LIMITS))
    frozen = dict(sides, update_norms=np.zeros(len(raw["leaves"])))
    assert [r["name"] for r in train_sambay_lm.compare(frozen, reference, LIMITS) if not r["ok"]] == ["update_norm_gap"]


def test_the_runner_is_the_shared_window_with_its_own_parts():
    from chipbench.runners import train_mla_lm, train_sambay_lm

    parts = train_sambay_lm.PARTS
    assert isinstance(parts, train_mla_lm.Parts)
    assert (parts.facts_key, parts.top_k_key) == ("sambay_lm", "num_experts_per_tok")
    config = real_config()
    cfg = train_sambay_lm.model_config(config)
    assert cfg.kinds == ("M", "S", "M*", "F", "G", "X") and cfg.held == (0, 1, 16, 17, 18, 19)
    assert (cfg.remat, str(cfg.dtype)) == (config["assumed"]["program"]["remat"], "bfloat16")
    assert (cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.mamba_d_conv, cfg.head_dim) == (5120, 16, 160, 4, 64)
    with pytest.raises(SystemExit, match="held"):
        train_sambay_lm.model_config({**config, "num_hidden_layers": 5})
    said = []
    assert train_sambay_lm.scan_was_traced(said.append) in (True, False) and "sscan.chunk" in said[0]
    assert train_sambay_lm.CONTROLS == ("bfloat16", "float8") + FAULTS
    assert parts.compare is train_sambay_lm.compare


def test_the_comparison_leaves_out_what_bfloat16_cannot_hold_by_rule_and_nothing_else():
    """Three limits, as the harness states them.  A key's bias (no gradient in
    the reference: rounding) is out of ``update_norm_gap`` and stays under
    ``grad_norm_gap``; the ``lambda`` vectors (one all but cancelled scalar a
    layer) are out of both; every other leaf is held as in every cell."""
    from chipbench import correct
    from chipbench.runners import train_sambay_lm

    config = real_config()
    names, leaves = train_sambay_lm.leaf_names(config), train_sambay_lm.lambda_leaves(config)
    assert len(names) == 96 and len(leaves) == 12 and all("lambda_" in names[i] for i in leaves)
    assert [names[i] for i in leaves[:4]] == [f"['params']['layers_1']['mixer']['lambda_{v}']" for v in ("k1", "k2", "q1", "q2")]
    keys = [i for i, n in enumerate(names) if n.endswith("['qkv_proj']['bias'][1]")]
    assert [names[i][:22] for i in keys] == ["['params']['layers_1']", "['params']['layers_3']"]      # S and F; X projects no key
    limits = config["limits"]
    assert set(limits) == set(correct.LIMIT_KEYS) and "limits_more" not in config
    grads, moved = np.full(96, 2e-3), np.full(96, 1e-4)
    grads[leaves], grads[keys] = 5e-4, 2e-10                 # under the median leaf, as on the chip; a key's bias: rounding
    reference = {"losses": [1.0] * 3, "grad_norms": grads, "update_norms": moved}

    def verdict(grad_norms=grads * 1.001, update_norms=moved * 1.001):
        program = {"losses": [1.0] * 3, "grad_norms": grad_norms, "update_norms": update_norms, "lambda_leaves": leaves}
        return {r["name"]: (round(r["value"], 6), r["ok"]) for r in train_sambay_lm.compare(program, reference, limits)}

    sound = verdict()
    assert sound["grad_norm_gap"] == (0.001, True) and sound["update_norm_gap"] == (0.001, True) and len(sound) == 5
    noisy, stepped = grads * 1.001, moved * 1.001
    noisy[leaves[:4]] = 5e-4 * 1.4                            # the window layer's scalar 40% off
    noisy[keys] = 2e-7                                        # bfloat16's rounding where float32's stood
    stepped[keys], stepped[leaves[4:8]] = 1.4e-4, 0.3e-4      # stepped by the noise's sign at the full rate; a sign the other way
    assert verdict(noisy, stepped) == sound
    for i, row in ((keys[0], "grad_norm_gap"), (0, "grad_norm_gap")):
        wrong = noisy.copy()
        wrong[i] = grads[i] + 2e-3 * 0.02                     # a key's bias that takes a gradient; any leaf 2% of the median off
        assert [name for name, (_, ok) in verdict(wrong, stepped).items() if not ok] == [row]
    wrong = stepped.copy()
    wrong[0] *= 1.3                                           # any leaf with a gradient, its change 30% off
    assert [name for name, (_, ok) in verdict(noisy, wrong).items() if not ok] == ["update_norm_gap"]


def test_each_keys_bias_is_a_leaf_of_its_own_on_both_sides(readings):
    """``weights_sambay_lm.key_bias_apart``: a ``qkv_proj``'s bias in three, at
    the same places in the program's column order and the published one; both
    sides' norms come in ``leaf_names``' order and the key's parts read nought."""
    from chipbench import weights_sambay_lm
    from chipbench.runners import train_sambay_lm

    config = tiny_sambay_config()
    names = train_sambay_lm.leaf_names(config)
    params = weights_sambay_lm.make_params(4, config)
    apart = weights_sambay_lm.key_bias_apart(params, config)
    paths = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(apart)]
    assert paths == names and weights_sambay_lm.bias_parts(config) == [32, 48]
    q, k, v = apart["params"]["layers_1"]["mixer"]["qkv_proj"]["bias"]
    assert (q.shape, k.shape, v.shape) == ((32,), (16,), (16,))
    assert apart["params"]["layers_5"]["mixer"]["q_proj"]["bias"].shape == (32,)          # X projects a query only
    keys = [i for i, n in enumerate(names) if n.endswith("['qkv_proj']['bias'][1]")]
    for side in ("program", "reference"):
        numbers = readings[2]["by_seed"]["5"][side]
        assert len(numbers["grad_norms"]) == len(numbers["update_norms"]) == len(names)
        assert np.all(np.asarray(numbers["grad_norms"])[keys] < 1e-6 * np.median(numbers["grad_norms"]))
    moved = np.asarray(weights_sambay_lm.moved_norms(params, 4, config))
    assert moved.shape == (len(names),) and np.all(moved == 0.0)


def test_the_weight_maker_hands_the_reference_the_programs_weights_in_the_published_order():
    """The program keeps each group's columns together; the published order
    pairs heads ``2p, 2p + 1``.  The permutation is the model's own, column
    for column, and ``published_order`` undoes it on the kernel and the bias."""
    from adapcc_tpu.models import phi4_flash
    from chipbench import weights_sambay_lm

    for cross in (False, True):
        assert np.array_equal(phi4_flash.grouped_columns(40, 20, 64, cross), weights_sambay_lm.grouped_columns(40, 20, 64, cross))
    cols = weights_sambay_lm.grouped_columns(4, 2, 8)
    # q1 = published query heads 0 and 2, q2 = heads 1 and 3; k1 = K/V head 0, k2 = head 1; the values as published
    assert cols.tolist() == [*range(0, 8), *range(16, 24), *range(8, 16), *range(24, 32), *range(32, 40), *range(40, 48), *range(48, 64)]
    assert sorted(cols.tolist()) == list(range(64))
    config = tiny_sambay_config()
    params = weights_sambay_lm.make_params(4, config)
    there = weights_sambay_lm.published_order(params, config)
    ours, theirs = params["params"]["layers_1"]["mixer"]["qkv_proj"], there["params"]["layers_1"]["mixer"]["qkv_proj"]
    assert np.array_equal(np.asarray(theirs["kernel"])[:, cols], np.asarray(ours["kernel"]))
    assert np.array_equal(np.asarray(theirs["bias"])[cols], np.asarray(ours["bias"]))
    cross = weights_sambay_lm.grouped_columns(4, 2, 8, cross=True)
    ours, theirs = params["params"]["layers_5"]["mixer"]["q_proj"], there["params"]["layers_5"]["mixer"]["q_proj"]
    assert np.array_equal(np.asarray(theirs["kernel"])[:, cross], np.asarray(ours["kernel"]))
    assert there["params"]["layers_0"] is params["params"]["layers_0"]                # the other layers as they are
    a_log = np.asarray(params["params"]["layers_0"]["mixer"]["A_log"])
    assert a_log.shape == (64, 4) and np.allclose(np.exp(a_log), np.arange(1, 5))      # log(1 .. N) along the state axis


def test_the_manifest_names_the_configuration_the_cell_and_the_six_metrics():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == "phi4-mini-flash-vp8")
    assert config["file"] == "chipbench/configs/phi4-mini-flash-vp8.json" and config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi4-mini-flash-vp8", "packed8192-b1-sambay", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    added = [m for m in manifest["per_layer"] if m["name"] in SAMBAY_METRICS]
    assert tuple(m["name"] for m in added) == SAMBAY_METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    # no accepted metric's list of cells gained this one: their readers find nothing to read in it
    assert all(CELL not in m.get("workloads", []) or m in added for m in manifest["per_layer"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 and len(manifest["workloads"]) >= 7


def test_the_state_space_cells_entries_are_what_its_own_test_holds_by_name():
    """``test_chipbench_ssm_lm``'s manifest test finds cell 6 as the *last* of
    *six* cells and its metrics as the last five: it fails on any manifest
    with a cell after it, and that file is the benchmark's, not this PR's to
    edit (CHANGES.md, PR 41, asks a ``benchmark`` PR for the repair).  Every
    assertion of it, by name and not from the end."""
    SSM_CELL = "granite-h-micro-vp8-train"
    SSM_METRICS = ("granite_train_mfu", "ssd_time_share", "ssd_roofline", "granite_attn_time_share", "ssd_decay_floor")
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == "granite-h-micro-vp8")
    assert config["file"] == "chipbench/configs/granite-h-micro-vp8.json" and config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    # where the accepted test left them: the sixth configuration and the sixth cell, nothing put before or between
    assert manifest["configs"][5] is config and manifest["workloads"][5]["name"] == SSM_CELL
    cell = manifest["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-h-micro-vp8", "packed8192-b1-ssm", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(SSM_METRICS[0])
    added = manifest["per_layer"][first:first + 5]
    assert tuple(m["name"] for m in added) == SSM_METRICS and names[first + 5] == SAMBAY_METRICS[0]
    for m in added:
        assert m["workloads"] == [SSM_CELL] and m["moves"] == "train_tokens_per_s"
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    # no accepted metric's list of cells gained that one, nor this PR's: their readers find nothing to read in either
    for name in (SSM_CELL, CELL):
        assert all(m["workloads"] == [name] for m in manifest["per_layer"] if name in m.get("workloads", []))
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [w["name"] for w in manifest["workloads"]][:6] == [
        "gpt2-small-train", "gpt2-medium-ddp4", "trinity-mini-ep8-train", "kimi-linear-ep32-train", "joyai-flash-ep16-train",
        SSM_CELL,
    ]


def test_the_configuration_and_the_mix_are_the_published_widths_and_the_issues_traffic():
    cfg, mix = real_config(), real_mix()
    from pathlib import Path

    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = next(
        (json.loads(l) for l in rows.read_text().splitlines() if '"name": "Phi-4-mini-flash-reasoning"' in l), None
    ) if rows.is_file() else None
    if catalog is not None:                                # every published key unchanged but the two reduced
        differs = {k for k, v in catalog["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "vocab_size"} and cfg["source"] == catalog["source_url"]
    published = {
        "hidden_size": 2560, "intermediate_size": 10240, "num_attention_heads": 40, "num_key_value_heads": 20,
        "sliding_window": 512, "mb_per_layer": 2, "layer_norm_eps": 1e-05, "tie_word_embeddings": True,
        "hidden_act": "silu", "mlp_bias": False, "lm_head_bias": False, "model_type": "phi4flash",
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["assumed"]["mamba"] == {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["layers_held"]) == (6, 25008, [0, 1, 16, 17, 18, 19])
    assert cfg["published"]["num_hidden_layers"] == 32 and cfg["published"]["vocab_size"] == 200064 == 8 * 25008
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert "697,094,272" in cfg["deployment"]
    assert cfg["assumed"]["optimizer"]["learning_rate"] == 1e-6 and cfg["assumed"]["program"]["donate_state"] is True
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"} and "seeds" in cfg["limits_note"]
    assert "limits_more" not in cfg                              # the harness's three keys and no other limit anywhere
    assert {k: mix[k] for k in ("runner", "seq_len", "walks_per_row", "batch_per_chip", "corpus_rows", "branching",
                                "prefetch", "steps_per_sample")} == {
        "runner": "train_sambay_lm", "seq_len": 1024, "walks_per_row": 8, "batch_per_chip": 1, "corpus_rows": 2048,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


def test_the_arithmetic_counts_the_parameters_the_recurrence_and_the_products_by_hand():
    cfg = real_config()
    mlp, norms = 3 * 2560 * 10240, 4 * 2560
    scan = 2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    own = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    unit = 2 * 2560 * 5120
    per_layer = {"M": scan + mlp + norms, "S": own + mlp + norms, "G": unit + mlp + norms, "X": cross + mlp + norms}
    assert per_layer == {"M": 119_895_040, "S": 98_322_304, "G": 104_867_840, "X": 91_766_144}     # ISSUE 41, leaf by leaf
    layers = 2 * per_layer["M"] + 2 * per_layer["S"] + per_layer["G"] + per_layer["X"]
    assert layers == 633_068_672
    assert arithmetic_sambay_lm.parameter_count(cfg) == layers + 25008 * 2560 + 2 * 2560 == 697_094_272
    assert arithmetic_sambay_lm.recurrence_flops_per_token_layer(cfg) == 6 * 5120 * 16 == 491_520
    # the band: 512 * 513 / 2 + 7,680 * 512 keys; the triangle: 8,192 * 8,193 / 2
    assert arithmetic_sambay_lm.keys_seen(8192, "S", 512) == 4_063_488
    assert arithmetic_sambay_lm.keys_seen(8192, "F", 512) == arithmetic_sambay_lm.keys_seen(8192, "X", 512) == 33_558_528
    # a pair a key: two score maps at 64 and two products against 128 forward; each softmax's five products backward
    assert arithmetic_sambay_lm.pair_flops_per_key(cfg) == {"fwd": 2 * 2 * (64 + 128), "bwd": 2 * 2 * (3 * 64 + 2 * 128)}
    parts = arithmetic_sambay_lm.forward_flops_per_token(cfg, 8192)
    millions = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert millions == {
        "scan_projections": 164.5, "scan_recurrence": 1.0, "memory_units": 52.4, "attention_projections": 104.9,
        "attention_products": 133.5, "mlp": 943.7, "head": 128.0,
    }
    # six a matrix parameter met: everything but the norms, the biases, the scans' small leaves, lambda and the embedding's lookup
    matrices = 2 * (scan - 4 * 5120 - 3 * 5120 - 5120 * 16) + 2 * (2560 * 5120 + 2560 * 2560) + unit + 2 * 2560 * 2560 + 6 * mlp
    assert sum(v for k, v in parts.items() if k.endswith(("projections", "units", "mlp"))) == 2 * matrices
    total = arithmetic_sambay_lm.train_flops_per_token(cfg, 8192)
    assert 37.5e12 < total * 8192 < 37.6e12            # ISSUE 41 reckoned 37.6 TFLOP a step with the band at 512 keys a query
    flops, nbytes = arithmetic_sambay_lm.sscan_flops(1, cfg, 8192), arithmetic_sambay_lm.sscan_bytes(1, cfg, 8192)
    assert flops["fwd"] == 8192 * 491_520 and flops["bwd"] == 2 * flops["fwd"]
    assert nbytes["fwd"] == 8192 * (3 * 5120 + 2 * 16) * 2 and nbytes["bwd"] == 8192 * (5 * 5120 + 4 * 16) * 2
    # bytes bind by two orders: 0.31 ms a layer forward against 20 microseconds of FLOPs at the MXU's peak
    assert 0.30e-3 < nbytes["fwd"] / 819e9 < 0.32e-3 and flops["fwd"] / 197e12 < 0.03e-3
    band, full = (arithmetic_sambay_lm.diff_attention_flops(1, cfg, 8192, kind) for kind in ("S", "F"))
    assert band["fwd"] == 20 * 768 * 4_063_488 and full["bwd"] == 20 * 1792 * 33_558_528
    moved = arithmetic_sambay_lm.diff_attention_bytes(1, cfg, 8192)
    assert moved["fwd"] == 8192 * (5120 + 5120) * 2 and moved["bwd"] == 2 * moved["fwd"]
    # FLOPs bind in both: the triangle's 2.6 ms forward against 0.2 ms of bytes, the band's 0.32 ms by half as much again
    assert full["fwd"] / 197e12 > band["fwd"] / 197e12 > moved["fwd"] / 819e9 > band["fwd"] / 197e12 / 2


def test_the_readers_read_a_recorded_steps_kernels_by_name_and_nothing_without_a_trace():
    """``chipbench/fixtures/sambay_step_kernels.json``: the kernels'
    instructions as the TPU compiler names them in the cell's step, with
    durations a chip's trace read.  By name the five are told apart."""
    from chipbench import run, trace_reduce
    from chipbench.runners import train_sambay_lm

    trace = trace_reduce.load_json(str(ROOT / "chipbench/fixtures/sambay_step_kernels.json"))
    seconds = train_sambay_lm.kernel_seconds(trace)
    assert set(seconds) == {"sscan_fwd", "sscan_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert all(v > 0 for v in seconds.values())
    said = []
    reduced = {}
    train_sambay_lm.reduce_trace(trace, reduced, 1, said.append)
    assert reduced["sambay_kernel_s"] == seconds and any("sscan_bwd" in line for line in said)

    facts = {
        "config": real_config(), "mix": real_mix(), "world": 1, "steps": 10, "platform": "tpu",
        "device_kind": "TPU v5 lite", "tokens_per_s": 16000.0, "sambay_lm": {"assignments_per_layer_step": 0.0},
        "trace": {"window_s": 5.0, "sambay_kernel_s": {
            "sscan_fwd": 0.12, "sscan_bwd": 0.13, "flash_fwd": 0.30, "flash_bwd_dq": 0.25, "flash_bwd_dkv": 0.35,
        }},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in SAMBAY_METRICS}
    got = {name: read[name](facts) for name in SAMBAY_METRICS[:5]}
    assert got["sscan_time_share"] == pytest.approx(100 * 0.25 / 5.0)
    assert got["phi4_attn_time_share"] == pytest.approx(100 * 0.90 / 5.0)
    assert all(0 < v < 100 for v in got.values()), got
    # two scan layers, ten steps: bytes bind both ways
    need = 10 * 2 * (8192 * (3 * 5120 + 32) * 2 + 8192 * (5 * 5120 + 64) * 2) / 819e9
    assert got["sscan_roofline"] == pytest.approx(100 * need / 0.25)
    # FLOPs bind in the band and in the two triangles
    pairs = 20
    keys = 2 * 33_558_528 + 4_063_488
    assert got["phi4_attn_roofline"] == pytest.approx(100 * 10 * pairs * keys * (768 + 1792) / 197e12 / 0.90)
    assert 30 < got["phi4_train_mfu"] < 40
    bare = dict(facts, trace=None, platform="cpu")
    assert all(read[name](bare) is None for name in SAMBAY_METRICS[:5])
    assert all(read[name](dict(facts, trace={"window_s": 5.0})) is None for name in SAMBAY_METRICS[1:5])
    other_runner = {k: v for k, v in facts.items() if k != "sambay_lm"}
    assert all(read[name](other_runner) is None for name in ("phi4_train_mfu", "phi4_band_tile_waste"))
