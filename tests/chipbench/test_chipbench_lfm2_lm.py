"""The ``train_lfm2_lm`` runner at toy widths on the virtual CPU devices: one
traced run of the cell through ``run.main`` and one run of the readings tool
with float8 and the ten changed pieces of the mathematics, each computed once
for the module (PERF.md section 7 item 27); the configuration, mix and metric
files the manifest names, as ISSUE 45 states them, found by name; the readers
on a recorded step's kernel names, each event counted once; the arithmetic
against hand counts."""

import json

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT, make_tree

from chipbench import arithmetic_lfm2_lm

LFM2_METRICS = (
    "lfm2_train_mfu", "gconv_time_share", "gconv_roofline", "lfm2_attn_time_share", "lfm2_expert_time_share",
    "lfm2_moe_load_max_over_mean", "lfm2_moe_short_rows_share",
)
FAULTS = (
    "no_in_gate", "no_out_gate", "silu_on_conv", "taps_late", "gates_swapped", "softmax_router", "no_topk_norm",
    "rope_before_norm", "no_qk_norm", "untied_head",
)
# float32 activations on the CPU: sound runs read 1e-7 to 2e-6, each control 2e-2 or more on the gradient
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}
CELL = "lfm2-24b-a2b-ep4-train"
CONFIG = "lfm2-24b-a2b-ep4"


def real_config() -> dict:
    return json.loads((ROOT / f"chipbench/configs/{CONFIG}.json").read_text())


def real_mix() -> dict:
    return json.loads((ROOT / "chipbench/traffic/packed8192-b1-lfm2.json").read_text())


def tiny_lfm2_config() -> dict:
    """Eight published layers of which three are held, every kind of layer
    once (a convolution over the dense MLP, attention over the experts, a
    convolution over the experts), 8 experts top-2 of which 4 are held."""
    real = real_config()
    real.update(
        name="tiny-lfm2", vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8, num_experts_per_tok=2, num_experts_held=4,
        layer_types=real["layer_types"][:8], layers_held=[1, 2, 3], num_hidden_layers=3, limits=dict(LIMITS),
        published={"num_hidden_layers": 8, "num_dense_layers": 2, "num_experts": 8, "vocab_size": 256},
    )
    real["assumed"]["head_dim"] = 8
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_lfm2_mix() -> dict:
    return {
        "runner": "train_lfm2_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_lfm2"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-lfm2.json").write_text(json.dumps(tiny_lfm2_config()))
    (tmp / "chipbench/traffic/tiny-lfm2-b2.json").write_text(json.dumps(tiny_lfm2_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-lfm2", "source": "test", "file": "chipbench/configs/tiny-lfm2.json",
        "reduced": tiny_lfm2_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-lfm2", "config": "tiny-lfm2", "traffic": "tiny-lfm2-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in LFM2_METRICS:
            m["workloads"] = ["tiny-lfm2"]
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
        ["--workload", "tiny-lfm2", "--seed", str(2**31 + 11), "--seconds", "0.3", "--trace", "1"],
        require_chip=False, root=tree, bench=tree / "chipbench",
    ))
    return code, json.loads(out.strip().splitlines()[-1]), out, before


@pytest.fixture(scope="module")
def readings(tree):
    """One run of the readings tool, one seed, float8 and the ten changed pieces: ``(the seed's line, the
    summary, every side's numbers as --raw keeps them)``."""
    from chipbench import readings_lfm2_lm

    code, out = said_by(lambda: readings_lfm2_lm.main(
        ["--workload", "tiny-lfm2", "--seeds", "5", "--raw", str(tree / "raw.json")], require_chip=False, root=tree,
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
    # four of eight experts held under top-2: a token lands here twice at most (the bound), once on balance
    assert "assignments of held experts dropped = 0" in out and "(bound 192)" in next(l for l in out.splitlines() if "routing:" in l)
    said = next(l for l in out.splitlines() if "conv.gated_calls" in l)
    assert "conv.gated_calls = 2.0  wanted 2 a traced step" in said and "gconv.block_rows = 64.0" in said


def test_a_traced_run_reports_the_programs_samples_and_no_reader_raises(traced):
    code, line, _, recompiles_before = traced
    assert code == 0 and line["correct"] is True
    unlisted = {"input_wait_ms", "step_dispatch_ms", "window_compiles", "step_trace_lower_s", "step_load_s",
                "step_cache_misses", "step_recompiles"}
    assert unlisted <= set(line["metrics"])                  # the seven readers without a list of cells read this one
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert line["metrics"]["step_recompiles"]["value"] - recompiles_before == 0.0
    # the routing's two samples need no device; device-trace and chip-only readers return nothing on the CPU
    assert set(LFM2_METRICS) & set(line["metrics"]) == {"lfm2_moe_load_max_over_mean", "lfm2_moe_short_rows_share"}
    assert line["metrics"]["lfm2_moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= line["metrics"]["lfm2_moe_short_rows_share"]["value"] <= 100.0


def test_the_readings_tool_reads_the_program_by_the_files_own_limits(readings, tree):
    from chipbench import readings_lfm2_lm

    seed_line, summary, raw = readings
    assert seed_line["verdict"]["program"] == [] and "params" in seed_line["worst_leaf"]
    assert set(seed_line["program"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert summary["sound_largest"] == seed_line["program"]
    assert set(raw["by_seed"]["5"]) == {"program", "reference", "float8", *FAULTS}
    with pytest.raises(SystemExit, match="controls"):
        readings_lfm2_lm.main(["--workload", "tiny-lfm2", "--seeds", "5", "--controls", "float4"], require_chip=False, root=tree)


@pytest.mark.parametrize("control", ("float8",) + FAULTS)
def test_each_control_fails_the_cells_own_limits(readings, control):
    """float8 in the reference's place, and the reference with each of the ten pieces changed."""
    seed_line, summary, _ = readings
    assert seed_line["verdict"][control]
    assert summary[f"{control}_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(readings):
    """The timed path's own numbers from the tool's run, with no leaf moved: ``update_norm_gap`` alone fails."""
    from chipbench import correct, weights_lfm2_lm

    raw = readings[2]
    program, reference = (raw["by_seed"]["5"][side] for side in ("program", "reference"))
    assert raw["leaves"] == weights_lfm2_lm.leaf_names(tiny_lfm2_config())
    assert all(r["ok"] for r in correct.compare(program, reference, LIMITS))
    frozen = dict(program, update_norms=np.zeros(len(raw["leaves"])))
    assert [r["name"] for r in correct.compare(frozen, reference, LIMITS) if not r["ok"]] == ["update_norm_gap"]


def test_the_runner_is_the_shared_window_with_its_own_parts():
    from chipbench import correct
    from chipbench.runners import train_lfm2_lm, train_mla_lm, train_moe_lm

    config = real_config()
    parts = train_lfm2_lm.parts_for(config, 8192)
    assert isinstance(parts, train_mla_lm.Parts)
    assert (parts.facts_key, parts.top_k_key) == ("lfm2_lm", "num_experts_per_tok")
    assert parts.compare is correct.compare and parts.recording is train_moe_lm.Recording
    assert train_lfm2_lm.conv_layers(config) == 4
    assert train_lfm2_lm.CONTROLS == ("bfloat16", "float8") + FAULTS
    said = []
    assert parts.also_correct(said.append) in (True, False) and "conv.gated_calls" in said[0]
    assert train_lfm2_lm.kernel_of('%gated_conv_bwd.3 = (bf16[1]) custom-call(%a), custom_call_target="tpu_custom_call"') == "gated_conv_bwd"
    assert train_lfm2_lm.kernel_of('%short_conv_bwd.3 = (bf16[1]) custom-call(%a), custom_call_target="tpu_custom_call"') is None


def test_the_weight_maker_counts_the_cells_parameters_and_nothing_moves_at_the_start():
    from chipbench import weights_lfm2_lm

    config = tiny_lfm2_config()
    params = weights_lfm2_lm.make_params(4, config)
    names = weights_lfm2_lm.leaf_names(config)
    assert [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(params)] == names
    moved = np.asarray(weights_lfm2_lm.moved_norms(params, 4, config))
    assert moved.shape == (len(names),) and np.all(moved == 0.0)
    taps = np.asarray(params["params"]["layers_0"]["conv"]["conv_taps"])
    assert taps.shape == (3, 32) and np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.4
    assert not np.asarray(params["params"]["layers_1"]["feed_forward"]["expert_bias"]).any()
    assert weights_lfm2_lm.layer_plan(real_config()) == (
        ("conv", False), ("full_attention", True), ("conv", True), ("conv", True), ("conv", True),
    )


def test_the_manifest_names_the_configuration_the_cell_and_the_seven_metrics():
    """Found by name, never from the end or by a count: a later PR's entries
    after these change nothing here."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts_held", "vocab_size"]
    assert config["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "packed8192-b1-lfm2", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    added = [m for m in manifest["per_layer"] if m["name"] in LFM2_METRICS]
    assert tuple(m["name"] for m in added) == LFM2_METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    assert {m["name"]: m["layer"] for m in added} == {
        "lfm2_train_mfu": "model step", "gconv_time_share": "Pallas kernels", "gconv_roofline": "Pallas kernels",
        "lfm2_attn_time_share": "Pallas kernels", "lfm2_expert_time_share": "expert layer",
        "lfm2_moe_load_max_over_mean": "expert layer", "lfm2_moe_short_rows_share": "expert layer",
    }
    # no accepted metric's list of cells gained this one: their readers find nothing to read in it
    assert all(CELL not in m.get("workloads", []) or m in added for m in manifest["per_layer"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 and len(manifest["workloads"]) >= 8
    assert [w["name"] for w in manifest["workloads"]].index(CELL) >= 7              # appended: nothing put before what was there


def test_the_configuration_and_the_mix_are_the_published_widths_and_the_issues_traffic():
    cfg, mix = real_config(), real_mix()
    published = {
        "hidden_size": 2048, "intermediate_size": 11776, "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts": 64, "num_experts_per_tok": 4, "conv_L_cache": 3, "conv_bias": False,
        "norm_eps": 1e-05, "norm_topk_prob": True, "routed_scaling_factor": 1, "use_expert_bias": True,
        "model_type": "lfm2_moe", "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    }
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 40 and [i for i, k in enumerate(cfg["layer_types"]) if k == "full_attention"] == list(range(2, 40, 4))
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"], cfg["num_experts_held"], cfg["vocab_size"]) == (5, 1, 16, 16384)
    assert cfg["layers_held"] == [1, 2, 3, 4, 5]
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["num_dense_layers"] == 2
    assert cfg["published"]["num_experts"] == 64 == 4 * cfg["num_experts_held"] and cfg["published"]["vocab_size"] == 65536 == 4 * 16384
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts_held", "vocab_size"]
    assert "788,052,352" in cfg["deployment"] and "four-chip" in cfg["deployment"]
    assumed = cfg["assumed"]
    assert assumed["head_dim"] == 64 and assumed["tie_word_embeddings"] is True
    assert {"conv", "full_attention", "experts", "stream", "output", "packing"} <= set(assumed["not_in_config_json"])
    assert assumed["optimizer"]["learning_rate"] == 1e-6 and assumed["program"]["donate_state"] is True
    assert assumed["program"]["activations"] == "bfloat16" and assumed["program"]["loss"] in ("dense", "chunked")
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"} and "seeds" in cfg["limits_note"]
    assert "limits_more" not in cfg                              # the harness's three keys and no other limit anywhere
    assert {k: mix[k] for k in ("runner", "seq_len", "walks_per_row", "batch_per_chip", "corpus_rows", "branching",
                                "prefetch", "steps_per_sample")} == {
        "runner": "train_lfm2_lm", "seq_len": 1024, "walks_per_row": 8, "batch_per_chip": 1, "corpus_rows": 2048,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


def test_the_arithmetic_counts_the_parameters_the_convolution_and_the_products_by_hand():
    cfg = real_config()
    d = 2048
    conv = d * 3 * d + 3 * d + d * d
    attention = 2 * d * d + 2 * d * 512 + 2 * 64
    dense, expert = 3 * d * 11776, 3 * d * 1536
    experts = 16 * expert + d * 64 + 64
    norms = 2 * d
    assert (conv, attention, dense, expert) == (16_783_360, 10_485_888, 72_351_744, 9_437_184)           # ISSUE 45, leaf by leaf
    layers = (conv + dense + norms) + (attention + experts + norms) + 3 * (conv + experts + norms)
    assert conv + dense + norms == 89_139_200 and attention + experts + norms == 161_616_064 and conv + experts + norms == 167_913_536
    assert arithmetic_lfm2_lm.parameter_count(cfg) == layers + 16384 * d + d == 788_052_352
    parts = arithmetic_lfm2_lm.forward_flops_per_token(cfg, 8192, 1.0)
    millions = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert millions == {
        "conv_projections": 134.2, "attention_projections": 21.0, "attention_products": 33.6, "dense_mlp": 144.7,
        "router": 1.0, "routed_experts": 75.5, "head": 67.1,
    }
    # six a matrix parameter met: the mixers' and the dense MLP's matrices, the router, an expert for each assignment
    matrices = 4 * (conv - 3 * d) + (attention - 128) + dense + 4 * d * 64 + 4 * expert
    assert sum(v for k, v in parts.items() if k not in ("attention_products", "head")) == 2 * matrices
    assert parts["attention_products"] == 32 * 4 * 64 * 8193 / 2 and parts["head"] == 2 * d * 16384 * 8191 / 8192
    total = arithmetic_lfm2_lm.train_flops_per_token(cfg, 8192, 1.0)
    assert 11.7e12 < total * 8192 < 11.75e12            # ISSUE 45 reckoned 11.7 TFLOP a row, 59.5 ms at the peak
    assert arithmetic_lfm2_lm.train_flops_per_token(cfg, 8192, 2.0) - total == 3 * 4 * 6 * d * 1536
    flops, nbytes = arithmetic_lfm2_lm.gconv_flops(1, cfg, 8192), arithmetic_lfm2_lm.gconv_bytes(1, cfg, 8192)
    assert flops == {"fwd": 8192 * d * 8, "bwd": 8192 * d * 16}
    assert nbytes == {"fwd": 8192 * 4 * d * 2, "bwd": 8192 * 7 * d * 2}
    # bytes bind by two orders: 0.16 ms a layer forward and 0.29 backward against a microsecond of FLOPs at the MXU's peak
    assert 0.16e-3 < nbytes["fwd"] / 819e9 < 0.17e-3 and 0.28e-3 < nbytes["bwd"] / 819e9 < 0.29e-3
    assert flops["bwd"] / 197e12 < 0.002e-3


def test_the_readers_read_a_recorded_steps_kernels_by_name_each_event_once_and_nothing_without_a_trace():
    """``chipbench/fixtures/lfm2_step_kernels.json``: the kernels' and the
    expert layer's instructions as the TPU compiler names them in the cell's
    step, with durations a chip's trace read.  By name the five kernels are
    told apart; a ``conditional`` that spans its children is left out of the
    expert layers' time, which ``trace_moe_lm.expert_seconds`` counts twice."""
    from chipbench import run, trace_lfm2_lm, trace_moe_lm, trace_reduce
    from chipbench.runners import train_lfm2_lm

    trace = trace_reduce.load_json(str(ROOT / "chipbench/fixtures/lfm2_step_kernels.json"))
    seconds = train_lfm2_lm.kernel_seconds(trace)
    assert set(seconds) == {"gated_conv_fwd", "gated_conv_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert all(v > 0 for v in seconds.values())
    config = real_config()
    once = trace_lfm2_lm.expert_seconds(trace, config, 8192)
    twice = trace_moe_lm.expert_seconds(trace, config, 8192)
    events = [e for evs in trace_reduce.device_ops(trace).values() for e in evs]
    parents = [e for e in events if e not in trace_lfm2_lm.leaves(events)]
    assert len(parents) == 1 and parents[0][0].startswith("%conditional")
    assert once["grouped_products"] > 0 and set(once) == {"grouped_products", "rows"}
    assert sum(twice.values()) - sum(once.values()) == pytest.approx(parents[0][2] / 1e9)
    said, reduced = [], {}
    train_lfm2_lm.reduce_trace_for(config, 8192)(trace, reduced, 1, said.append)
    assert reduced["lfm2_kernel_s"] == seconds and reduced["lfm2_expert_s"] == once
    assert any("gated_conv_bwd" in line for line in said) and any("ragged-dot" in line for line in said)

    facts = {
        "config": config, "mix": real_mix(), "world": 1, "steps": 10, "platform": "tpu",
        "device_kind": "TPU v5 lite", "tokens_per_s": 41000.0, "lfm2_lm": {"assignments_per_layer_step": 8192.0},
        "trace": {"window_s": 2.0, "lfm2_kernel_s": {
            "gated_conv_fwd": 0.008, "gated_conv_bwd": 0.012, "flash_fwd": 0.04, "flash_bwd_dq": 0.05, "flash_bwd_dkv": 0.06,
        }, "lfm2_expert_s": {"grouped_products": 0.3, "rows": 0.1}},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in LFM2_METRICS}
    got = {name: read[name](facts) for name in LFM2_METRICS[:5]}
    assert got["gconv_time_share"] == pytest.approx(100 * 0.020 / 2.0)
    assert got["lfm2_attn_time_share"] == pytest.approx(100 * 0.15 / 2.0)
    assert got["lfm2_expert_time_share"] == pytest.approx(100 * 0.4 / 2.0)
    # four convolution layers, ten steps: bytes bind both ways, 11 arrays of 8,192 x 2,048 at two bytes
    need = 10 * 4 * 8192 * 11 * 2048 * 2 / 819e9
    assert got["gconv_roofline"] == pytest.approx(100 * need / 0.020) and got["gconv_roofline"] < 100
    assert got["lfm2_train_mfu"] == pytest.approx(100 * 41000 * arithmetic_lfm2_lm.train_flops_per_token(config, 8192, 1.0) / 197e12)
    assert 25 < got["lfm2_train_mfu"] < 35
    bare = dict(facts, trace=None, platform="cpu")
    assert all(read[name](bare) is None for name in LFM2_METRICS[:5])
    assert all(read[name](dict(facts, trace={"window_s": 2.0})) is None for name in LFM2_METRICS[1:5])
    other_runner = {k: v for k, v in facts.items() if k != "lfm2_lm"}
    assert all(read[name](other_runner) is None for name in ("lfm2_train_mfu", "lfm2_moe_load_max_over_mean", "lfm2_moe_short_rows_share"))
