"""The ``train_ssm_lm`` runner end to end at toy widths on the virtual CPU
devices, through ``run.main``; the configuration, mix and metric files the
manifest names; the controls (float8, the attention scale at ``1 /
sqrt(head)``, the norm before the gate, ``D`` left out) through the readings
tool, and two broken timed paths that must each come out not ``correct``: the
program's skip left out, a step that returns its state unchanged; the
readers on a recorded fixture; the arithmetic against hand counts."""

import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import ROOT, make_tree, run_cell

from chipbench import arithmetic_ssm_lm

SSM_METRICS = ("granite_train_mfu", "ssd_time_share", "ssd_roofline", "granite_attn_time_share", "ssd_decay_floor")
# float32 activations on the CPU: sound runs read 1e-7 to 2e-5, each control 5e-3 or more on the gradient
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}
CELL = "granite-h-micro-vp8-train"


def real_config() -> dict:
    return json.loads((ROOT / "chipbench/configs/granite-h-micro-vp8.json").read_text())


def real_mix() -> dict:
    return json.loads((ROOT / "chipbench/traffic/packed8192-b1-ssm.json").read_text())


def tiny_ssm_config() -> dict:
    real = real_config()
    real.update(
        name="tiny-ssm", vocab_size=256, hidden_size=32, shared_intermediate_size=64, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"], num_attention_heads=4, num_key_value_heads=2,
        attention_multiplier=4.0, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, limits=dict(LIMITS),
    )
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_ssm_mix() -> dict:
    return {
        "runner": "train_ssm_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_ssm"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-ssm.json").write_text(json.dumps(tiny_ssm_config()))
    (tmp / "chipbench/traffic/tiny-ssm-b2.json").write_text(json.dumps(tiny_ssm_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-ssm", "source": "test", "file": "chipbench/configs/tiny-ssm.json",
        "reduced": tiny_ssm_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-ssm", "config": "tiny-ssm", "traffic": "tiny-ssm-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in SSM_METRICS:
            m["workloads"] = ["tiny-ssm"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def test_the_cell_runs_end_to_end_and_is_correct(tree, capsys):
    code, line, out = run_cell(tree, "tiny-ssm", capsys, seed=2**31 + 11)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_step_p95_ms", "setup_s"}
    for name in ("loss_gap.step1", "loss_gap.step3", "grad_norm_gap", "update_norm_gap"):
        assert f"correct: {name} = " in out
    # no experts: nothing routed, nothing dropped; the scan was traced at the chunk that holds a row of 48
    assert "assignments of held experts dropped = 0" in out and "gauge ssd.chunk = 48.0" in out
    assert "(bound 0)" in next(l for l in out.splitlines() if "routing:" in l)


def test_a_traced_run_reports_the_programs_sample_and_no_reader_raises(tree, capsys):
    from chipbench import program_registry

    # the registry is the process's: other tests of this worker may have recompiled a step, so compare with what was there
    recompiles_before = program_registry._entry("counters", "step.recompiles") or 0.0
    floors_before = (program_registry._entry("samples", "ssd.decay_floor") or {"count": 0})["count"]
    code, line, _ = run_cell(tree, "tiny-ssm", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles", "step_recompiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert line["metrics"]["step_recompiles"]["value"] - recompiles_before == 0.0
    # the decay floor needs no device; device-trace and chip-only readers return nothing on the CPU
    assert set(SSM_METRICS) & set(line["metrics"]) == {"ssd_decay_floor"}
    assert 0.0 <= line["metrics"]["ssd_decay_floor"]["value"] < 1.0
    assert program_registry._entry("samples", "ssd.decay_floor")["count"] - floors_before == line["attempted"]
    # in a cell of another runner kind this kind's readers find nothing
    code, line, _ = run_cell(tree, "tiny-w1", capsys, trace=1)
    assert code == 0 and not set(SSM_METRICS) & set(line["metrics"])


def test_the_readings_tool_takes_each_control_through_the_cells_own_limits(tree, capsys):
    """``readings_ssm_lm`` at the toy cell: the program fails none of the
    configuration file's limits; the float8 control, the reference with the
    scores at ``1 / sqrt(head)``, with the norm before the gate and without
    ``D`` each fail one at least."""
    from chipbench import readings_ssm_lm

    capsys.readouterr()
    assert readings_ssm_lm.main(["--workload", "tiny-ssm", "--seeds", "5"], require_chip=False, root=tree) == 0
    seed_line, summary = (json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{"))
    assert seed_line["verdict"]["program"] == [] and "params" in seed_line["worst_leaf"]
    for control in ("float8", "sqrt_scale", "norm_before_gate", "no_skip"):
        assert seed_line["verdict"][control], control
        assert summary[f"{control}_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]
    with pytest.raises(SystemExit, match="controls"):
        readings_ssm_lm.main(["--workload", "tiny-ssm", "--seeds", "5", "--controls", "float4"], require_chip=False, root=tree)


def test_lower_precision_in_the_references_place_is_told_apart(tree):
    from chipbench import correct
    from chipbench.runners import train_ssm_lm

    config = tiny_ssm_config()
    rows = train_ssm_lm.packed_rows(tiny_ssm_mix(), config["vocab_size"], 3)[:6].reshape(3, 2, -1)
    sound = train_ssm_lm.reference_numbers(config, rows, 3)
    assert set(sound) == {"losses", "grad_norms", "update_norms"}
    gaps = {}
    for precision in ("bfloat16", "float8"):
        rows_cmp = correct.compare(train_ssm_lm.reference_numbers(config, rows, 3, precision), sound, LIMITS)
        gaps[precision] = {r["name"]: r["value"] for r in rows_cmp}
    assert gaps["float8"]["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert gaps["float8"]["grad_norm_gap"] > 4 * gaps["bfloat16"]["grad_norm_gap"] > 0
    assert len(gaps["float8"]) == 3 + 2          # the loss at each of three steps, two norms


def test_the_programs_skip_left_out_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ops import ssd as ssd_module

    real = ssd_module.ssd     # the mixer asks the module for it at each call
    monkeypatch.setattr(ssd_module, "ssd", lambda x, dt, A, B, C, D, **kw: real(x, dt, A, B, C, jnp.zeros_like(D), **kw))
    code, line, out = run_cell(tree, "tiny-ssm", capsys)
    assert code == 0 and line["correct"] is False
    assert any("grad_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def broken(self, state, batch, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state)     # the step donates what it is given
        new, loss = real(self, state, batch, *a, **kw)
        return kept.replace(model_state=new.model_state), loss

    monkeypatch.setattr(DDPTrainer, "step", broken)
    code, line, out = run_cell(tree, "tiny-ssm", capsys)
    assert code == 0 and line["correct"] is False
    assert any("update_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_the_runner_is_the_shared_window_with_its_own_parts():
    from chipbench.runners import train_mla_lm, train_ssm_lm

    parts = train_ssm_lm.PARTS
    assert isinstance(parts, train_mla_lm.Parts)
    assert (parts.facts_key, parts.top_k_key) == ("ssm_lm", "num_experts_per_tok")
    cfg = train_ssm_lm.model_config(real_config())
    assert (cfg.remat, cfg.kinds.count("mamba"), cfg.kinds[5], str(cfg.dtype)) == ("dots", 9, "attention", "bfloat16")
    said = []
    assert train_ssm_lm.scan_was_traced(said.append) in (True, False) and "ssd.chunk" in said[0]
    assert train_ssm_lm.CONTROLS == ("bfloat16", "float8", "sqrt_scale", "norm_before_gate", "no_skip")


def test_the_manifest_names_the_configuration_the_cell_and_the_five_metrics():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == "granite-h-micro-vp8")
    assert config["file"] == "chipbench/configs/granite-h-micro-vp8.json" and config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
    assert manifest["configs"][-1] is config and manifest["workloads"][-1]["name"] == CELL
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-h-micro-vp8", "packed8192-b1-ssm", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    added = manifest["per_layer"][-5:]
    assert tuple(m["name"] for m in added) == SSM_METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    # no accepted metric's list of cells gained this one: their readers find nothing to read in it
    assert all(CELL not in m.get("workloads", [CELL]) or m in added or "workloads" not in m for m in manifest["per_layer"])
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1 and len(manifest["workloads"]) == 6


def test_the_configuration_and_the_mix_are_the_published_widths_and_the_issues_traffic():
    cfg, mix = real_config(), real_mix()
    from pathlib import Path

    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    catalog = next(
        (json.loads(l) for l in rows.read_text().splitlines() if '"name": "granite-4.0-h-micro"' in l), None
    ) if rows.is_file() else None
    if catalog is not None:                                # every published key unchanged but the two reduced
        differs = {k for k, v in catalog["config"].items() if cfg.get(k) != v}
        assert differs == {"num_hidden_layers", "vocab_size"} and cfg["source"] == catalog["source_url"]
    published = {
        "hidden_size": 2048, "shared_intermediate_size": 8192, "num_attention_heads": 32, "num_key_value_heads": 8,
        "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 256, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
        "position_embedding_type": "nope", "num_local_experts": 0,
    }
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 40 and [i for i, k in enumerate(cfg["layer_types"]) if k == "attention"] == [5, 15, 25, 35]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["vocab_size"] == 100352 == 8 * 12544
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["assumed"]["optimizer"]["learning_rate"] == 1e-6 and cfg["assumed"]["program"]["donate_state"] is True
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"} and "seeds" in cfg["limits_note"]
    assert {k: mix[k] for k in ("runner", "seq_len", "walks_per_row", "batch_per_chip", "corpus_rows", "branching",
                                "prefetch", "steps_per_sample")} == {
        "runner": "train_ssm_lm", "seq_len": 1024, "walks_per_row": 8, "batch_per_chip": 1, "corpus_rows": 2048,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


def test_the_arithmetic_counts_the_parameters_and_the_recurrence_by_hand():
    cfg = real_config()
    mamba = 2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048 + 2 * 2048 + 3 * 2048 * 8192
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 2048 + 3 * 2048 * 8192
    assert (mamba, attention) == (76_182_976, 60_821_504)                       # ISSUE 39: 76.18 M, 60.82 M
    assert arithmetic_ssm_lm.parameter_count(cfg) == 9 * mamba + attention + 12544 * 2048 + 2048 == 772_160_448
    assert arithmetic_ssm_lm.recurrence_flops_per_token_layer(cfg) == 6 * 64 * 64 * 128 == 3_145_728
    parts = arithmetic_ssm_lm.forward_flops_per_token(cfg, 8192)
    millions = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert millions == {
        "ssm_projections": 464.8, "ssm_recurrence": 28.3, "attention_projections": 21.0, "attention_products": 33.6,
        "mlp": 1006.6, "head": 51.4,
    }
    total = arithmetic_ssm_lm.train_flops_per_token(cfg, 8192)
    assert 4.81e9 < total < 4.83e9 and 39.4e12 < total * 8192 < 39.5e12        # ISSUE 39: 4.82 GFLOP a token, 39.5 TFLOP a step
    flops, nbytes = arithmetic_ssm_lm.ssd_flops(1, cfg, 8192), arithmetic_ssm_lm.ssd_bytes(1, cfg, 8192)
    assert flops["fwd"] == 8192 * 3_145_728 and flops["bwd"] == 2 * flops["fwd"]
    assert nbytes["fwd"] == 8192 * (2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4)
    assert nbytes["bwd"] == 8192 * (3 * 4096 * 2 + 2 * (2 * 128 * 2 + 64 * 4))
    # at the table's peaks bytes bind forward, 0.17 ms a layer; backward FLOPs and bytes ask the same time within 0.2%
    assert 0.16e-3 < nbytes["fwd"] / 819e9 < 0.18e-3 and nbytes["fwd"] / 819e9 > flops["fwd"] / 197e12
    assert abs(nbytes["bwd"] / 819e9 / (flops["bwd"] / 197e12) - 1) < 0.002


def test_the_readers_read_a_recorded_steps_kernels_by_name_and_nothing_without_a_trace():
    """``chipbench/fixtures/granite_step_kernels.json``: the kernels'
    instructions as the TPU compiler wrote them for the cell's step, with the
    durations the chip's trace read.  By name the five are told apart; by
    signature alone ``ssd_bwd`` would pass for a flash kernel, which is why
    this runner kind reads names."""
    from chipbench import run, trace_hybrid_lm, trace_reduce
    from chipbench.runners import train_ssm_lm

    trace = trace_reduce.load_json(str(ROOT / "chipbench/fixtures/granite_step_kernels.json"))
    seconds = train_ssm_lm.kernel_seconds(trace)
    assert {k: round(v * 1e6) for k, v in seconds.items()} == {
        "ssd_fwd": 781, "ssd_bwd": 1018, "flash_fwd": 4449, "flash_bwd_dq": 5067, "flash_bwd_dkv": 6805,
    }
    by_signature = trace_hybrid_lm.kernel_seconds(trace)
    assert by_signature["flash_bwd_dkv"] > seconds["flash_bwd_dkv"] and by_signature["kda_fwd"] == 0.0
    said = []
    reduced = {}
    train_ssm_lm.reduce_trace(trace, reduced, 1, said.append)
    assert reduced["ssm_kernel_s"] == seconds and any("ssd_bwd" in line for line in said)
    assert any("fusion bf16[1,8192,8512]" in line for line in said)

    facts = {
        "config": real_config(), "mix": real_mix(), "world": 1, "steps": 10, "platform": "tpu",
        "device_kind": "TPU v5 lite", "tokens_per_s": 22000.0, "ssm_lm": {"assignments_per_layer_step": 0.0},
        "trace": {"window_s": 3.7, "ssm_kernel_s": {
            "ssd_fwd": 0.070, "ssd_bwd": 0.092, "flash_fwd": 0.089, "flash_bwd_dq": 0.051, "flash_bwd_dkv": 0.068,
        }},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in SSM_METRICS}
    got = {name: read[name](facts) for name in SSM_METRICS[:4]}
    assert got["ssd_time_share"] == pytest.approx(100 * 0.162 / 3.7)
    assert got["granite_attn_time_share"] == pytest.approx(100 * 0.208 / 3.7)
    assert all(0 < v < 100 for v in got.values()), got
    # nine layers, ten steps: bytes bind forward; backward the two bounds meet (0.2616 ms of FLOPs, 0.2612 ms of bytes)
    need = 10 * 9 * (140_509_184 / 819e9 + max(213_909_504 / 819e9, 2 * 8192 * 3_145_728 / 197e12))
    assert got["ssd_roofline"] == pytest.approx(100 * need / 0.162)
    assert 50 < got["granite_train_mfu"] < 56
    bare = dict(facts, trace=None, platform="cpu")
    assert all(read[name](bare) is None for name in SSM_METRICS[:4])
    assert all(read[name](dict(facts, trace={"window_s": 3.7})) is None for name in SSM_METRICS[1:4])
    other_runner = {k: v for k, v in facts.items() if k != "ssm_lm"}
    assert all(read[name](other_runner) is None for name in ("granite_train_mfu", "ssd_decay_floor"))
