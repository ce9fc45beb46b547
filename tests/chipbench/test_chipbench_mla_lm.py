"""The ``train_mla_lm`` runner end to end at toy widths on the virtual CPU
devices, through ``run.main``; the configuration, mix and metric files the
manifest names; the controls (float8, the rotation left out, the module's
loss term left out) through the readings tool, and three broken timed paths
that must each come out not ``correct``: the program's rotation left out, its
loss without the module's term, a step that returns its state unchanged."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import ROOT, make_tree, run_cell

from chipbench import arithmetic_mla_lm

MLA_METRICS = (
    "joyai_train_mfu", "joyai_mla_time_share", "joyai_mla_roofline", "joyai_moe_short_rows_share",
    "joyai_moe_load_max_over_mean",
)
# float32 activations on the CPU: sound runs read 1e-7 to 2e-5, each control 1e-2 or more on the gradient
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}


def real_config() -> dict:
    return json.loads((ROOT / "chipbench/configs/joyai-flash-ep16.json").read_text())


def real_mix() -> dict:
    return json.loads((ROOT / "chipbench/traffic/packed8192-b1-mtp.json").read_text())


def tiny_mla_config() -> dict:
    real = real_config()
    real.update(
        name="tiny-mla", vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8, num_experts_held=4, expert_offset=2,
        num_experts_per_tok=2, limits=dict(LIMITS),
    )
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_mla_mix() -> dict:
    return {
        "runner": "train_mla_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_mla"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-mla.json").write_text(json.dumps(tiny_mla_config()))
    (tmp / "chipbench/traffic/tiny-mla-b2.json").write_text(json.dumps(tiny_mla_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-mla", "source": "test", "file": "chipbench/configs/tiny-mla.json",
        "reduced": tiny_mla_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-mla", "config": "tiny-mla", "traffic": "tiny-mla-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in MLA_METRICS:
            m["workloads"] = ["tiny-mla"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def test_the_cell_runs_end_to_end_and_is_correct_in_the_loss_and_in_each_of_its_terms(tree, capsys):
    code, line, out = run_cell(tree, "tiny-mla", capsys, seed=2**31 + 11)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_step_p95_ms", "setup_s"}
    for name in (
        "loss_gap.step1", "loss_gap.step3", "loss_main_gap.step1", "loss_main_gap.step3", "loss_mtp_gap.step1",
        "loss_mtp_gap.step3", "grad_norm_gap", "update_norm_gap",
    ):
        assert f"correct: {name} = " in out
    assert "assignments of held experts dropped = 0" in out and "gauge mtp.depth = 1" in out
    assert "(bound 192)" in next(l for l in out.splitlines() if "routing:" in l)    # 2 rows x 48 tokens x min(top-2, 4 held)


def test_a_traced_run_reports_the_programs_counters_and_no_reader_raises(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-mla", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    # the expert layer's counters need no device; device-trace and chip-only readers return nothing on the CPU
    assert set(MLA_METRICS) & set(line["metrics"]) == {"joyai_moe_short_rows_share", "joyai_moe_load_max_over_mean"}
    # at toy sizes a batch now and then sends a layer more than its short rows hold: how many, the window's length decides
    assert 80.0 <= line["metrics"]["joyai_moe_short_rows_share"]["value"] <= 100.0
    from adapcc_tpu.utils.observability import default_registry

    samples = default_registry().snapshot()["samples"]
    assert samples["lm.loss_main"]["count"] == samples["lm.loss_mtp"]["count"] == line["attempted"]
    assert 0 < samples["lm.loss_main"]["mean"] < 10
    # in a cell of another runner kind this kind's readers find nothing
    code, line, _ = run_cell(tree, "tiny-w1", capsys, trace=1)
    assert code == 0 and not set(MLA_METRICS) & set(line["metrics"])


def test_the_readings_tool_takes_each_control_through_the_cells_own_limits(tree, capsys):
    """``readings_mla_lm`` at the toy cell: the program fails none of the
    configuration file's limits; the float8 control, the reference without
    the rotation and the reference without the module's loss term each fail
    one at least, and without its term the module's leaves get no gradient:
    the gap reads 1."""
    from chipbench import readings_mla_lm

    capsys.readouterr()
    assert readings_mla_lm.main(["--workload", "tiny-mla", "--seeds", "5"], require_chip=False, root=tree) == 0
    seed_line, summary = (json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{"))
    assert seed_line["verdict"]["program"] == [] and "params" in seed_line["worst_leaf"]
    for control in ("float8", "no_rotation", "no_mtp_term"):
        assert "grad_norm_gap" in seed_line["verdict"][control], control
        assert summary[f"{control}_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]
    assert summary["no_mtp_term_smallest"]["grad_norm_gap"] == 1.0
    assert any(name.startswith("loss_gap") for name in seed_line["verdict"]["no_mtp_term"])
    with pytest.raises(SystemExit, match="controls"):
        readings_mla_lm.main(["--workload", "tiny-mla", "--seeds", "5", "--controls", "float4"], require_chip=False, root=tree)


def test_lower_precision_in_the_references_place_is_told_apart(tree):
    from chipbench.runners import train_mla_lm

    config = tiny_mla_config()
    rows = train_mla_lm.packed_rows(tiny_mla_mix(), config["vocab_size"], 3)[:6].reshape(3, 2, -1)
    sound = train_mla_lm.reference_numbers(config, rows, 3)
    assert set(sound) == {"losses", "losses_main", "losses_mtp", "grad_norms", "update_norms"}
    gaps = {}
    for precision in ("bfloat16", "float8"):
        rows_cmp = train_mla_lm.compare(train_mla_lm.reference_numbers(config, rows, 3, precision), sound, LIMITS)
        gaps[precision] = {r["name"]: r["value"] for r in rows_cmp}
    assert gaps["float8"]["grad_norm_gap"] > LIMITS["grad_norm_gap"]
    assert gaps["float8"]["grad_norm_gap"] > 4 * gaps["bfloat16"]["grad_norm_gap"] > 0
    assert len(gaps["float8"]) == 3 * 3 + 2          # L, L_main and L_mtp at each of three steps, two norms


def test_the_programs_rotation_left_out_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.models import kimi_linear

    monkeypatch.setattr(kimi_linear, "rotate_pairs", lambda x, theta, start=0: x)
    code, line, out = run_cell(tree, "tiny-mla", capsys)
    assert code == 0 and line["correct"] is False
    assert any("grad_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_the_programs_loss_without_the_modules_term_comes_out_not_correct(tree, capsys, monkeypatch):
    from chipbench.runners import train_mla_lm

    real = train_mla_lm.model_config
    monkeypatch.setattr(train_mla_lm, "model_config", lambda config: dataclasses.replace(real(config), mtp_loss_weight=0.0))
    code, line, out = run_cell(tree, "tiny-mla", capsys)
    assert code == 0 and line["correct"] is False
    failed = [l for l in out.splitlines() if "FAILED" in l]
    assert any("loss_gap.step1" in l for l in failed) and any("grad_norm_gap = 1 " in l for l in failed)
    assert not any("loss_main_gap" in l or "loss_mtp_gap" in l for l in failed)     # each term alone is sound


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def broken(self, state, batch, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state)     # the step donates what it is given
        new, loss = real(self, state, batch, *a, **kw)
        return kept.replace(model_state=new.model_state), loss

    monkeypatch.setattr(DDPTrainer, "step", broken)
    code, line, out = run_cell(tree, "tiny-mla", capsys)
    assert code == 0 and line["correct"] is False
    assert any("update_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_what_differs_between_the_runners_is_one_object_of_parts():
    from chipbench.runners import train_mla_lm

    parts = train_mla_lm.PARTS
    assert {f.name for f in dataclasses.fields(parts)} == {
        "facts_key", "top_k_key", "build", "fresh_state", "recording", "drive_first_steps",
        "reference_numbers", "compare", "check_program", "also_correct", "record_window", "reduce_trace",
    }
    assert (parts.facts_key, parts.top_k_key) == ("mla_lm", "num_experts_per_tok")
    said = []
    cfg = train_mla_lm.model_config(real_config())
    assert (cfg.held, cfg.mtp_loss_weight, cfg.remat, cfg.expert_layers) == (16, 0.3, "none", 5)
    assert train_mla_lm.module_is_there(said.append) in (True, False) and "mtp.depth" in said[0]


def test_the_configuration_and_the_mix_are_the_published_widths_and_the_issues_traffic():
    cfg, mix = real_config(), real_mix()
    published = {
        "hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768, "num_attention_heads": 32,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_head_dim": 192, "head_dim": 64, "n_routed_experts": 256, "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 32000000, "rms_norm_eps": 1e-06, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "n_group": 1, "topk_group": 1, "max_position_embeddings": 131072,
    }
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"], cfg["vocab_size"]) == (5, 16, 16160)
    assert cfg["published"]["num_hidden_layers"] == 40 and cfg["published"]["vocab_size"] == 129280 == 8 * 16160
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert cfg["assumed"]["mtp_loss_weight"] == 0.3 and cfg["assumed"]["optimizer"]["learning_rate"] == 1e-6
    assert {k: mix[k] for k in ("runner", "seq_len", "walks_per_row", "batch_per_chip", "corpus_rows", "branching",
                                "prefetch", "steps_per_sample")} == {
        "runner": "train_mla_lm", "seq_len": 1024, "walks_per_row": 8, "batch_per_chip": 1, "corpus_rows": 2048,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }
    from chipbench import weights_mla_lm

    table = jax.tree_util.tree_leaves(weights_mla_lm.leaf_table(cfg), is_leaf=weights_mla_lm._is_leaf)
    count = lambda shape: int(jnp.prod(jnp.asarray(shape)))  # noqa: E731
    assert sum(count(shape) for shape, _ in table) == 680_441_088


def test_the_arithmetic_counts_six_blocks_the_module_and_both_heads():
    cfg = real_config()
    parts = arithmetic_mla_lm.forward_flops_per_token(cfg, 8192, 0.5)
    millions = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert millions == {
        "mla_projections": 316.1, "mla_products": 503.4, "dense_ffn": 88.1, "router": 5.2, "shared_experts": 47.2,
        "routed_experts": 23.6, "mtp_merge": 16.8, "head": 132.4,
    }
    # ISSUE 34: the latent layers' causal products are 44% of a step's required FLOPs at T = 8,192
    total = arithmetic_mla_lm.train_flops_per_token(cfg, 8192, 0.5)
    assert 0.43 < 3 * parts["mla_products"] / total < 0.46 and 27e12 < total * 8192 < 28.5e12
    attn = arithmetic_mla_lm.mla_flops(1, cfg, 8192)
    assert attn["fwd"] == 2 * 32 * 8192 * 8193 / 2 * (192 + 128) and attn["bwd"] / attn["fwd"] == (3 * 192 + 2 * 128) / 320
    assert arithmetic_mla_lm.mla_bytes(1, cfg, 8192)["fwd"] == 8192 * 32 * 2 * (2 * 192 + 2 * 128)
    # at T = 1,024 the mechanism would be a small part of the step
    short = arithmetic_mla_lm.forward_flops_per_token(cfg, 1024, 0.5)
    assert short["mla_products"] / sum(short.values()) < 0.1


def test_the_readers_give_shares_under_a_hundred_from_a_trace_and_nothing_without_one():
    from chipbench import run

    facts = {
        "config": real_config(), "mix": real_mix(), "world": 1, "steps": 10, "platform": "tpu",
        "device_kind": "TPU v5 lite", "tokens_per_s": 20000.0, "mla_lm": {"assignments_per_layer_step": 4096.0},
        "trace": {"window_s": 4.1, "mla_kernel_s": {
            "kda_fwd": 0.0, "kda_bwd": 0.0, "flash_fwd": 0.4, "flash_bwd_dq": 0.54, "flash_bwd_dkv": 0.66,
        }},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in MLA_METRICS}
    got = {name: read[name](facts) for name in MLA_METRICS[:3]}
    assert got["joyai_mla_time_share"] == pytest.approx(100 * 1.6 / 4.1)
    assert all(0 < v < 100 for v in got.values()), got
    # FLOPs bind: five layers' triangles at T and the module's at T - 1, forward and backward, at the peak
    pairs = lambda n: 2 * 32 * n * (n + 1) / 2  # noqa: E731
    need = (5 * pairs(8192) + pairs(8191)) * (4 * 192 + 3 * 128)
    assert got["joyai_mla_roofline"] == pytest.approx(100 * 10 * need / 197e12 / 1.6)
    assert 30 < got["joyai_train_mfu"] < 40
    bare = dict(facts, trace=None, platform="cpu")
    assert all(read[name](bare) is None for name in MLA_METRICS[:3])
    assert all(read[name](dict(facts, trace={"window_s": 4.1})) is None for name in MLA_METRICS[1:3])
    other_runner = {k: v for k, v in facts.items() if k != "mla_lm"}
    assert all(read[name](other_runner) is None for name in ("joyai_train_mfu",) + MLA_METRICS[3:])
