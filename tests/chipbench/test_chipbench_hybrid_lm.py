"""The ``train_hybrid_lm`` runner end to end at toy widths on the virtual CPU
devices, through ``run.main``; the configuration, mix and metric files the
manifest names; the float8 control and two broken timed paths that must each
come out not ``correct``: the latent layer's shared key channels left out, a
step that returns its state unchanged."""

import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import ROOT, make_tree, run_cell

from chipbench import arithmetic_hybrid_lm, correct, trace_hybrid_lm

HYBRID_METRICS = ("kimi_train_mfu", "kda_time_share", "kda_roofline", "mla_time_share", "mla_roofline")
# float32 activations on the CPU: sound runs read 1e-6 to 2e-5, the float8 control 1e-2 or more
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}


def real_config() -> dict:
    return json.loads((ROOT / "chipbench/configs/kimi-linear-ep32.json").read_text())


def tiny_hybrid_config() -> dict:
    real = real_config()
    real.update(
        name="tiny-hybrid", vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=4, num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, num_experts_held=4, expert_offset=2, num_experts_per_token=2,
        linear_attn_config={
            "kda_layers": [1, 2, 4], "full_attn_layers": [3], "num_heads": 2, "head_dim": 16,
            "short_conv_kernel_size": 4,
        },
        limits=dict(LIMITS),
    )
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_hybrid_mix() -> dict:
    return {
        "runner": "train_hybrid_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_hybrid"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-hybrid.json").write_text(json.dumps(tiny_hybrid_config()))
    (tmp / "chipbench/traffic/tiny-hybrid-b2.json").write_text(json.dumps(tiny_hybrid_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-hybrid", "source": "test", "file": "chipbench/configs/tiny-hybrid.json",
        "reduced": tiny_hybrid_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-hybrid", "config": "tiny-hybrid", "traffic": "tiny-hybrid-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in HYBRID_METRICS:
            m["workloads"] = ["tiny-hybrid"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def test_the_hybrid_cell_runs_end_to_end_and_is_correct(tree, capsys):
    code, line, out = run_cell(tree, "tiny-hybrid", capsys, seed=2**31 + 11)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_step_p95_ms", "setup_s"}
    for name in ("loss_gap.step1", "loss_gap.step3", "grad_norm_gap", "update_norm_gap"):
        assert f"correct: {name} = " in out
    assert "assignments of held experts dropped = 0" in out
    assert "(bound 192)" in next(l for l in out.splitlines() if "routing:" in l)    # 2 rows x 48 tokens x min(top-2, 4 held)


def test_a_traced_run_reports_what_needs_no_device_trace_and_no_reader_raises(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-hybrid", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    # device-trace and chip-only readers return nothing on the CPU, here and in a cell of another runner
    assert not set(HYBRID_METRICS) & set(line["metrics"])
    code, line, _ = run_cell(tree, "tiny-w1", capsys, trace=1)
    assert code == 0 and not set(HYBRID_METRICS) & set(line["metrics"])


def test_the_float8_control_fails_a_limit(tree):
    """The reference with every product's operands rounded to float8, in the
    program's place, is not ``correct`` by the toy cell's limits; in bfloat16's
    place of float32 it is told apart too, by less."""
    from chipbench.runners import train_hybrid_lm

    config = tiny_hybrid_config()
    rows = train_hybrid_lm.packed_rows(tiny_hybrid_mix(), config["vocab_size"], 3)[:6].reshape(3, 2, -1)
    sound = train_hybrid_lm.reference_numbers(config, rows, 3)
    gaps = {}
    for precision in ("bfloat16", "float8"):
        rows_cmp = correct.compare(train_hybrid_lm.reference_numbers(config, rows, 3, precision), sound, LIMITS)
        gaps[precision] = {r["name"]: r["value"] for r in rows_cmp}
        if precision == "float8":
            assert not correct.verdict(rows_cmp)
    assert gaps["float8"]["grad_norm_gap"] > 4 * gaps["bfloat16"]["grad_norm_gap"] > 0


def test_the_readings_tool_takes_each_control_through_the_cells_own_limits(tree, capsys):
    """``readings_hybrid_lm`` at the toy cell: the program fails none of the
    configuration file's limits, the float8 control at least one."""
    from chipbench import readings_hybrid_lm

    capsys.readouterr()
    assert readings_hybrid_lm.main(["--workload", "tiny-hybrid", "--seeds", "5"], require_chip=False, root=tree) == 0
    seed_line, summary = (json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{"))
    assert seed_line["verdict"]["program"] == []
    assert "grad_norm_gap" in seed_line["verdict"]["float8"]
    assert summary["float8_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]


def test_the_shared_key_channels_left_out_come_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.models import kimi_linear

    real = kimi_linear.jnp.broadcast_to

    def no_shared_channels(x, shape):
        out = real(x, shape)
        return jnp.zeros_like(out) if len(shape) == 4 and shape[-1] == 8 else out     # k_pe to every head

    monkeypatch.setattr(kimi_linear.jnp, "broadcast_to", no_shared_channels)
    code, line, out = run_cell(tree, "tiny-hybrid", capsys)
    assert code == 0 and line["correct"] is False
    assert any("FAILED" in l for l in out.splitlines())


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def broken(self, state, batch, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state)     # the step donates what it is given
        _, loss = real(self, state, batch, *a, **kw)
        return kept, loss

    monkeypatch.setattr(DDPTrainer, "step", broken)
    code, line, out = run_cell(tree, "tiny-hybrid", capsys)
    assert code == 0 and line["correct"] is False
    assert any("update_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_the_arithmetic_counts_the_recurrence_the_triangle_and_the_assignments():
    cfg = real_config()
    parts = arithmetic_hybrid_lm.forward_flops_per_token(cfg, 8192, 0.25)
    millions = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert millions == {
        "kda_projections": 315.7, "kda_recurrence": 12.6, "mla_projections": 58.2, "mla_products": 83.9,
        "dense_ffn": 127.4, "router": 4.7, "shared_experts": 56.6, "routed_experts": 14.2, "head": 94.4,
    }
    # ISSUE 32 counted 329 M active matmul parameters at 2,048 assignments a layer-step (a quarter a token)
    matmul = sum(v for k, v in parts.items() if k not in ("kda_recurrence", "mla_products"))
    assert round(matmul / 2 / 1e6) == 336
    scan, attn = arithmetic_hybrid_lm.kda_flops(1, cfg, 8192), arithmetic_hybrid_lm.mla_flops(1, cfg, 8192)
    assert scan == {"fwd": 8192 * 32 * 6 * 128 * 128, "bwd": 2 * 8192 * 32 * 6 * 128 * 128}
    assert attn["fwd"] == 2 * 32 * 8192 * 8193 / 2 * (192 + 128) and attn["bwd"] / attn["fwd"] == (3 * 192 + 2 * 128) / 320
    nbytes = arithmetic_hybrid_lm.kda_bytes(1, cfg, 8192)
    assert nbytes["fwd"] == 8192 * 32 * (3 * 128 * 2 + 128 * 4 + 4 + 128 * 2) == 8192 * 32 * (1284 + 256)
    assert arithmetic_hybrid_lm.mla_bytes(1, cfg, 8192)["fwd"] == 8192 * 32 * 2 * (2 * 192 + 2 * 128)


def test_the_five_kernels_are_told_by_name_or_by_signature_and_the_expert_kernels_left_out():
    call = 'custom_call_target="tpu_custom_call"'
    a = "bf16[32,8192,128]{2,1,0} %x"
    named = f"%kda_bwd.4 = (bf16[32,8192,128], f32[32,8192,128]) custom-call({', '.join([a] * 7)}), {call}"
    unnamed = f"%self_attn.9 = (bf16[32,8192,128], f32[32,16,128,128]) custom-call({', '.join([a] * 5)}), {call}"
    flash = f"%flash_fwd.1 = (bf16[32,8192,128], f32[32,8192,8]) custom-call({', '.join([a] * 3)}), {call}"
    expert = f"%ragged-dot-none.86 = bf16[65536,1024] custom-call({', '.join([a] * 6)}), {call}"
    fusion = "%fusion.1 = bf16[8192,9216]{1,0} fusion(bf16[8192,2304] %x, bf16[2304,9216] %w), kind=kOutput"
    assert [trace_hybrid_lm.kernel_of(n) for n in (named, unnamed, flash, expert, fusion)] == [
        "kda_bwd", "kda_fwd", "flash_fwd", None, None,
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        [named, 0, 3_000_000], [unnamed, 3_000_000, 1_000_000], [flash, 4_000_000, 2_000_000],
        [expert, 6_000_000, 5_000_000], [fusion, 11_000_000, 5_000_000],
    ]}]}]}
    seconds = trace_hybrid_lm.kernel_seconds(trace)
    assert (seconds["kda_bwd"], seconds["kda_fwd"], seconds["flash_fwd"]) == (0.003, 0.001, 0.002)
    assert sum(seconds.values()) == pytest.approx(0.006)
    top = trace_hybrid_lm.top_operations(trace, 3)
    assert [name for name, _ in top] == ["ragged-dot bf16[65536,1024]", "fusion bf16[8192,9216]", "kda_bwd"]


def test_the_readers_give_shares_under_a_hundred_from_a_trace_and_nothing_without_one():
    from chipbench import run

    cfg = real_config()
    mix = json.loads((ROOT / "chipbench/traffic/packed8192-b1-hybrid.json").read_text())
    facts = {
        "config": cfg, "mix": mix, "world": 1, "steps": 10, "platform": "tpu", "device_kind": "TPU v5 lite",
        "tokens_per_s": 27000.0, "hybrid": {"assignments_per_layer_step": 2048.0},
        "trace": {"window_s": 3.0, "hybrid_kernel_s": {
            "kda_fwd": 0.2, "kda_bwd": 0.5, "flash_fwd": 0.08, "flash_bwd_dq": 0.1, "flash_bwd_dkv": 0.12,
        }},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in HYBRID_METRICS}
    got = {name: fn(facts) for name, fn in read.items()}
    assert got["kda_time_share"] == pytest.approx(100 * 0.7 / 3.0) and got["mla_time_share"] == pytest.approx(10.0)
    assert all(0 < v < 100 for v in got.values()), got
    # the recurrence is bound by its bytes, the triangle by its FLOPs
    assert got["kda_roofline"] == pytest.approx(100 * 10 * 4 * 8192 * 32 * (3 * 1284 + 2 * 256) / 819e9 / 0.7)
    bare = dict(facts, trace=None, platform="cpu")
    assert all(fn(bare) is None for fn in read.values())
    assert all(fn(dict(facts, trace={"window_s": 3.0})) is None for name, fn in read.items() if name != "kimi_train_mfu")
