"""The ``train_moe_lm`` runner end to end at toy widths on the virtual CPU
devices, through ``run.main``; the configuration, mix and metric files the
manifest names; and three broken timed paths that must each come out not
``correct``: a step that returns its state unchanged, the routed experts'
contribution left out, the window ignored on sliding layers."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import ROOT, make_tree, run_cell

from chipbench import arithmetic_moe_lm, trace_moe_lm

KINDS = ["sliding_attention", "sliding_attention", "full_attention"]
MOE_METRICS = (
    "moe_train_mfu", "attn_band_roofline", "attn_band_time_share", "moe_expert_time_share",
    "moe_load_max_over_mean",
)


def tiny_moe_config() -> dict:
    real = json.loads((ROOT / "chipbench/configs/trinity-mini-ep8.json").read_text())
    real.update(
        name="tiny-moe", vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, sliding_window=16, num_experts=8, num_experts_held=4, expert_offset=2,
        num_experts_per_tok=2, layer_types_here=KINDS,
        # float32 activations on the CPU: sound runs read 1e-6, a fault 1e-2 or more
        limits={"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05},
    )
    real["assumed"]["program"].update(attention="xla", activations="float32", loss="dense", remat="none")
    return real


def tiny_moe_mix() -> dict:
    return {
        "runner": "train_moe_lm", "seq_len": 32, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_moe"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-moe.json").write_text(json.dumps(tiny_moe_config()))
    (tmp / "chipbench/traffic/tiny-moe-b2.json").write_text(json.dumps(tiny_moe_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-moe", "source": "test", "file": "chipbench/configs/tiny-moe.json",
        "reduced": tiny_moe_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-moe", "config": "tiny-moe", "traffic": "tiny-moe-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in MOE_METRICS:
            m["workloads"] = ["tiny-moe"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def test_the_moe_cell_runs_end_to_end_and_is_correct(tree, capsys):
    code, line, out = run_cell(tree, "tiny-moe", capsys, seed=2**31 + 11)
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_tokens_per_s", "train_step_p95_ms", "setup_s"}
    for name in ("loss_gap.step1", "loss_gap.step3", "grad_norm_gap", "update_norm_gap"):
        assert f"correct: {name} = " in out
    assert "assignments of held experts dropped = 0" in out
    routing = next(l for l in out.splitlines() if "routing:" in l)
    assert "(bound 256)" in routing     # 2 rows x 64 tokens x min(top-2, 4 held)


def test_a_traced_run_reports_what_needs_no_device_trace_and_no_gpt2_reader_raises(tree, capsys):
    code, line, _ = run_cell(tree, "tiny-moe", capsys, trace=1)
    assert code == 0 and line["correct"] is True
    # readers without a workloads list are read in the new cell too
    assert {"input_wait_ms", "step_dispatch_ms", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    # the program's own sample, recorded from what the steps returned beside the loss
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    # device-trace and chip-only readers return nothing on the CPU; GPT-2's mfu is not asked
    assert not {"attn_band_roofline", "moe_expert_time_share", "moe_train_mfu", "train_mfu"} & set(line["metrics"])


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer

    real = DDPTrainer.step

    def broken(self, state, batch, *a, **kw):
        kept = jax.tree_util.tree_map(jnp.copy, state)     # the step donates what it is given
        _, loss = real(self, state, batch, *a, **kw)
        return kept, loss

    monkeypatch.setattr(DDPTrainer, "step", broken)
    code, line, out = run_cell(tree, "tiny-moe", capsys)
    assert code == 0 and line["correct"] is False
    assert any("update_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_the_routed_experts_left_out_come_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.models import trinity

    real = trinity.routed_experts

    def shared_only(x, ids, weights, stacked, **kw):
        y, sizes = real(x, ids, weights, stacked, **kw)
        return jnp.zeros_like(y), sizes

    monkeypatch.setattr(trinity, "routed_experts", shared_only)
    code, line, out = run_cell(tree, "tiny-moe", capsys)
    assert code == 0 and line["correct"] is False
    assert any("FAILED" in l for l in out.splitlines())


def test_the_window_ignored_on_sliding_layers_comes_out_not_correct(tree, capsys, monkeypatch):
    from adapcc_tpu.models import trinity

    real = trinity._dense_attention
    monkeypatch.setattr(trinity, "_dense_attention", lambda q, k, v, window: real(q, k, v, None))
    code, line, out = run_cell(tree, "tiny-moe", capsys)     # T = 64 > window = 16
    assert code == 0 and line["correct"] is False
    assert any("loss_gap" in l or "grad_norm_gap" in l for l in out.splitlines() if "FAILED" in l)


def test_the_arithmetic_counts_the_band_the_triangle_and_the_assignments():
    cfg = json.loads((ROOT / "chipbench/configs/trinity-mini-ep8.json").read_text())
    parts = arithmetic_moe_lm.forward_flops_per_token(cfg, 8192, 1.0)
    millions = {k: round(v / 1e6) for k, v in parts.items()}
    # ISSUE 26's count: products 185, projections and gate 273, dense 76, shared and routed 50 + 50, head 103 (102.5), and the router's 2
    assert millions == {
        "attention_products": 185, "attention_projections": 273, "dense_ffn": 75, "router": 2,
        "shared_experts": 50, "routed_experts": 50, "head": 102,
    }
    assert arithmetic_moe_lm.keys_seen(8192, "sliding_attention", 2048) / arithmetic_moe_lm.keys_seen(
        8192, "full_attention", 2048
    ) == pytest.approx(0.4375, abs=1e-3)     # the band is 44% of the triangle
    twice = arithmetic_moe_lm.forward_flops_per_token(cfg, 8192, 2.0)
    assert twice["routed_experts"] == 2 * parts["routed_experts"] and twice["head"] == parts["head"]
    nbytes = arithmetic_moe_lm.attention_bytes(1, cfg, 8192)
    assert nbytes["fwd"] == 2 * 8192 * 128 * 2 * (32 + 4)


def test_expert_operations_are_told_by_their_kernel_and_their_bound_sized_arrays():
    cfg = json.loads((ROOT / "chipbench/configs/trinity-mini-ep8.json").read_text())
    kernel, rows = trace_moe_lm.expert_patterns(cfg, 8192)
    yes = [
        '%ragged-dot-none.3 = bf16[65536,1024]{1,0} custom-call(s32[1] %a, bf16[65536,2048] %x, bf16[16,2048,1024] %w), custom_call_target="tpu_custom_call"',
        "%fusion.12 = bf16[65536,2048]{1,0} fusion(bf16[8192,2048] %x, s32[65536] %i), kind=kLoop",
        "%fusion.9 = f32[8192,2048]{1,0} fusion(f32[8192,8,2048] %picked, f32[8192,8] %w), kind=kLoop",
        "%sort.1 = (s32[65536], s32[65536]) sort(s32[65536] %k, s32[65536] %i), dimensions={0}",
    ]
    no = [
        "%fusion.1 = bf16[8192,6144]{1,0} fusion(bf16[8192,2048] %x, bf16[2048,6144] %w), kind=kOutput",
        '%flash_fwd.1 = (bf16[32,8192,128], f32[32,8192,8]) custom-call(bf16[32,8192,128] %q, bf16[4,8192,128] %k, bf16[4,8192,128] %v), custom_call_target="tpu_custom_call"',
        "%multiply_add_fusion.2 = f32[16,2048,1024]{2,1,0} fusion(f32[16,2048,1024] %p, f32[16,2048,1024] %m), kind=kLoop",
    ]
    assert all(trace_moe_lm.is_expert_op(n, kernel, rows) for n in yes)
    assert not any(trace_moe_lm.is_expert_op(n, kernel, rows) for n in no)
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        [yes[0], 0, 3_000_000], [yes[1], 3_000_000, 1_000_000], [no[0], 4_000_000, 5_000_000],
    ]}]}]}
    assert trace_moe_lm.expert_seconds(trace, cfg, 8192) == {"grouped_products": 0.003, "rows": 0.001}
