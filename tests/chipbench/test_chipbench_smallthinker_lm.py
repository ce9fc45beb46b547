"""The ``train_smallthinker_lm`` runner at toy widths on the virtual CPU
devices: one traced run of the cell through ``run.main`` and one run of the
readings tool with float8 and the seven changed pieces of the mathematics,
each computed once for the module (PERF.md section 7 item 27); the
configuration, mix and metric files the manifest names, as ISSUE 49 states
them, found by name; the readers on a recorded step's instructions, each event
counted once, the routing apart from the experts; the arithmetic against hand
counts."""

import json

import jax
import numpy as np
import pytest

from chipbench_tiny import ROOT, make_tree

from chipbench import arithmetic_smallthinker_lm

METRICS = (
    "smallthinker_train_mfu", "smallthinker_attn_time_share", "smallthinker_attn_roofline", "smallthinker_expert_time_share",
    "smallthinker_route_time_share", "smallthinker_moe_load_max_over_mean", "smallthinker_moe_short_rows_share",
)
FAULTS = (
    "router_after_attention", "router_on_normed_input", "silu_experts", "softmax_over_all", "rope_on_global",
    "no_rope_on_window", "window_off",
)
# float32 activations on the CPU: sound runs read 1e-7 to 2e-6, each control 2e-2 or more on the gradient
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 2e-3, "update_norm_gap": 0.05}
CELL = "smallthinker-21b-a3b-ep4-train"
CONFIG = "smallthinker-21b-a3b-ep4"
MIX = "packed8192-b1-smallthinker"
SOURCE = "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"


def real_config() -> dict:
    return json.loads((ROOT / f"chipbench/configs/{CONFIG}.json").read_text())


def real_mix() -> dict:
    return json.loads((ROOT / f"chipbench/traffic/{MIX}.json").read_text())


def tiny_config() -> dict:
    """Eight published layers of which two are held, each kind once (a global
    layer without positions, a windowed one with them: the whole period is
    ``tests/test_smallthinker.py``'s), 7 query heads on 1 K/V head, 8 experts
    top-3 of which 4 are held, a window shorter than the row."""
    real = real_config()
    layout = [int(i % 4 != 0) for i in range(8)]
    real.update(
        name="tiny-smallthinker", vocab_size=256, hidden_size=32, head_dim=8, num_attention_heads=7, num_key_value_heads=1,
        moe_ffn_hidden_size=16, moe_num_primary_experts=8, moe_num_active_primary_experts=3, num_experts_held=4,
        sliding_window_size=16, rope_layout=layout, sliding_window_layout=layout, layers_held=[0, 1],
        num_hidden_layers=2, limits=dict(LIMITS), published={"num_hidden_layers": 8, "moe_num_primary_experts": 8, "vocab_size": 256},
    )
    real["assumed"]["program"].update(activations="float32", loss="dense", remat="none")
    return real


def tiny_mix() -> dict:
    return {
        "runner": "train_smallthinker_lm", "seq_len": 24, "walks_per_row": 2, "batch_per_chip": 2, "corpus_rows": 64,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    tmp = make_tree(tmp_path_factory.mktemp("bench_smallthinker"), cells=(("tiny-w1", 1),))
    (tmp / "chipbench/configs/tiny-smallthinker.json").write_text(json.dumps(tiny_config()))
    (tmp / "chipbench/traffic/tiny-smallthinker-b2.json").write_text(json.dumps(tiny_mix()))
    manifest = json.loads((tmp / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-smallthinker", "source": "test", "file": "chipbench/configs/tiny-smallthinker.json",
        "reduced": tiny_config()["reduced"], "why": "toy widths for the CPU tests",
    })
    manifest["workloads"].append(
        {"name": "tiny-smallthinker", "config": "tiny-smallthinker", "traffic": "tiny-smallthinker-b2", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"] = ["tiny-smallthinker"]
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
        ["--workload", "tiny-smallthinker", "--seed", str(2**31 + 11), "--seconds", "0.3", "--trace", "1"],
        require_chip=False, root=tree, bench=tree / "chipbench",
    ))
    return code, json.loads(out.strip().splitlines()[-1]), out, before


@pytest.fixture(scope="module")
def readings(tree):
    """One run of the readings tool, one seed, float8 and the seven changed pieces: ``(the seed's line, the
    summary, every side's numbers as --raw keeps them)``."""
    from chipbench import readings_smallthinker_lm

    code, out = said_by(lambda: readings_smallthinker_lm.main(
        ["--workload", "tiny-smallthinker", "--seeds", "5", "--raw", str(tree / "raw.json")], require_chip=False, root=tree,
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
    # four of eight experts held under top-3: a token lands here three times at most (the bound), 1.5 times on balance
    assert "assignments of held experts dropped = 0" in out and "(bound 288)" in next(l for l in out.splitlines() if "routing:" in l)
    said = next(l for l in out.splitlines() if "smallthinker.early_route_calls" in l)
    assert "wanted 2 a traced step" in said and float(said.split("=")[1].split()[0]) % 2 == 0


def test_a_traced_run_reports_the_programs_samples_and_no_reader_raises(traced):
    code, line, _, recompiles_before = traced
    assert code == 0 and line["correct"] is True
    unlisted = {"input_wait_ms", "step_dispatch_ms", "window_compiles", "step_trace_lower_s", "step_load_s",
                "step_cache_misses", "step_recompiles"}
    assert unlisted <= set(line["metrics"])                  # the seven readers without a list of cells read this one
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert line["metrics"]["step_recompiles"]["value"] - recompiles_before == 0.0
    # the routing's two samples need no device; device-trace and chip-only readers return nothing on the CPU
    assert set(METRICS) & set(line["metrics"]) == {"smallthinker_moe_load_max_over_mean", "smallthinker_moe_short_rows_share"}
    assert line["metrics"]["smallthinker_moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= line["metrics"]["smallthinker_moe_short_rows_share"]["value"] <= 100.0


def test_the_readings_tool_reads_the_program_by_the_files_own_limits(readings, tree):
    from chipbench import readings_smallthinker_lm

    seed_line, summary, raw = readings
    assert seed_line["verdict"]["program"] == [] and "params" in seed_line["worst_leaf"]
    assert set(seed_line["program"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    assert summary["sound_largest"] == seed_line["program"]
    assert set(raw["by_seed"]["5"]) == {"program", "reference", "float8", *FAULTS}
    with pytest.raises(SystemExit, match="controls"):
        readings_smallthinker_lm.main(
            ["--workload", "tiny-smallthinker", "--seeds", "5", "--controls", "float4"], require_chip=False, root=tree
        )


@pytest.mark.parametrize("control", ("float8",) + FAULTS)
def test_each_control_fails_the_cells_own_limits(readings, control):
    """float8 in the reference's place, and the reference with each of the seven pieces changed."""
    seed_line, summary, _ = readings
    assert seed_line["verdict"][control]
    assert summary[f"{control}_smallest"]["grad_norm_gap"] > LIMITS["grad_norm_gap"] > summary["sound_largest"]["grad_norm_gap"]


def test_a_step_that_returns_its_state_unchanged_comes_out_not_correct(readings):
    """The timed path's own numbers from the tool's run, with no leaf moved: ``update_norm_gap`` alone fails."""
    from chipbench import correct, weights_smallthinker_lm

    raw = readings[2]
    program, reference = (raw["by_seed"]["5"][side] for side in ("program", "reference"))
    assert raw["leaves"] == weights_smallthinker_lm.leaf_names(tiny_config())
    assert all(r["ok"] for r in correct.compare(program, reference, LIMITS))
    frozen = dict(program, update_norms=np.zeros(len(raw["leaves"])))
    assert [r["name"] for r in correct.compare(frozen, reference, LIMITS) if not r["ok"]] == ["update_norm_gap"]


def test_the_runner_is_the_shared_window_with_its_own_parts():
    from chipbench import correct
    from chipbench.runners import train_mla_lm, train_moe_lm, train_smallthinker_lm

    config = real_config()
    parts = train_smallthinker_lm.parts_for(config, 8192)
    assert isinstance(parts, train_mla_lm.Parts)
    assert (parts.facts_key, parts.top_k_key) == ("smallthinker_lm", "moe_num_active_primary_experts")
    assert parts.compare is correct.compare and parts.recording is train_moe_lm.Recording
    assert train_smallthinker_lm.CONTROLS == ("bfloat16", "float8") + FAULTS
    said = []
    assert parts.also_correct(said.append) in (True, False) and "smallthinker.early_route_calls" in said[0]


def test_the_weight_maker_counts_the_cells_parameters_and_nothing_moves_at_the_start():
    from chipbench import weights_smallthinker_lm

    config = tiny_config()
    params = weights_smallthinker_lm.make_params(4, config)
    names = weights_smallthinker_lm.leaf_names(config)
    assert [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(params)] == names
    moved = np.asarray(weights_smallthinker_lm.moved_norms(params, 4, config))
    assert moved.shape == (len(names),) and np.all(moved == 0.0)
    layer = params["params"]["layers_1"]
    assert np.all(np.asarray(layer["input_layernorm"]["scale"]) == 1.0) and layer["router"]["kernel"].shape == (32, 8)
    # the projections back into the stream are scaled by 1/sqrt(2 x the published depth)
    down, up = np.asarray(layer["block_sparse_moe"]["experts_w2"]), np.asarray(layer["block_sparse_moe"]["experts_w1"])
    assert down.std() == pytest.approx(0.02 / 4.0, rel=0.1) and up.std() == pytest.approx(0.02, rel=0.1)
    assert weights_smallthinker_lm.layer_plan(real_config()) == ((False, False), (True, True), (True, True), (True, True))


def test_the_embedding_outweighs_what_a_layer_writes_into_the_stream():
    """The router reads the stream un-normed, so the embedding is drawn at
    0.2: more than the 0.117 of a unit value that ``o_proj`` writes at the
    published widths (0.02 / sqrt(104) a weight over 28 heads of 128), which
    an embedding of 0.02 is a sixth of."""
    from chipbench import weights_smallthinker_lm

    real = real_config()
    table = weights_smallthinker_lm.leaf_table(real)["params"]
    (rows, _), o_std = table["layers_0"]["self_attn"]["o_proj"]["kernel"]
    written = o_std * np.sqrt(rows)
    assert written == pytest.approx(0.117, abs=0.001)
    assert table["embed_tokens"]["embedding"][1] == weights_smallthinker_lm.EMBEDDING_STD > 1.5 * written > 0.02 * 6
    embedding = np.asarray(weights_smallthinker_lm.make_params(4, tiny_config())["params"]["embed_tokens"]["embedding"])
    assert embedding.std() == pytest.approx(weights_smallthinker_lm.EMBEDDING_STD, rel=0.05)


def test_the_manifest_names_the_configuration_the_cell_and_the_seven_metrics():
    """Found by name, never from the end or by a count: a later PR's entries
    after these change nothing here."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"] and config["source"] == SOURCE
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    added = [m for m in manifest["per_layer"] if m["name"] in METRICS]
    assert tuple(m["name"] for m in added) == METRICS
    for m in added:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
    assert {m["name"]: (m["layer"], m["source"]) for m in added} == {
        "smallthinker_train_mfu": ("model step", "host_clock"),
        "smallthinker_attn_time_share": ("Pallas kernels", "device_trace"),
        "smallthinker_attn_roofline": ("Pallas kernels", "device_trace"),
        "smallthinker_expert_time_share": ("expert layer", "device_trace"),
        "smallthinker_route_time_share": ("expert layer", "device_trace"),
        "smallthinker_moe_load_max_over_mean": ("expert layer", "program_counter"),
        "smallthinker_moe_short_rows_share": ("expert layer", "program_counter"),
    }
    # no accepted metric's list of cells gained this one: their readers find nothing to read in it
    assert all(CELL not in m.get("workloads", []) or m in added for m in manifest["per_layer"])
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= max(1, len(manifest["workloads"]) // 4)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("lfm2-24b-a2b-ep4-train")      # appended: nothing put before what was there


def test_the_configuration_and_the_mix_are_the_published_widths_and_the_issues_traffic():
    cfg, mix = real_config(), real_mix()
    layout = [int(i % 4 != 0) for i in range(52)]
    published = {
        "hidden_size": 2560, "head_dim": 128, "num_attention_heads": 28, "num_key_value_heads": 4, "moe_ffn_hidden_size": 768,
        "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6, "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True, "sliding_window_size": 4096, "rope_theta": 1500000, "rope_scaling": None,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "rope_layout": layout, "sliding_window_layout": layout,
    }
    assert {k: cfg[k] for k in published} == published and cfg["source"] == SOURCE
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"], cfg["expert_offset"], cfg["vocab_size"]) == (4, 16, 0, 37984)
    assert cfg["layers_held"] == [0, 1, 2, 3]
    assert cfg["published"]["num_hidden_layers"] == 52 and cfg["published"]["moe_num_primary_experts"] == 64 == 4 * cfg["num_experts_held"]
    assert cfg["published"]["vocab_size"] == 151936 == 4 * 37984
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert "656,529,920" in cfg["deployment"] and "four-chip" in cfg["deployment"] and "768 rows" in cfg["deployment"]
    assumed = cfg["assumed"]
    wanted = {"router_input", "biases", "rope_pairing", "window_count", "topk_before_softmax", "one_router"}
    assert wanted <= set(assumed["not_in_config_json"]) and "rmsnorm_in(h)" in assumed["not_in_config_json"]["router_input"]
    assert assumed["optimizer"]["learning_rate"] == 1e-6 and assumed["program"]["donate_state"] is True
    assert assumed["program"]["activations"] == "bfloat16" and assumed["program"]["loss"] in ("dense", "chunked")
    assert assumed["program"]["remat"] in ("none", "dots", "full") and "ms a step" in assumed["program"]["note"]
    assert set(cfg["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"} and "seeds" in cfg["limits_note"]
    assert {k: mix[k] for k in ("runner", "seq_len", "walks_per_row", "batch_per_chip", "corpus_rows", "branching",
                                "prefetch", "steps_per_sample")} == {
        "runner": "train_smallthinker_lm", "seq_len": 1024, "walks_per_row": 8, "batch_per_chip": 1, "corpus_rows": 2048,
        "branching": 4, "prefetch": 2, "steps_per_sample": 1,
    }


def test_the_arithmetic_counts_the_parameters_and_the_products_by_hand():
    cfg = real_config()
    d = 2560
    attention = 2 * d * 3584 + 2 * d * 512
    router, expert, norms = d * 64, 3 * d * 768, 2 * d
    assert (attention, router, expert) == (20_971_520, 163_840, 5_898_240)                 # ISSUE 49, leaf by leaf
    layer = attention + router + norms + 16 * expert
    assert attention + router + norms == 21_140_480 and layer == 115_512_320
    assert arithmetic_smallthinker_lm.parameter_count(cfg) == 4 * layer + 2 * 37984 * d + d == 656_529_920
    pairs = arithmetic_smallthinker_lm.pairs_seen(cfg, 8192)
    assert pairs == {"attn_full": 8192 * 8193 / 2, "attn_window": 3 * (4096 * 4097 / 2 + 4096 * 4096)}
    assert round(pairs["attn_window"] / 3 / 1e6, 2) == 25.17 and round(pairs["attn_full"] / 1e6, 2) == 33.56
    parts = arithmetic_smallthinker_lm.forward_flops_per_token(cfg, 8192, 1.5)
    # six a matrix parameter met: the router, the four projections, an expert for each assignment
    assert parts["router"] + parts["attention_projections"] + parts["routed_experts"] == 2 * (4 * (router + attention) + 4 * 1.5 * expert)
    assert parts["attention_products"] == 2 * 2 * 28 * 128 * sum(pairs.values()) / 8192
    assert parts["head"] == 2 * d * 37984 * 8191 / 8192
    tera = {k: round(3 * 8192 * v / 1e12, 2) for k, v in parts.items()}
    assert tera == {"router": 0.03, "attention_projections": 4.12, "attention_products": 4.69, "routed_experts": 1.74, "head": 4.78}
    total = arithmetic_smallthinker_lm.train_flops_per_token(cfg, 8192, 1.5)
    assert 15.3e12 < total * 8192 < 15.4e12            # ISSUE 49 reckoned 15.3 TFLOP a step, 78 ms at the peak
    assert arithmetic_smallthinker_lm.train_flops_per_token(cfg, 8192, 2.5) - total == 3 * 4 * 6 * d * 768
    band = arithmetic_smallthinker_lm.attention_flops(1, cfg, 8192, True)
    full = arithmetic_smallthinker_lm.attention_flops(1, cfg, 8192, False)
    assert band == {"fwd": 4 * 3584 * pairs["attn_window"] / 3, "bwd": 10 * 3584 * pairs["attn_window"] / 3}
    assert full["fwd"] / band["fwd"] == pytest.approx(33.56 / 25.17, rel=1e-3)           # the band is 75% of the triangle
    nbytes = arithmetic_smallthinker_lm.attention_bytes(1, cfg, 8192)
    assert nbytes == {"fwd": 8192 * 128 * 2 * (2 * 28 + 2 * 4), "bwd": 8192 * 128 * 2 * (4 * 28 + 4 * 4)}
    # the MXU binds by an order: 1.8 ms forward on a band against 0.16 ms of bytes at the table's 819 GB/s
    assert band["fwd"] / 197e12 > 10 * nbytes["fwd"] / 819e9


def test_the_readers_price_the_kernels_the_experts_and_the_routing_apart_and_read_nothing_without_a_trace():
    from chipbench import run

    config = real_config()
    facts = {
        "config": config, "mix": real_mix(), "world": 1, "steps": 10, "platform": "tpu",
        "device_kind": "TPU v5 lite", "tokens_per_s": 36000.0, "smallthinker_lm": {"assignments_per_layer_step": 12288.0},
        "trace": {"window_s": 2.0, "smallthinker_kernel_s": {"flash_fwd": 0.12, "flash_bwd_dq": 0.16, "flash_bwd_dkv": 0.20},
                  "smallthinker_expert_s": {"grouped_products": 0.3, "rows": 0.2}, "smallthinker_route_s": {"route": 0.05}},
    }
    read = {name: run.load_reader(name, ROOT / "chipbench" / "metrics").read for name in METRICS}
    got = {name: read[name](facts) for name in METRICS[:5]}
    assert got["smallthinker_attn_time_share"] == pytest.approx(100 * 0.48 / 2.0)
    assert got["smallthinker_expert_time_share"] == pytest.approx(100 * 0.5 / 2.0)
    assert got["smallthinker_route_time_share"] == pytest.approx(100 * 0.05 / 2.0)
    # ten steps; three bands of 25.17 M pairs and one triangle of 33.56 M, seven products of 2 x 3,584 a pair: the MXU binds
    pairs = 3 * (4096 * 4097 / 2 + 4096 * 4096) + 8192 * 8193 / 2
    need = 10 * 7 * 2 * 3584 * pairs / 197e12
    assert got["smallthinker_attn_roofline"] == pytest.approx(100 * need / 0.48) and 50 < got["smallthinker_attn_roofline"] < 100
    assert got["smallthinker_train_mfu"] == pytest.approx(
        100 * 36000 * arithmetic_smallthinker_lm.train_flops_per_token(config, 8192, 1.5) / 197e12
    )
    assert 30 < got["smallthinker_train_mfu"] < 40
    bare = dict(facts, trace=None, platform="cpu")
    assert all(read[name](bare) is None for name in METRICS[:5])
    assert all(read[name](dict(facts, trace={"window_s": 2.0})) is None for name in METRICS[1:5])       # the parent's trace has no such keys
    other_runner = {k: v for k, v in facts.items() if k != "smallthinker_lm"}
    assert all(read[name](other_runner) is None for name in (METRICS[0],) + METRICS[5:])


def test_the_trace_is_read_by_shape_each_event_once_the_routing_apart_and_a_conditionals_children_the_experts():
    """``chipbench/fixtures/smallthinker_step_kernels.json``: instructions of
    the cell's traced step as the chip's profile names them (whole HLO text
    with operand types, no ``op_name``), with the durations it read: the first
    layer's routing forward and backward, two layers' flash kernels, one expert
    layer's backward ``conditional`` with its children over 20 us, two
    bound-sized passes outside it and the head's weight gradient."""
    from chipbench import trace_hybrid_lm, trace_lfm2_lm, trace_reduce
    from chipbench import trace_smallthinker_lm as reader
    from chipbench.runners import train_smallthinker_lm

    trace = trace_reduce.load_json(str(ROOT / "chipbench/fixtures/smallthinker_step_kernels.json"))
    config = real_config()
    events = [e for evs in trace_reduce.device_ops(trace).values() for e in evs]
    assert not any("op_name" in e[0] for e in events)
    shapes = reader.patterns(config, 8192)
    by_part = {}
    for name, _, dur in events:
        by_part.setdefault(reader.part_of(name, *shapes), []).append((name.split(" = ")[0], dur))
    routed = {name.rstrip(".0123456789") for name, _ in by_part["route"]}
    assert routed == {"%iota", "%fusion", "%sort", "%slice_bitcast_fusion", "%multiply_reduce_fusion"}     # logits, top-k, dW_r, dh
    assert not any(name.startswith("%flash") for part in ("route", "grouped_products", "rows") for name, _ in by_part[part])
    kernels = trace_hybrid_lm.kernel_seconds(trace)
    assert all(kernels[k] > 0 for k in trace_reduce.FLASH_KERNELS) and kernels["kda_fwd"] == 0.0
    parents = [e for e in events if e not in trace_lfm2_lm.leaves(events)]
    assert len(parents) == 1 and parents[0][0].startswith("%conditional")
    seconds = reader.part_seconds(trace, config, 8192)
    assert seconds["route"] == pytest.approx(sum(dur for _, dur in by_part["route"]) / 1e9)
    # inside the conditional the stacked weights' casts and the passes on the 24,576 short rows match no shape: told by where they run
    inside = [e for e in events if e is not parents[0] and parents[0][1] <= e[1] < parents[0][1] + parents[0][2]]
    untold = [e for e in inside if reader.part_of(e[0], *shapes) is None]
    assert len(untold) >= 10 and any("bf16[16,2560,768]" in e[0] for e in untold) and any("[24576,2560]" in e[0] for e in untold)
    told = sum(dur for part in ("grouped_products", "rows") for name, dur in by_part[part] if not name.startswith("%conditional"))
    assert seconds["grouped_products"] + seconds["rows"] == pytest.approx((told + sum(e[2] for e in untold)) / 1e9)
    assert sum(e[2] for e in inside) <= parents[0][2]                     # each event once: the children never pass their parent
    said, reduced = [], {}
    train_smallthinker_lm.reduce_trace_for(config, 8192)(trace, reduced, 1, said.append)
    assert reduced["smallthinker_kernel_s"] == {k: kernels[k] for k in trace_reduce.FLASH_KERNELS}
    assert reduced["smallthinker_route_s"] == {"route": seconds["route"]}
    assert sum(reduced["smallthinker_expert_s"].values()) == pytest.approx(seconds["grouped_products"] + seconds["rows"])
    assert any("flash_bwd_dkv" in line for line in said) and any("route: " in line for line in said)
