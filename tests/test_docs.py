"""The docs' code blocks execute — documentation that cannot drift.

Every ```python block in docs/PARALLELISM.md, docs/OPERATIONS.md,
docs/SIMULATION.md, docs/RING.md, docs/QUANT.md, docs/TUNER.md,
docs/OVERLAP.md, docs/LATENCY.md, docs/ELASTIC.md, docs/ADAPT.md,
docs/SUPERVISOR.md, docs/HIERARCHY.md, docs/FABRIC.md, docs/RECOVERY.md,
docs/SERVING.md, docs/COMPILER.md, docs/PIPELINE.md and
docs/OBSERVABILITY.md runs verbatim on the virtual pod.
A snippet that stops compiling or produces wrong shapes fails here.
"""

import os
import re

import pytest

_DOCS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "docs"
)
_PARALLELISM = os.path.join(_DOCS_DIR, "PARALLELISM.md")
_OPERATIONS = os.path.join(_DOCS_DIR, "OPERATIONS.md")
_SIMULATION = os.path.join(_DOCS_DIR, "SIMULATION.md")
_RING = os.path.join(_DOCS_DIR, "RING.md")
_QUANT = os.path.join(_DOCS_DIR, "QUANT.md")
_TUNER = os.path.join(_DOCS_DIR, "TUNER.md")
_OVERLAP = os.path.join(_DOCS_DIR, "OVERLAP.md")
_LATENCY = os.path.join(_DOCS_DIR, "LATENCY.md")
_ELASTIC = os.path.join(_DOCS_DIR, "ELASTIC.md")
_ADAPT = os.path.join(_DOCS_DIR, "ADAPT.md")
_SUPERVISOR = os.path.join(_DOCS_DIR, "SUPERVISOR.md")
_HIERARCHY = os.path.join(_DOCS_DIR, "HIERARCHY.md")
_FABRIC = os.path.join(_DOCS_DIR, "FABRIC.md")
_RECOVERY = os.path.join(_DOCS_DIR, "RECOVERY.md")
_SERVING = os.path.join(_DOCS_DIR, "SERVING.md")
_COMPILER = os.path.join(_DOCS_DIR, "COMPILER.md")
_PIPELINE = os.path.join(_DOCS_DIR, "PIPELINE.md")
_OBSERVABILITY = os.path.join(_DOCS_DIR, "OBSERVABILITY.md")


def _blocks(path):
    text = open(path).read()
    return re.findall(r"```python\n(.*?)```", text, re.DOTALL)


def test_parallelism_doc_has_snippets():
    assert len(_blocks(_PARALLELISM)) >= 6


def test_operations_doc_has_snippets():
    assert len(_blocks(_OPERATIONS)) >= 4


def test_operations_doc_covers_the_contract():
    """The operator topics VERDICT r4 item 8 names must all be present."""
    text = open(_OPERATIONS).read()
    for needle in (
        "ADAPCC_NUM_PROCESSES", "ADAPCC_RESTART_GEN", "ADAPCC_MERGE_ROUNDS",
        "ip_table.txt", "topo_detect_<r>.xml", "logical_graph.xml",
        "strategy.xml", "reconstruct_topology", "chip_smoke.py", "--chips 4",
        "JAX_COMPILATION_CACHE_DIR",
        "chipbench/run.py", "--entry_point", "--dry-run",
        "ADAPCC_DISAGG", "ADAPCC_KV_WIRE_DTYPE", "ADAPCC_KV_KL_BOUND",
        "ADAPCC_PIPE_SCHEDULE", "ADAPCC_IR_OPT",
    ):
        assert needle in text, f"OPERATIONS.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_PARALLELISM))))
def test_parallelism_doc_snippet_runs(idx):
    code = _blocks(_PARALLELISM)[idx]
    exec(compile(code, f"{_PARALLELISM}:block{idx}", "exec"), {})


@pytest.mark.parametrize("idx", range(len(_blocks(_OPERATIONS))))
def test_operations_doc_snippet_runs(idx):
    code = _blocks(_OPERATIONS)[idx]
    exec(compile(code, f"{_OPERATIONS}:block{idx}", "exec"), {})


def test_simulation_doc_has_snippets():
    assert len(_blocks(_SIMULATION)) >= 7


def test_simulation_doc_covers_the_contract():
    """The simulator topics the operator runbook leans on must exist."""
    text = open(_SIMULATION).read()
    for needle in (
        '"mode": "simulated"', "pred_time_us", "topology/calibration.json",
        "sim-rank", "calibrate_from_battery", "make sim-bench",
        "relay_latency", "predict_degradation",
        # §7 scaling and certification
        "ADAPCC_SIM_ENGINE", "VECTOR_MIN_WORLD", "optimality_gap",
        "lowering_cache_info", "make simscale-bench",
        "within_replay_budget_s",
    ):
        assert needle in text, f"SIMULATION.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_SIMULATION))))
def test_simulation_doc_snippet_runs(idx):
    code = _blocks(_SIMULATION)[idx]
    exec(compile(code, f"{_SIMULATION}:block{idx}", "exec"), {})


def test_ring_doc_has_snippets():
    assert len(_blocks(_RING)) >= 5


def test_ring_doc_covers_the_contract():
    """The staged-pipeline topics the tuning runbook leans on must exist."""
    text = open(_RING).read()
    for needle in (
        "hbm-stream", "vmem", "chunk_bytes", "ADAPCC_RING_CHUNK_BYTES",
        "plan_ring_schedule", "make ring-sweep", "Zero1Optimizer",
        "ring_chunk_sweep", "credit", "c_m",
    ):
        assert needle in text, f"RING.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_RING))))
def test_ring_doc_snippet_runs(idx):
    code = _blocks(_RING)[idx]
    exec(compile(code, f"{_RING}:block{idx}", "exec"), {})


def test_quant_doc_has_snippets():
    assert len(_blocks(_QUANT)) >= 5


def test_quant_doc_covers_the_contract():
    """The wire-codec topics the quantization runbook leans on must exist."""
    text = open(_QUANT).read()
    for needle in (
        "block_size", "wire_dtype", "ADAPCC_WIRE_DTYPE", "error_feedback",
        "error-feedback", "sim-rank", "make quant-bench", "int8",
        "stochastic", "choose_wire_dtype", "p99",
    ):
        assert needle in text, f"QUANT.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_QUANT))))
def test_quant_doc_snippet_runs(idx):
    code = _blocks(_QUANT)[idx]
    exec(compile(code, f"{_QUANT}:block{idx}", "exec"), {})


def test_tuner_doc_has_snippets():
    assert len(_blocks(_TUNER)) >= 4


def test_tuner_doc_covers_the_contract():
    """The autotuner topics the tuning runbook leans on must exist."""
    text = open(_TUNER).read()
    for needle in (
        "ADAPCC_TUNER", "ADAPCC_TUNER_DB", "topology/tuning.jsonl",
        "trial_budget", "hysteresis", "explore", "measured", "prior",
        "size_bucket", "replay_trace", "make tune-bench",
        "make trace-export", "block_until_ready",
        "tuner > strategy",
    ):
        assert needle in text, f"TUNER.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_TUNER))))
def test_tuner_doc_snippet_runs(idx):
    code = _blocks(_TUNER)[idx]
    exec(compile(code, f"{_TUNER}:block{idx}", "exec"), {})


def test_overlap_doc_has_snippets():
    assert len(_blocks(_OVERLAP)) >= 4


def test_overlap_doc_covers_the_contract():
    """The overlapped-sync topics the tuning runbook leans on must exist."""
    text = open(_OVERLAP).read()
    for needle in (
        "ADAPCC_OVERLAP", "microbatch", "bucket", "chunk_bytes",
        "overlapped_step_time", "exposed_comm_s", "make overlap-bench",
        "bitwise", "error_feedback", "hook-bucket", "Zero1Optimizer",
        "MetricsRegistry",
    ):
        assert needle in text, f"OVERLAP.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_OVERLAP))))
def test_overlap_doc_snippet_runs(idx):
    code = _blocks(_OVERLAP)[idx]
    exec(compile(code, f"{_OVERLAP}:block{idx}", "exec"), {})


def test_latency_doc_has_snippets():
    assert len(_blocks(_LATENCY)) >= 5


def test_latency_doc_covers_the_contract():
    """The small-message-regime topics the selection runbook leans on."""
    text = open(_LATENCY).read()
    for needle in (
        "ADAPCC_COLL_ALGO", "rd_allreduce_shard", "recursive",
        "binomial", "allreduce_crossover_bytes", "crossover_bytes",
        "make latency-bench", "all_to_all",
        "expert_a2a", "power-of-two", "env > explicit arg > tuner",
    ):
        assert needle in text, f"LATENCY.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_LATENCY))))
def test_latency_doc_snippet_runs(idx):
    code = _blocks(_LATENCY)[idx]
    exec(compile(code, f"{_LATENCY}:block{idx}", "exec"), {})


def test_elastic_doc_has_snippets():
    assert len(_blocks(_ELASTIC)) >= 4


def test_elastic_doc_covers_the_contract():
    """The failover topics the elastic runbook leans on must exist."""
    text = open(_ELASTIC).read()
    for needle in (
        "ADAPCC_FAULT_PLAN", "ADAPCC_HEARTBEAT_TIMEOUT_S",
        "ADAPCC_SLOW_RANK_FACTOR", "WorldView", "epoch", "EpochMismatch",
        "StandbyPlanCache", "cache_hit", "FaultPlan", "make elastic-bench",
        "reshard_zero1_snapshot", "apply_snapshot",
        "failover_cost", "simulate_fault_plan",
    ):
        assert needle in text, f"ELASTIC.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_ELASTIC))))
def test_elastic_doc_snippet_runs(idx):
    code = _blocks(_ELASTIC)[idx]
    exec(compile(code, f"{_ELASTIC}:block{idx}", "exec"), {})


def test_adapt_doc_has_snippets():
    assert len(_blocks(_ADAPT)) >= 5


def test_adapt_doc_covers_the_contract():
    """The closed-adaptation-loop topics the runbook leans on must exist."""
    text = open(_ADAPT).read()
    for needle in (
        "ADAPCC_ADAPT", "ADAPCC_DRIFT_FACTOR", "ADAPCC_DRIFT_WINDOW",
        "DriftDetector", "drift_correction", "merge_calibration",
        "resynthesize", "warm_strategy", "advance_epoch", "cache_hit",
        "hysteresis", "make adapt-bench",
        "fingerprint", "zero probe traffic",
    ):
        assert needle in text, f"ADAPT.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_ADAPT))))
def test_adapt_doc_snippet_runs(idx):
    code = _blocks(_ADAPT)[idx]
    exec(compile(code, f"{_ADAPT}:block{idx}", "exec"), {})


def test_supervisor_doc_has_snippets():
    assert len(_blocks(_SUPERVISOR)) >= 5


def test_supervisor_doc_covers_the_contract():
    """The out-of-band supervision topics the runbook leans on."""
    text = open(_SUPERVISOR).read()
    for needle in (
        "ADAPCC_SUPERVISOR", "ADAPCC_RPC_TIMEOUT_S",
        "ADAPCC_HEARTBEAT_TIMEOUT_S", "ADAPCC_HEARTBEAT_PERIOD_S",
        "ADAPCC_HEARTBEAT_GRACE", "CoordinatorUnavailable",
        "HeartbeatClient", "LivenessTable", "DecisionJournal", "fsync",
        "zero duplicate epoch bumps", "chaos_schedule", "SIGKILL",
        "SIGSTOP", "cache_hit", "make chaos-bench",
        "attach_supervisor", "train_ddp --supervisor",
    ):
        assert needle in text, f"SUPERVISOR.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_SUPERVISOR))))
def test_supervisor_doc_snippet_runs(idx):
    code = _blocks(_SUPERVISOR)[idx]
    exec(compile(code, f"{_SUPERVISOR}:block{idx}", "exec"), {})


def test_hierarchy_doc_has_snippets():
    assert len(_blocks(_HIERARCHY)) >= 5


def test_hierarchy_doc_covers_the_contract():
    """The pod-scale synthesis topics the hierarchy story leans on."""
    text = open(_HIERARCHY).read()
    for needle in (
        "ADAPCC_HIER_SKETCH", "HierarchySketch", "synthesize_two_level",
        "resolve_leader_level", "MILP_SYNTH_BUDGET_S", "ragged",
        "two_level_allreduce_time", "choose_two_level",
        "two_level_crossover_pods", "psum_scatter", "cache_hit",
        "resolved_level", "make hier-bench", "two_level_synth",
        "plan_of", "leader_projection", "4096",
    ):
        assert needle in text, f"HIERARCHY.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_HIERARCHY))))
def test_hierarchy_doc_snippet_runs(idx):
    code = _blocks(_HIERARCHY)[idx]
    exec(compile(code, f"{_HIERARCHY}:block{idx}", "exec"), {})


def test_fabric_doc_has_snippets():
    assert len(_blocks(_FABRIC)) >= 5


def test_fabric_doc_covers_the_contract():
    """The multi-tenant fabric topics the triage/QoS story leans on."""
    text = open(_FABRIC).read()
    for needle in (
        "ADAPCC_CONGESTION_PROFILE", "ADAPCC_JOB_PRIORITY",
        "CongestionProfile", "contended_coeffs", "classify_drift",
        "congestion-reroute", "congestion-cleared", "byte-untouched",
        "resolve_leader_level", "synthesize_two_level", "SharedFabric",
        "hot_links", "high_beats_uncoordinated", "make fabric-bench",
        "fabric_contention", "load_env_json_artifact", "cache_hit",
        "simulate_congestion_profile",
    ):
        assert needle in text, f"FABRIC.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_FABRIC))))
def test_fabric_doc_snippet_runs(idx):
    code = _blocks(_FABRIC)[idx]
    exec(compile(code, f"{_FABRIC}:block{idx}", "exec"), {})


def test_recovery_doc_has_snippets():
    assert len(_blocks(_RECOVERY)) >= 5


def test_recovery_doc_covers_the_contract():
    """The durable-recovery topics the replication/checkpoint/rejoin
    story leans on."""
    text = open(_RECOVERY).read()
    for needle in (
        "ADAPCC_SHARD_REPLICAS", "ADAPCC_ASYNC_CKPT",
        "ADAPCC_RPC_TIMEOUT_S", "replica_placement", "ShardReplicaStore",
        "recover_zero1_trainer_state", "grow_zero1_trainer_state",
        "restore_newest_across_processes", "AsyncCheckpointManager",
        "CheckpointCorrupt", "MANIFEST.json", "keep-last-good",
        "latest_good_step", "admit", "restart_generation",
        "mark_recovered", "restore_full", "cache_hit",
        "replication_overhead_time", "recovery_cost",
        "make recovery-bench",
    ):
        assert needle in text, f"RECOVERY.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_RECOVERY))))
def test_recovery_doc_snippet_runs(idx):
    code = _blocks(_RECOVERY)[idx]
    exec(compile(code, f"{_RECOVERY}:block{idx}", "exec"), {})


def test_serving_doc_has_snippets():
    assert len(_blocks(_SERVING)) >= 5


def test_serving_doc_covers_the_contract():
    """The serving-plane topics the latency-SLO story leans on."""
    text = open(_SERVING).read()
    for needle in (
        "ADAPCC_SERVE_TRACE", "ADAPCC_SERVE_SLOTS", "ADAPCC_SERVE_SLO_MS",
        "ADAPCC_TUNER_OBJECTIVE", "synthesize_arrival_trace",
        "SlotKVCache", "GPT2Server", "continuous batch", "evict-on-EOS",
        "bit-identical", "head-sharded", "simulate_serve_queue",
        "serve_queue_metrics", "decode_step_time", "make serve-bench",
        "small-message", "p99", "without retracing",
        # the disaggregated plane (§7)
        "ClusterRouter", "kv_transfer", "simulate_disagg_queue",
        "ADAPCC_DISAGG", "ADAPCC_KV_WIRE_DTYPE", "ADAPCC_KV_KL_BOUND",
        "make disagg-bench", "KL", "measure_token_kl",
        "bit-identical",
    ):
        assert needle in text, f"SERVING.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_SERVING))))
def test_serving_doc_snippet_runs(idx):
    code = _blocks(_SERVING)[idx]
    exec(compile(code, f"{_SERVING}:block{idx}", "exec"), {})


def test_compiler_doc_has_snippets():
    assert len(_blocks(_COMPILER)) >= 8


def test_compiler_doc_covers_the_contract():
    """The schedule-compiler topics the one-IR story leans on."""
    text = open(_COMPILER).read()
    for needle in (
        "ScheduleProgram", "verify_program", "fingerprint",
        "algo=\"ir\"", "ADAPCC_COLL_ALGO=ir", "set_schedule_program",
        "schedule_program_time", "simulate_program", "emit_program_xml",
        "parse_program_xml", "pipelined", "relay", "rank, round, chunk",
        "make compiler-bench", "IR_PATH", "schema",
        "lockstep",
        # the optimizer (PR 20): the pass pipeline and its knob
        "ADAPCC_IR_OPT", "optimize_program", "coalesce", "fuse_codec",
        "dce", "dispatch_count", "IR_OPT_PATH", "applied_passes",
        "two_level_color_axes", "per_dispatch_s",
    ):
        assert needle in text, f"COMPILER.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_COMPILER))))
def test_compiler_doc_snippet_runs(idx):
    code = _blocks(_COMPILER)[idx]
    exec(compile(code, f"{_COMPILER}:block{idx}", "exec"), {})


def test_pipeline_doc_has_snippets():
    assert len(_blocks(_PIPELINE)) >= 6


def test_pipeline_doc_covers_the_contract():
    """The pipeline-parallel topics the one-schedule-four-places story leans on."""
    text = open(_PIPELINE).read()
    for needle in (
        "pipeline_schedule", "pipeline_program", "verify_program",
        "PipelineExecutor", "partition_gpt2", "split_params", "merge_params",
        "pipe_send", "total_sends", "stash_high_water",
        "min(m, stages - stage)", "bubble", "1f1b", "gpipe",
        "pipeline_step_time", "pipeline_stash_bytes", "simulate_program",
        "ADAPCC_PIPE_SCHEDULE", "resolve_pipe_schedule", "pipe_step",
        "pipe-gpipe", "pipe-1f1b", "--pp-stages", "--pp-microbatches",
        "--pp-schedule", "make pipe-bench", "grad_sync",
        "rank, round, chunk", "head_wte", "pipeline_apply",
    ):
        assert needle in text, f"PIPELINE.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_PIPELINE))))
def test_pipeline_doc_snippet_runs(idx):
    code = _blocks(_PIPELINE)[idx]
    exec(compile(code, f"{_PIPELINE}:block{idx}", "exec"), {})


def test_observability_doc_names_everything_the_program_records():
    """One page, linked from the operations guide, that lists every span,
    counter, gauge and sample the training path records and every name it
    puts on the device side."""
    text = open(_OBSERVABILITY).read()
    assert "OBSERVABILITY.md" in open(_OPERATIONS).read()
    for needle in (
        "default_registry", "profiler_trace", "is_enabled", "start_server",
        "step.prepare", "step.enqueue", "step.finish", "data.pull",
        "data.queue_depth", "data.h2d", "data.h2d_bytes", "grad_sync.bytes",
        "grad_sync.calls", "bucket_plan.bucket_bytes", "jit_ddp_step",
        "grad_sync", "optimizer", "lm_head", "loss", "flash_fwd",
        "flash_bwd_dq", "flash_bwd_dkv", "Perfetto", "step_enqueue_ms",
        "step_host_self_ms", "input_queue_depth", "input_h2d_ms",
        "grad_sync_bytes_per_step", "grad_sync_calls_per_step",
        "step.build", "step.recompiles", "compile.backend", "step_trace_lower_s",
        "step_load_s", "step_cache_misses", "step_recompiles",
    ):
        assert needle in text, f"OBSERVABILITY.md lost its {needle!r} coverage"


@pytest.mark.parametrize("idx", range(len(_blocks(_OBSERVABILITY))))
def test_observability_doc_snippet_runs(idx):
    code = _blocks(_OBSERVABILITY)[idx]
    exec(compile(code, f"{_OBSERVABILITY}:block{idx}", "exec"), {})
