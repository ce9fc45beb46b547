"""Pipeline-parallel training plane (docs/PIPELINE.md).

One schedule object, four places, all pinned here: the tick table's
invariants (ticks, bubble, stash windows), the emitted ``pipeline``
ScheduleProgram and its verifier's p2p rejections, the executor's
bit-parity against the composed single-stage math (with the tied
embedding's Megatron-style gradient exchange), the traced ``pipe_send``
hops, the closed-form pricing twins, the env > arg > tuner schedule
resolution, and the DP×PP grad-sync composition.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.engine import CollectiveEngine
from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.compiler.verify import ScheduleVerificationError, verify_program
from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from adapcc_tpu.pipe import (
    DEFAULT_PIPE_SCHEDULE,
    PIPE_SCHEDULE_ENV,
    PIPE_SCHEDULES,
    PipeTask,
    PipelineExecutor,
    composed_loss,
    merge_params,
    partition_gpt2,
    pipeline_program,
    pipeline_schedule,
    resolve_pipe_schedule,
    split_params,
    sync_tied_embedding,
)
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.observability import CollectiveTrace

CFG = GPT2Config.tiny()


def _params(cfg=CFG, seed=0):
    return GPT2(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )


def _tokens(cfg=CFG, batch=4, T=16, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, T), 0, cfg.vocab_size
    )


# --------------------------------------------------------------------------- #
# tick tables
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", PIPE_SCHEDULES)
@pytest.mark.parametrize("stages,microbatches", [(2, 2), (2, 4), (4, 4), (4, 8)])
def test_schedule_ticks_and_bubble_closed_forms(kind, stages, microbatches):
    """Both schedules run 2·(m+s−1) ticks; the measured bubble equals the
    closed form (s−1)/(m+s−1)."""
    sched = pipeline_schedule(stages, microbatches, kind)
    assert sched.num_ticks == 2 * (microbatches + stages - 1)
    want = (stages - 1) / (microbatches + stages - 1)
    assert sched.bubble_fraction == pytest.approx(want, abs=1e-12)


def test_schedule_stash_windows():
    """GPipe stashes all m per stage; 1F1B bounds stage s to
    min(m, stages − s) — the memory axis that separates the schedules."""
    assert pipeline_schedule(4, 8, "gpipe").stash_high_water == (8, 8, 8, 8)
    assert pipeline_schedule(4, 8, "1f1b").stash_high_water == (4, 3, 2, 1)
    assert pipeline_schedule(2, 4, "1f1b").stash_high_water == (2, 1)
    for s, m in [(2, 4), (4, 8), (4, 4)]:
        g = pipeline_schedule(s, m, "gpipe").stash_high_water
        f = pipeline_schedule(s, m, "1f1b").stash_high_water
        assert all(fi <= gi for fi, gi in zip(f, g))
        assert sum(f) < sum(g)


def test_schedule_rejects_malformed_shapes():
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        pipeline_schedule(2, 2, "wavefront")
    with pytest.raises(ValueError, match="stages"):
        pipeline_schedule(0, 2)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_schedule(2, 0)
    with pytest.raises(ValueError, match="unknown task kind"):
        PipeTask("fwdbwd", 0)


def test_schedule_tick_rows_respect_dependencies():
    """A stage's forward for microbatch m must run strictly after the
    upstream stage's — the hop needs a tick boundary to cross."""
    for kind in PIPE_SCHEDULES:
        sched = pipeline_schedule(3, 4, kind)
        seen = {}
        for t, row in enumerate(sched.ticks):
            for s, task in enumerate(row):
                if task is None:
                    continue
                if task.kind == "fwd" and s > 0:
                    assert seen[("fwd", s - 1, task.mb)] < t
                if task.kind == "bwd":
                    assert seen[("fwd", s, task.mb)] < t
                    if s < sched.stages - 1:
                        assert seen[("bwd", s + 1, task.mb)] < t
                seen[(task.kind, s, task.mb)] = t


# --------------------------------------------------------------------------- #
# the emitted ScheduleProgram + p2p verification
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", PIPE_SCHEDULES)
def test_pipeline_program_verifies_and_counts_sends(kind):
    sched = pipeline_schedule(4, 4, kind)
    prog = pipeline_program(sched, tied_embedding=True)
    verify_program(prog)
    assert prog.collective == "pipeline"
    assert prog.world == 4
    # m fwd hops per stage boundary + m bwd hops + the tied-embed exchange
    assert prog.total_sends() == 4 * (4 - 1) * 2 + 1
    assert prog.chunks == 2 * 4 + 1
    assert prog.chunk_sources[:4] == (0, 0, 0, 0)
    assert prog.chunk_sinks[:4] == (3, 3, 3, 3)
    assert prog.chunk_sources[4:8] == (3, 3, 3, 3)
    assert prog.chunk_sinks[-1] == 0
    # emission is deterministic: same table → same fingerprint
    assert prog.fingerprint() == pipeline_program(
        pipeline_schedule(4, 4, kind), tied_embedding=True
    ).fingerprint()


def test_pipeline_program_rejects_degenerate_shapes():
    with pytest.raises(ValueError, match="no hops"):
        pipeline_program(pipeline_schedule(1, 4))
    with pytest.raises(ValueError, match="cannot host"):
        pipeline_program(pipeline_schedule(4, 2), world=3)


def _first_hop_round(prog):
    for i, rnd in enumerate(prog.rounds):
        if any(s.kind == "send" for s in rnd):
            return i
    raise AssertionError("program has no sends")


def test_verifier_rejects_dropped_recv():
    """Deleting one recv drops the sent payload; the rejection names the
    (rank, round, chunk)."""
    prog = pipeline_program(pipeline_schedule(2, 4, "1f1b"), tied_embedding=True)
    broken = tuple(
        tuple(s for s in rnd if not (s.kind == "recv" and s.chunk == 0))
        for rnd in prog.rounds
    )
    with pytest.raises(ScheduleVerificationError) as e:
        verify_program(dataclasses.replace(prog, rounds=broken))
    msg = str(e.value)
    assert "rank=" in msg and "round=" in msg and "chunk=" in msg
    assert "dropped" in msg


def test_verifier_rejects_mismatched_round():
    """Moving a recv+copy pair one round later leaves its send unmatched in
    the barrier round it actually runs in."""
    prog = pipeline_program(pipeline_schedule(2, 4, "gpipe"), tied_embedding=True)
    i = _first_hop_round(prog)
    rounds = [list(r) for r in prog.rounds]
    moved = [s for s in rounds[i] if s.kind in ("recv", "copy") and s.chunk == 0]
    assert moved, "expected chunk 0's recv/copy in the first hop round"
    rounds[i] = [s for s in rounds[i] if s not in moved]
    rounds[i + 1] = list(rounds[i + 1]) + moved
    with pytest.raises(ScheduleVerificationError) as e:
        verify_program(
            dataclasses.replace(prog, rounds=tuple(tuple(r) for r in rounds))
        )
    msg = str(e.value)
    assert "rank=" in msg and f"round={i}" in msg and "chunk=" in msg
    assert "no matching recv" in msg


def test_verifier_rejects_deadlocked_pair():
    """A recv whose send never ran in its round can never be satisfied —
    rounds are barriers, and the verifier says 'deadlock' outright."""
    prog = pipeline_program(pipeline_schedule(2, 4, "1f1b"), tied_embedding=True)
    broken = tuple(
        tuple(s for s in rnd if not (s.kind == "send" and s.chunk == 0))
        for rnd in prog.rounds
    )
    with pytest.raises(ScheduleVerificationError) as e:
        verify_program(dataclasses.replace(prog, rounds=broken))
    msg = str(e.value)
    assert "rank=" in msg and "round=" in msg and "chunk=" in msg
    assert "deadlock" in msg


def test_verifier_rejects_use_before_receive():
    """Swapping a forward chunk's two hops sends a payload the stage does
    not hold yet — the routed custody check catches the ordering bug."""
    prog = pipeline_program(pipeline_schedule(3, 2, "gpipe"))
    hops = [
        (i, s)
        for i, rnd in enumerate(prog.rounds)
        for s in rnd
        if s.kind == "send" and s.chunk == 0
    ]
    assert len(hops) == 2  # stage 0→1 then 1→2
    (i0, _), (i1, _) = hops
    rounds = [list(r) for r in prog.rounds]
    # swap the two hop rounds wholesale for chunk 0: the 1→2 hop now runs
    # before stage 1 ever received the payload
    r0 = [s for s in rounds[i0] if s.chunk == 0]
    r1 = [s for s in rounds[i1] if s.chunk == 0]
    rounds[i0] = [s for s in rounds[i0] if s.chunk != 0] + r1
    rounds[i1] = [s for s in rounds[i1] if s.chunk != 0] + r0
    with pytest.raises(ScheduleVerificationError, match="before holding it"):
        verify_program(
            dataclasses.replace(prog, rounds=tuple(tuple(r) for r in rounds))
        )


# --------------------------------------------------------------------------- #
# stage partitioning
# --------------------------------------------------------------------------- #

def test_partition_balances_and_rejects():
    part = partition_gpt2(CFG, 2)
    assert part.block_ranges == ((0, 1), (1, 2))
    assert len(part.param_counts) == 2
    with pytest.raises(ValueError, match="un-splittable"):
        partition_gpt2(CFG, CFG.n_layer + 1)
    with pytest.raises(ValueError, match="num_stages"):
        partition_gpt2(CFG, 0)
    with pytest.raises(ValueError, match="dropout"):
        partition_gpt2(dataclasses.replace(CFG, dropout=0.1), 2)
    with pytest.raises(ValueError, match="sequence"):
        partition_gpt2(dataclasses.replace(CFG, sp_axis="sp"), 2)


def test_partition_balance_spreads_remainder():
    """With 5 blocks over 2 stages the extra block lands on the lighter
    stage, not blindly on stage 0 (the embedding already weighs it)."""
    cfg = dataclasses.replace(CFG, n_layer=5)
    part = partition_gpt2(cfg, 2)
    assert [hi - lo for lo, hi in part.block_ranges] in ([2, 3], [3, 2])
    assert sum(hi - lo for lo, hi in part.block_ranges) == 5
    # contiguity
    assert part.block_ranges[0][1] == part.block_ranges[1][0]


def test_composed_loss_is_the_model_bit_for_bit():
    params = _params()
    part = partition_gpt2(CFG, 2)
    sp = split_params(params, part)
    toks = _tokens()
    a = composed_loss(CFG, part, sp, toks)
    b = lm_loss(GPT2(CFG).apply(params, toks), toks)
    assert jnp.array_equal(a, b)


def test_split_merge_round_trip():
    params = _params()
    part = partition_gpt2(CFG, 2)
    sp = split_params(params, part)
    assert "head_wte" in sp[-1]  # the tied copy rides the last stage
    merged = merge_params(sp, part)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(jnp.array_equal, merged, params)
    )


# --------------------------------------------------------------------------- #
# pipe_send: the traced p2p primitive
# --------------------------------------------------------------------------- #

def test_pipe_send_moves_one_row_and_traces(mesh4):
    trace = CollectiveTrace()
    eng = CollectiveEngine(
        mesh4, Strategy.ring(4), use_xla_fastpath=False, trace=trace
    )
    buf = jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 3)
    out = eng.pipe_send(buf, src=1, dst=3, kind="activation", mb=0, tick=2)
    assert jnp.array_equal(out[3], buf[1])
    for r in (0, 1, 2):
        assert jnp.array_equal(out[r], buf[r])
    ev = [e for e in trace.events() if e.primitive == "pipe_send"][-1]
    assert ev.impl == "ici_hop"
    assert ev.nbytes == int(buf[1].nbytes)  # one row, not the stacked buffer
    assert ev.extra["src"] == 1 and ev.extra["dst"] == 3
    assert ev.extra["kind"] == "activation"
    assert ev.extra["mb"] == 0 and ev.extra["tick"] == 2


def test_pipe_send_validates_route_and_kind(mesh4):
    eng = CollectiveEngine(mesh4, Strategy.ring(4), use_xla_fastpath=False)
    buf = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="src=4 outside world"):
        eng.pipe_send(buf, src=4, dst=0)
    with pytest.raises(ValueError, match="dst=-1 outside world"):
        eng.pipe_send(buf, src=0, dst=-1)
    with pytest.raises(ValueError, match="src == dst"):
        eng.pipe_send(buf, src=2, dst=2)
    with pytest.raises(ValueError, match="kind"):
        eng.pipe_send(buf, src=0, dst=1, kind="payload")


# --------------------------------------------------------------------------- #
# the executor: parity, stash, traced hops
# --------------------------------------------------------------------------- #

def _microbatched_baseline(part, stage_params, tokens, M):
    """The composed single-process twin of forward_backward: per-microbatch
    value_and_grad of the composed loss, accumulated in microbatch order,
    with the same tied-embedding fold."""
    B = tokens.shape[0]
    mb = tokens.reshape(M, B // M, *tokens.shape[1:])
    loss = None
    grads = None
    for m in range(M):
        l, g = jax.value_and_grad(
            lambda sp: composed_loss(CFG, part, sp, mb[m])
        )(stage_params)
        loss = l if loss is None else loss + l
        grads = (
            g if grads is None
            else jax.tree_util.tree_map(jnp.add, grads, g)
        )
    loss = loss / M
    grads = jax.tree_util.tree_map(lambda x: x / M, grads)
    head_g = grads[-1]["head_wte"]["embedding"]
    grads[0]["wte"]["embedding"] = grads[0]["wte"]["embedding"] + head_g
    grads[-1]["head_wte"]["embedding"] = jnp.zeros_like(head_g)
    return loss, grads


@pytest.mark.parametrize("kind", PIPE_SCHEDULES)
def test_executor_bit_matches_composed_microbatched_baseline(mesh2, kind):
    """The pipelined step IS the composed microbatched step: same stage
    functions, same accumulation order, hops are bit-exact moves — so loss
    and every per-stage gradient leaf match to the bit, under BOTH
    schedules."""
    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    params = _params()
    sp = split_params(params, part)
    toks = _tokens(batch=4)

    ex = PipelineExecutor(CFG, part, eng, num_microbatches=2, schedule=kind)
    loss, grads, report = ex.forward_backward(sp, toks)
    base_loss, base_grads = _microbatched_baseline(part, sp, toks, 2)

    assert jnp.array_equal(loss, base_loss)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(jnp.array_equal, grads, base_grads)
    )
    assert report.schedule == kind
    assert report.ticks == 2 * (2 + 2 - 1)
    assert report.hops == ex.program.total_sends()


def test_gpipe_and_1f1b_gradients_are_bit_identical(mesh2):
    """Same microbatch accumulation order under both schedules → the
    schedule choice moves memory, never the math."""
    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    sp = split_params(_params(), part)
    toks = _tokens(batch=4)
    out = {}
    for kind in PIPE_SCHEDULES:
        ex = PipelineExecutor(CFG, part, eng, num_microbatches=4, schedule=kind)
        out[kind] = ex.forward_backward(sp, toks)
    lg, gg, rg = out["gpipe"]
    lf, gf, rf = out["1f1b"]
    assert jnp.array_equal(lg, lf)
    assert jax.tree_util.tree_all(
        jax.tree_util.tree_map(jnp.array_equal, gg, gf)
    )
    # ... but memory differs: the 1F1B stash is strictly smaller in total
    assert rg.stash_peak == (4, 4)
    assert rf.stash_peak == (2, 1)
    assert sum(rf.stash_peak_bytes) < sum(rg.stash_peak_bytes)


def test_executor_matches_full_batch_model_grads(mesh2):
    """Against the UN-microbatched single-stage model the pipeline is
    tolerance-pinned, not bit-pinned: microbatch accumulation reorders the
    fp32 sums (the same noise a plain grad-accum trainer has)."""
    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    params = _params()
    sp = split_params(params, part)
    toks = _tokens(batch=4)

    ex = PipelineExecutor(CFG, part, eng, num_microbatches=2, schedule="1f1b")
    loss, grads, _ = ex.forward_backward(sp, toks)

    model = GPT2(CFG)
    full_loss, full_grads = jax.value_and_grad(
        lambda p: lm_loss(model.apply(p, toks), toks)
    )(params)
    assert jnp.allclose(loss, full_loss, atol=1e-5)
    merged = merge_params(grads, part)
    flat_a = jax.tree_util.tree_leaves(merged)
    flat_b = jax.tree_util.tree_leaves(full_grads)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)


def test_executor_hops_land_in_the_dispatch_trace(mesh2):
    trace = CollectiveTrace()
    eng = CollectiveEngine(
        mesh2, Strategy.ring(2), use_xla_fastpath=False, trace=trace
    )
    part = partition_gpt2(CFG, 2)
    sp = split_params(_params(), part)
    ex = PipelineExecutor(CFG, part, eng, num_microbatches=2, schedule="1f1b")
    _, _, report = ex.forward_backward(sp, _tokens(batch=4))

    events = [e for e in trace.events() if e.primitive == "pipe_send"]
    assert len(events) == report.hops == ex.program.total_sends()
    kinds = [e.extra["kind"] for e in events]
    assert kinds.count("activation") == 2  # M fwd hops across the one cut
    assert kinds.count("grad") == 2
    assert kinds.count("tied_embed") == 1
    for e in events:
        assert 0 <= e.extra["src"] < 2 and 0 <= e.extra["dst"] < 2
        assert e.nbytes > 0


def test_executor_rejects_malformed_shapes(mesh2):
    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    with pytest.raises(ValueError, match="num_microbatches"):
        PipelineExecutor(CFG, part, eng, num_microbatches=0)
    part4 = partition_gpt2(dataclasses.replace(CFG, n_layer=4), 4)
    with pytest.raises(ValueError, match="cannot host"):
        PipelineExecutor(CFG, part4, eng)
    ex = PipelineExecutor(CFG, part, eng, num_microbatches=2)
    with pytest.raises(ValueError, match="not divisible"):
        ex.forward_backward(split_params(_params(), part), _tokens(batch=3))


def test_sync_tied_embedding_refreshes_the_head_copy():
    part = partition_gpt2(CFG, 2)
    sp = split_params(_params(), part)
    sp[0]["wte"]["embedding"] = sp[0]["wte"]["embedding"] + 1.0
    sync_tied_embedding(sp)
    assert jnp.array_equal(
        sp[-1]["head_wte"]["embedding"], sp[0]["wte"]["embedding"]
    )


# --------------------------------------------------------------------------- #
# DP×PP: the grad_sync attach point
# --------------------------------------------------------------------------- #

def test_dp_pp_composition_matches_full_batch_pipeline(mesh2):
    """Two data-parallel pipeline replicas on batch halves, per-stage grads
    averaged through the DDP hook's device half — the composed DP×PP
    gradient equals the full-batch pipeline's to accumulation-order
    tolerance."""
    from adapcc_tpu.ddp.hook import GradSyncHook

    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    sp = split_params(_params(), part)
    toks = _tokens(batch=8)
    half_a, half_b = toks[:4], toks[4:]

    ex = PipelineExecutor(CFG, part, eng, num_microbatches=2, schedule="1f1b")
    _, grads_b, _ = ex.forward_backward(sp, half_b)

    # psum mode: stateless per-leaf sync, so one hook serves every stage's
    # differently-shaped gradient pytree
    hook = GradSyncHook(Strategy.ring(2), mode="psum")
    hook_fn = jax.jit(jax.shard_map(
        hook.sync,
        mesh=mesh2,
        in_specs=(P(RANKS_AXIS), P()),
        out_specs=P(RANKS_AXIS),
        check_vma=False,
    ))
    mask = jnp.ones((2,), dtype=bool)
    stage_iter = iter(range(part.num_stages))

    def dp_sync(gs):
        s = next(stage_iter)
        stacked = jax.tree_util.tree_map(
            lambda a, b: jnp.stack([a, b]), gs, grads_b[s]
        )
        synced = hook_fn(stacked, mask)
        return jax.tree_util.tree_map(lambda x: x[0], synced)

    _, grads_dp, _ = ex.forward_backward(sp, half_a, grad_sync=dp_sync)

    ex_full = PipelineExecutor(
        CFG, part, eng, num_microbatches=4, schedule="1f1b"
    )
    _, grads_full, _ = ex_full.forward_backward(sp, toks)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads_dp),
        jax.tree_util.tree_leaves(grads_full),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


# --------------------------------------------------------------------------- #
# schedule resolution: env > arg > tuner > default
# --------------------------------------------------------------------------- #

def test_resolve_env_beats_arg_and_malformed_is_loud(monkeypatch):
    monkeypatch.setenv(PIPE_SCHEDULE_ENV, "gpipe")
    assert resolve_pipe_schedule("1f1b") == "gpipe"
    monkeypatch.setenv(PIPE_SCHEDULE_ENV, "Wavefront")
    with pytest.raises(ValueError, match=PIPE_SCHEDULE_ENV):
        resolve_pipe_schedule()
    monkeypatch.delenv(PIPE_SCHEDULE_ENV)
    assert resolve_pipe_schedule("gpipe") == "gpipe"
    with pytest.raises(ValueError, match="pipe schedule"):
        resolve_pipe_schedule("wavefront")
    assert resolve_pipe_schedule() == DEFAULT_PIPE_SCHEDULE == "1f1b"


def _pipe_cell(schedule, world, microbatches, topology=""):
    from adapcc_tpu.pipe.schedule import PIPE_PRIMITIVE
    from adapcc_tpu.tuner.db import TuningKey, size_bucket
    from adapcc_tpu.tuner.policy import pipe_path

    return TuningKey(
        primitive=PIPE_PRIMITIVE,
        size_bucket=size_bucket(0),
        world=world,
        topology=topology,
        path=pipe_path(schedule),
        chunk_bytes=microbatches,
        wire_dtype="off",
    )


def test_resolve_reads_the_measured_tuner_cell():
    from adapcc_tpu.tuner.db import TuningDatabase

    db = TuningDatabase(persist=False)
    for _ in range(3):
        db.record(_pipe_cell("gpipe", 2, 4), 0.010)
        db.record(_pipe_cell("1f1b", 2, 4), 0.002)
    assert resolve_pipe_schedule(None, tuner_db=db, world=2, microbatches=4) == "1f1b"
    for _ in range(5):
        db.record(_pipe_cell("gpipe", 2, 4), 0.0001)
    assert resolve_pipe_schedule(None, tuner_db=db, world=2, microbatches=4) == "gpipe"
    # a different cell coordinate falls back to the default
    assert resolve_pipe_schedule(None, tuner_db=db, world=4, microbatches=4) == "1f1b"


def test_executor_records_and_resolves_tuner_cells(mesh2):
    """The executor's recorder and the resolver spell the SAME cell — a
    third executor picks the schedule measured cells favor."""
    from adapcc_tpu.tuner.db import TuningDatabase, mesh_fingerprint

    db = TuningDatabase(persist=False)
    eng = CollectiveEngine(mesh2, Strategy.ring(2), use_xla_fastpath=False)
    part = partition_gpt2(CFG, 2)
    sp = split_params(_params(), part)
    toks = _tokens(batch=2)
    for kind in PIPE_SCHEDULES:
        ex = PipelineExecutor(
            CFG, part, eng, num_microbatches=2, schedule=kind, tuner_db=db
        )
        ex.forward_backward(sp, toks)
    topo = mesh_fingerprint(eng.mesh)
    for kind in PIPE_SCHEDULES:
        assert db.stats(_pipe_cell(kind, 2, 2, topo)) is not None
    # stack the deck: gpipe's measured cell becomes unbeatable
    for _ in range(8):
        db.record(_pipe_cell("gpipe", 2, 2, topo), 1e-6)
    chosen = PipelineExecutor(
        CFG, part, eng, num_microbatches=2, tuner_db=db
    )
    assert chosen.schedule_kind == "gpipe"


def test_policy_path_round_trip_and_drift_pins():
    from adapcc_tpu.tuner.policy import (
        PIPE_SCHEDULE_MODES,
        pipe_path,
        pipe_schedule_of,
    )

    assert PIPE_SCHEDULE_MODES == PIPE_SCHEDULES  # the mirror must not drift
    for kind in PIPE_SCHEDULES:
        assert pipe_path(kind) == f"pipe-{kind}"
        assert pipe_schedule_of(pipe_path(kind)) == kind
    with pytest.raises(ValueError, match="schedule"):
        pipe_path("wavefront")
    with pytest.raises(ValueError, match="pipe"):
        pipe_schedule_of("ring-uni")


# --------------------------------------------------------------------------- #
# pricing twins: cost model + program replay
# --------------------------------------------------------------------------- #

def test_cost_model_pipeline_closed_forms():
    from adapcc_tpu.sim.cost_model import (
        LinkCoeffs,
        pipeline_bubble_fraction,
        pipeline_stash_bytes,
        pipeline_step_time,
    )

    assert pipeline_bubble_fraction(4, 8) == pytest.approx(3 / 11)
    assert pipeline_bubble_fraction(1, 8) == 0.0
    with pytest.raises(ValueError):
        pipeline_bubble_fraction(0, 8)

    # the stash closed forms equal the measured tick-table high water
    for s, m in [(2, 4), (4, 8)]:
        for kind in PIPE_SCHEDULES:
            sched = pipeline_schedule(s, m, kind)
            for stage in range(s):
                assert pipeline_stash_bytes(s, m, kind, stage, 1.0) == float(
                    sched.stash_high_water[stage]
                )
    with pytest.raises(ValueError, match="schedule"):
        pipeline_stash_bytes(2, 4, "wavefront", 0, 1.0)

    coeffs = LinkCoeffs(1e-6, 1.0 / 45e9)
    t8 = pipeline_step_time(4, 8, 1e-4, 1 << 20, coeffs)
    t16 = pipeline_step_time(4, 16, 1e-4, 1 << 20, coeffs)
    assert t16 / 16 < t8 / 8  # the bubble amortizes with m
    # a single stage has no hops and no bubble
    assert pipeline_step_time(1, 8, 1e-4, 1 << 20, coeffs) == pytest.approx(
        8 * 1e-4 * 3.0
    )
    with pytest.raises(ValueError):
        pipeline_step_time(0, 8, 1e-4, 1 << 20, coeffs)


@pytest.mark.parametrize("kind", PIPE_SCHEDULES)
def test_pipeline_program_replay_engine_parity(kind):
    """simulate_program prices the pipeline program bitwise-identically on
    the event and vector engines — including a degraded stage link."""
    from adapcc_tpu.sim.cost_model import LinkCoeffs, LinkCostModel, ICI
    from adapcc_tpu.sim.replay import simulate_program

    prog = pipeline_program(pipeline_schedule(4, 4, kind), tied_embedding=True)
    model = LinkCostModel(4, classes={ICI: LinkCoeffs(2e-6, 1.0 / 40e9)})
    model.links[(2, 1)] = LinkCoeffs(1e-4, 1.0 / 2e9)
    ev = simulate_program(prog, model, float(1 << 20), engine="event")
    ve = simulate_program(prog, model, float(1 << 20), engine="vector")
    assert ev.seconds == ve.seconds
    assert ev.seconds > 0


# --------------------------------------------------------------------------- #
# the forward-only block (its parity with the sequential composition: tests/test_parallel.py)
# --------------------------------------------------------------------------- #

def test_pipe_package_reexports_the_forward_block():
    from adapcc_tpu.pipe import pipeline_apply
    from adapcc_tpu.pipe.forward import pipeline_apply as direct

    assert pipeline_apply is direct


# --------------------------------------------------------------------------- #
# workload flag plumbing
# --------------------------------------------------------------------------- #

def test_train_gpt2_pp_flag_guards():
    from adapcc_tpu.workloads.train_gpt2 import build_parser, run

    base = ["--corpus-tokens", "4000", "--epochs", "1"]
    with pytest.raises(ValueError, match="--sp"):
        run(build_parser().parse_args(base + ["--pp-stages", "2", "--sp", "ulysses"]))
    with pytest.raises(ValueError, match="--zero1"):
        run(build_parser().parse_args(base + ["--pp-stages", "2", "--zero1"]))
    with pytest.raises(ValueError, match="at least"):
        run(build_parser().parse_args(base + ["--pp-stages", "1"]))
    with pytest.raises(ValueError, match="--pp-microbatches"):
        run(build_parser().parse_args(
            base + ["--pp-stages", "2", "--batch", "6", "--pp-microbatches", "4"]
        ))
