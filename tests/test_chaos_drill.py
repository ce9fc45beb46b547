"""End-to-end multi-process chaos drill (docs/SUPERVISOR.md §5).

The acceptance drill for the autonomous supervisor: REAL worker processes
lease liveness through the coordinator's heartbeat RPC over the wire, the
deterministic chaos harness SIGKILLs one mid-run, and detection comes
from genuine cross-process silence — no ``ADAPCC_FAULT_PLAN``, no
injected arrivals.  The supervisor (out of band, on its own thread)
confirms the death through the grace window, journals the decision, and
actuates the standby-cache swap; the training loop only consumes the
actuated mask.  Pinned:

- the shrink is a standby-cache hit on BOTH planes (engine dispatch
  trace ``cache_hit``; trainer ``recompiles`` unchanged);
- the run completes with final loss within the pinned tolerance of an
  uninterrupted baseline;
- a supervisor restart mid-run replays its journal to an identical
  WorldView with ZERO duplicate epoch bumps.

A second drill SIGSTOP-duty-cycles a worker (the chaos spelling of a
FaultPlan ``slow`` event): the genuinely straggling process's
self-reported step walltimes inflate and the slow-rank rule demotes it
to a relay — then promotes it back after SIGCONT.

The third drill is PR 13's durable-recovery acceptance
(docs/RECOVERY.md): one rank is SIGKILLed mid-step and a second — a
real worker running the async crash-consistent save pipeline — is
SIGKILLed *mid-save*, at the exact publish rename.  Both dead ranks'
ZeRO-1 optimizer shards are reconstructed from their in-fabric replicas
(no checkpoint reload on the hot path), the mid-save crash leaves only
ignorable ``.tmp-*`` debris next to verified earlier steps (keep-last-
good), replacement workers heartbeat in and are journaled as ``admit``
decisions carrying the rendezvous generation, the world grows back with
``cache_hit=True`` on the first grown dispatch, the final loss lands
within the pinned tolerance of the uninterrupted baseline, and the
surviving ranks' processes are never restarted.

Wall-clock timing is involved (that is the point), so the knobs leave
generous margins: workers beat every ~70 ms against a 2 s suspicion
timeout; only multi-second stalls of a *live* worker could false-fire.
"""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from adapcc_tpu.comm.engine import CollectiveEngine
from adapcc_tpu.coordinator import CoordinatorLogic, CoordinatorServer
from adapcc_tpu.ddp import DDPTrainer, TrainState
from adapcc_tpu.elastic import FaultEvent, FaultPlan, StandbyPlanCache
from adapcc_tpu.models import MLP
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.supervisor import (
    ChaosInjector,
    LivenessConfig,
    Supervisor,
)
from adapcc_tpu.utils.observability import CollectiveTrace, MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A wire-compatible heartbeat worker with NO heavy imports (it must start
# in milliseconds so the drill spends its wall clock on detection, not on
# interpreter startup): the cont_request protobuf is two varint fields —
# step (field 1: the step walltime in µs) and world_rank (field 2).
WORKER = textwrap.dedent(
    """
    import sys, time
    import grpc

    rank, port, step_s = int(sys.argv[1]), int(sys.argv[2]), float(sys.argv[3])

    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def cont_request(median_us, world_rank):
        return b"\\x08" + varint(median_us) + b"\\x10" + varint(world_rank)

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    beat = channel.unary_unary(
        "/coordinator.Coordinator/heartbeat",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    # The "training step" is step_s of progress in slices of 10 ms.  A sleep
    # counts on while the process is stopped, so one sleep(step_s) under a
    # SIGSTOP duty cycle only runs to the end of the stop it falls into
    # (0.1 s -> 0.2 s at slowdown 4); slices advance while the process
    # runs, as work does (0.1 s -> 0.4 s, measured with the injector alone).
    slices = max(1, round(step_s / 0.01))
    while True:
        t0 = time.monotonic()
        for _ in range(slices):
            time.sleep(step_s / slices)
        dt = time.monotonic() - t0  # self-reported step walltime
        try:
            beat(cont_request(max(1, int(dt * 1e6)), rank), timeout=2.0)
        except grpc.RpcError:
            pass                    # keep leasing through control blips
    """
)


def _spawn_workers(tmp_path, port, world, step_s):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    return {
        r: subprocess.Popen(
            [sys.executable, str(script), str(r), str(port), str(step_s)],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        for r in range(world)
    }


def _kill_all(procs):
    for p in procs.values():
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGCONT)  # un-freeze before killing
            except ProcessLookupError:
                pass
            p.kill()
    for p in procs.values():
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass


def _wait_for_beats(logic, world, deadline_s=30.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if len(logic.heartbeat_snapshot()) == world:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"only {sorted(logic.heartbeat_snapshot())} of {world} workers "
        "ever heartbeat"
    )


def test_chaos_drill_sigkill_detection_swap_and_restart(mesh4, tmp_path):
    world, steps = 4, 40
    model = MLP(features=(4, 2))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(world, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(world, 2)), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x[:1])

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    def make_trainer():
        return DDPTrainer(
            loss_fn, optax.sgd(0.1), mesh4, Strategy.ring(world),
            dynamic_mask=True, sync_mode="schedule",
        )

    # -- baseline: the uninterrupted run ------------------------------------
    base_trainer = make_trainer()
    base_state = TrainState.create(params, base_trainer.tx)
    for _ in range(steps):
        base_state, base_loss = base_trainer.step(base_state, (x, y))

    # -- supervised run ------------------------------------------------------
    assert not os.environ.get("ADAPCC_FAULT_PLAN", "").strip(), (
        "the drill's detection must come from heartbeat loss alone"
    )
    trace = CollectiveTrace()
    engine = CollectiveEngine(mesh4, Strategy.ring(world), trace=trace)
    payload = jnp.ones((world, 2), jnp.float32)
    engine.all_reduce(payload)
    cache = StandbyPlanCache(engine, nbytes=payload.nbytes, top_k=world)
    cache.build()
    cache.warm((2,), jnp.float32)

    trainer = make_trainer()
    state = TrainState.create(params, trainer.tx)
    state, _ = trainer.step(state, (x, y))  # compile the healthy step
    for splan in cache.ranked():
        trainer.prewarm(splan.strategy, state, (x, y))
    warm_recompiles = trainer.recompiles
    state = TrainState.create(params, trainer.tx)
    trainer.reset()

    logic = CoordinatorLogic(world)
    srv = CoordinatorServer(world, port=0, logic=logic).start()
    metrics = MetricsRegistry()
    journal_path = str(tmp_path / "sup.journal")
    config = LivenessConfig(timeout_s=2.0, period_s=0.25, grace=2)
    sup = Supervisor(
        logic, engine, cache=cache, trainer=trainer,
        journal_path=journal_path, config=config, metrics=metrics,
    )
    trainer.attach_supervisor(sup)

    procs = _spawn_workers(tmp_path, srv.port, world, step_s=0.05)
    # the chaos harness, not the test, delivers the fault: the canonical
    # one-rank-down plan compiled to its wall-clock SIGKILL schedule
    plan = FaultPlan(
        [FaultEvent(step=2, kind="down", rank=2)], world=world,
        label="drill-sigkill",
    )
    injector = ChaosInjector(plan, step_period_s=1.0)  # kill at t≈2 s
    try:
        _wait_for_beats(logic, world)
        sup.start(period_s=0.05)
        injector.start({r: p.pid for r, p in procs.items()})

        losses = []
        masks_seen = set()
        restarted = False
        t0 = time.monotonic()
        for step in range(steps):
            mask = sup.current_mask()
            masks_seen.add(tuple(mask.astype(int)))
            state, loss = trainer.step(state, (x, y), step_idx=step)
            losses.append(float(np.mean(np.asarray(loss))))
            # the engine plane dispatches under the supervisor's epoch
            wv = sup.applied_view
            out = engine.all_reduce(
                payload,
                active_gpus=wv.active_list() if wv.degraded else None,
                epoch=sup.engine_epoch,
            )
            assert float(np.asarray(out)[0, 0]) == len(wv.active_list())
            if not restarted and sup.worldview().dead:
                # -- supervisor restart mid-run (the crash-safety pin) --
                restarted = True
                view_before = sup.applied_view
                epoch_before = engine.epoch
                sup.stop()
                sup = Supervisor(
                    logic, engine, cache=cache, trainer=trainer,
                    journal_path=journal_path, config=config,
                    metrics=metrics,
                )
                assert sup.applied_view == view_before
                assert engine.epoch == epoch_before, (
                    "journal replay duplicated an epoch bump"
                )
                trainer.attach_supervisor(sup)
                sup.start(period_s=0.05)
            # pace the loop so detection has wall clock to happen in; exit
            # early only if we somehow overrun the drill budget
            time.sleep(0.12)
            assert time.monotonic() - t0 < 60, "drill overran its budget"
        sup.stop()
        injector.stop()

        # -- the fault really happened, detected from silence alone ----------
        assert procs[2].wait(timeout=5) == -9, "chaos never killed rank 2"
        st = sup.journal.replay()
        kinds = [d.kind for d in st.decisions]
        dead = [d for d in st.decisions if d.kind == "dead"]
        assert len(dead) == 1 and dead[0].payload == {
            "rank": 2, "origin": "heartbeat",
        }, kinds
        assert "suspect" in kinds  # the grace window was walked, not skipped
        epochs = [d for d in st.decisions if d.kind == "epoch"]
        assert len(epochs) == 1, (
            f"expected exactly one epoch decision, got {kinds}"
        )
        assert epochs[0].payload["alive"] == [0, 1, 3]
        assert st.unapplied == []

        # -- the swap hit the standby cache on both planes -------------------
        swap = next(d for d in st.decisions if d.kind == "swap")
        assert swap.payload["warmed"] is True
        failover_events = [
            e for e in trace.events()
            if e.primitive == "allreduce" and e.extra.get("epoch") == 1
        ]
        assert failover_events, "no dispatch recorded under the failover epoch"
        assert failover_events[0].extra["cache_hit"] is True
        assert trainer.recompiles == warm_recompiles, (
            "the failover paid a trainer recompile the prewarm should "
            "have absorbed"
        )

        # -- the run completed, and training carried through ------------------
        assert len(losses) == steps and all(np.isfinite(losses))
        assert (1, 1, 0, 1) in masks_seen, (
            f"the actuated mask never excluded the dead rank: {masks_seen}"
        )
        final, base_final = losses[-1], float(np.mean(np.asarray(base_loss)))
        assert abs(final - base_final) <= 0.05, (
            f"drill final loss {final:.4f} vs baseline {base_final:.4f}"
        )
        # liveness observability rode along: per-rank gauges + decisions
        snap = metrics.snapshot()
        assert snap["gauges"]["liveness/rank2/state"] == 2.0
        assert snap["counters"]["supervisor/decisions/dead"] == 1.0
    finally:
        sup.stop()
        injector.stop()
        _kill_all(procs)
        srv.stop()


# A checkpoint-writer worker for the durable-recovery drill: it leases
# liveness exactly like WORKER *and* runs the real async crash-consistent
# save pipeline against a shared directory.  After publishing two good
# steps it waits for the parent's go-signal, then SIGKILLs ITSELF at the
# exact rename that would publish step-2 — a genuine process death in the
# widest torn window (every shard byte and the manifest written, the
# commit pending), deterministic by construction.  The heavy imports run
# before the beat thread starts so a GIL-bound import stall can never eat
# into the suspicion window.
CKPT_WORKER = textwrap.dedent(
    """
    import os, signal, sys, threading, time
    import grpc
    import numpy as np
    from adapcc_tpu.checkpoint import (
        AsyncCheckpointManager,
        TrainCheckpointState,
    )

    rank, port = int(sys.argv[1]), int(sys.argv[2])
    ckpt_dir, go_path = sys.argv[3], sys.argv[4]

    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    beat = channel.unary_unary(
        "/coordinator.Coordinator/heartbeat",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )

    def beat_loop():
        while True:
            try:
                beat(b"\\x08" + varint(50_000) + b"\\x10" + varint(rank),
                     timeout=2.0)
            except grpc.RpcError:
                pass
            time.sleep(0.07)

    threading.Thread(target=beat_loop, daemon=True).start()

    def state(step):
        return TrainCheckpointState(
            params={"w": np.full((64, 64), float(step), np.float32)},
            epoch=step, step=step,
        )

    mgr = AsyncCheckpointManager(ckpt_dir, max_to_keep=8)
    mgr.save(0, state(0))
    mgr.save(1, state(1))
    while not os.path.exists(go_path):
        time.sleep(0.05)
    real_rename = os.rename
    def die_at_publish(src, dst):
        if os.path.basename(dst) == "step-2":
            os.kill(os.getpid(), signal.SIGKILL)
        return real_rename(src, dst)
    os.rename = die_at_publish
    mgr.save(2, state(2))
    time.sleep(600)  # unreachable: the save above dies by SIGKILL
    """
)


def _nan_row(leaf, rank, world):
    arr = np.asarray(leaf)
    if arr.ndim >= 1 and arr.shape[0] == world and np.issubdtype(
        arr.dtype, np.floating
    ):
        arr = arr.copy()
        arr[rank] = np.nan
    return arr


def test_chaos_drill_durable_recovery_mid_step_mid_save_rejoin(
    mesh4, tmp_path
):
    """PR 13 acceptance (docs/RECOVERY.md): SIGKILL one rank mid-step and
    one mid-checkpoint-save, repair both lost ZeRO-1 shards from their
    in-fabric replicas with zero checkpoint reloads on the hot path and
    zero full-world restarts, rejoin replacement workers through the
    supervisor's ``admit`` decisions, grow the world back onto the warm
    base plan (``cache_hit=True`` on the first grown dispatch), and land
    the final loss within the pinned tolerance of the uninterrupted
    baseline — with the sim rows pinning replication wire overhead < 5 %
    of baseline step comm at the default config."""
    from adapcc_tpu.checkpoint import (
        AsyncCheckpointManager,
        TrainCheckpointState,
    )
    from adapcc_tpu.elastic import recover_zero1_trainer_state

    world = 4
    model = MLP(features=(4, 2))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(world, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(world, 2)), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x[:1])

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    def make_trainer():
        return DDPTrainer(
            loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(world),
            zero1=True, shard_replicas=1,
        )

    # -- the collective plane: engine + warmed standby cache -----------------
    assert not os.environ.get("ADAPCC_FAULT_PLAN", "").strip(), (
        "the drill's detection must come from heartbeat loss alone"
    )
    trace = CollectiveTrace()
    engine = CollectiveEngine(mesh4, Strategy.ring(world), trace=trace)
    payload = jnp.ones((world, 2), jnp.float32)
    engine.all_reduce(payload)  # compile the healthy base plan
    cache = StandbyPlanCache(engine, nbytes=payload.nbytes, top_k=world)
    cache.build()
    cache.warm((2,), jnp.float32)

    logic = CoordinatorLogic(world)
    srv = CoordinatorServer(world, port=0, logic=logic).start()
    journal_path = str(tmp_path / "sup.journal")
    config = LivenessConfig(timeout_s=3.0, period_s=0.25, grace=2)
    sup = Supervisor(
        logic, engine, cache=cache, journal_path=journal_path, config=config,
    )

    ckpt_dir = str(tmp_path / "steps")
    go_path = str(tmp_path / "go")
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    ckpt_script = tmp_path / "ckpt_worker.py"
    ckpt_script.write_text(CKPT_WORKER)

    def spawn_beat_worker(r):
        return subprocess.Popen(
            [sys.executable, str(script), str(r), str(srv.port), "0.05"],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )

    procs = {r: spawn_beat_worker(r) for r in (0, 2, 3)}
    # rank 1 is the checkpoint-writer: it leases AND saves for real
    procs[1] = subprocess.Popen(
        [sys.executable, str(ckpt_script), "1", str(srv.port), ckpt_dir,
         go_path],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
    )
    replacements = {}

    # the chaos harness delivers the mid-step fault: SIGKILL rank 2 at
    # t≈2 s of the wall schedule.  Rank 1's mid-save death is delivered
    # by the go-file (after rank 2's death is confirmed), so the drill
    # exercises two sequential shrinks, not one combined event.
    plan = FaultPlan(
        [FaultEvent(step=2, kind="down", rank=2)], world=world,
        label="drill-durable-recovery",
    )
    injector = ChaosInjector(plan, step_period_s=1.0)

    trainer = make_trainer()
    state = trainer.init_state(params)
    assert trainer.replica_store is not None

    try:
        _wait_for_beats(logic, world, deadline_s=90.0)
        sup.start(period_s=0.05)
        injector.start({r: p.pid for r, p in procs.items()})

        losses = []
        repaired = []
        grown_epoch = None
        steps_after_grow = 0
        t0 = time.monotonic()
        step = 0
        while True:
            dead_now = sorted(set(sup.worldview().dead) - set(repaired))
            for r in dead_now:
                # the dead rank's single-owner shard is GONE (its HBM
                # died with it): poison its rows, then repair from the
                # in-fabric replica — NO checkpoint reload on this path
                master = np.asarray(state.opt_state[0]).copy()
                master[r] = np.nan
                opt_state = jax.tree_util.tree_map(
                    lambda leaf: _nan_row(leaf, r, world),
                    jax.device_get(state.opt_state[1]),
                )
                broken = TrainState(
                    params=state.params, opt_state=(master, opt_state),
                    step=state.step, model_state=state.model_state,
                )
                state = recover_zero1_trainer_state(
                    trainer, broken, dead=[r], store=trainer.replica_store
                )
                repaired.append(r)
                if r == 2:
                    # rank 2's death is confirmed: unleash rank 1's
                    # mid-save SIGKILL
                    open(go_path, "w").close()
            if sorted(repaired) == [1, 2] and not replacements:
                # replacement workers for the two dead ranks lease in —
                # the rejoin protocol's entry point
                replacements = {r: spawn_beat_worker(r) for r in (1, 2)}
            wv = sup.applied_view
            if (
                replacements
                and grown_epoch is None
                and not wv.degraded
                and wv.epoch >= 3
            ):
                grown_epoch = sup.engine_epoch
            state, loss = trainer.step(state, (x, y))
            losses.append(float(np.mean(np.asarray(loss))))
            out = engine.all_reduce(
                payload,
                active_gpus=wv.active_list() if wv.degraded else None,
                epoch=sup.engine_epoch,
            )
            assert float(np.asarray(out)[0, 0]) == len(wv.active_list())
            step += 1
            if grown_epoch is not None:
                steps_after_grow += 1
                if steps_after_grow >= 5:
                    break
            time.sleep(0.12)
            assert time.monotonic() - t0 < 180, (
                f"drill overran its budget: repaired={repaired} "
                f"wv={sup.applied_view} dead={sorted(sup.worldview().dead)}"
            )
        sup.stop()
        injector.stop()

        # -- both deaths really happened, in their advertised windows --------
        assert procs[2].wait(timeout=5) == -9, "chaos never killed rank 2"
        assert procs[1].wait(timeout=5) == -9, (
            "rank 1 was supposed to die by SIGKILL mid-save"
        )
        assert sorted(repaired) == [1, 2]
        # zero full-world restarts: the surviving ranks' processes were
        # never touched
        assert procs[0].poll() is None and procs[3].poll() is None

        # -- the shards were really repaired from replicas: training math
        #    stayed finite through two poisoned-and-repaired states ----------
        assert all(np.isfinite(losses)), "a NaN'd shard leaked into training"
        assert trainer.replica_store.captures == step

        # -- the mid-save crash left crash-consistent debris only ------------
        amgr = AsyncCheckpointManager(ckpt_dir)
        torn = amgr.torn_saves()
        assert len(torn) == 1 and torn[0].startswith(".tmp-step-2-"), torn
        assert amgr.published_steps() == [0, 1]
        assert amgr.latest_good_step() == 1
        amgr.verify(1)

        # -- the journal tells the whole story -------------------------------
        st = sup.journal.replay()
        kinds = [d.kind for d in st.decisions]
        assert st.unapplied == []
        assert "suspect" in kinds  # the grace window was walked
        dead = [d for d in st.decisions if d.kind == "dead"]
        assert sorted(d.payload["rank"] for d in dead) == [1, 2]
        assert all(d.payload["origin"] == "heartbeat" for d in dead)
        admits = [d for d in st.decisions if d.kind == "admit"]
        assert sorted(d.payload["rank"] for d in admits) == [1, 2]
        # each re-admission of a genuinely dead rank bumps the rendezvous
        # generation the newcomer's catch-up restore keys by
        assert sorted(d.payload["gen"] for d in admits) == [1, 2]
        assert logic.restart_generation == 2
        epochs = [d for d in st.decisions if d.kind == "epoch"]
        assert epochs[-1].payload["alive"] == [0, 1, 2, 3], (
            "the world never grew back to full"
        )

        # -- the grow-back rode the warm base plan ---------------------------
        last_swap = [d for d in st.decisions if d.kind == "swap"][-1]
        assert last_swap.payload["label"] == "base"
        assert last_swap.payload["warmed"] is True
        grown = [
            e for e in trace.events()
            if e.primitive == "allreduce"
            and e.extra.get("epoch") == grown_epoch
        ]
        assert grown, "no dispatch recorded under the grown epoch"
        assert grown[0].extra["cache_hit"] is True, (
            "the first grown dispatch was a cold compile, not a cache hit"
        )

        # -- the replacement's catch-up: the freshest VERIFIED checkpoint
        #    restores from the directory the mid-save crash left behind;
        #    restore_newest_across_processes(gen=<admit gen>) then keys
        #    its rendezvous off the journaled generation ---------------------
        caught_up = TrainCheckpointState(
            params={"w": np.zeros((64, 64), np.float32)}
        )
        assert amgr.restore(caught_up, amgr.latest_good_step())
        assert caught_up.epoch == 1 and caught_up.step == 1
        np.testing.assert_array_equal(
            caught_up.params["w"], np.full((64, 64), 1.0, np.float32)
        )

        # -- final loss pinned against the uninterrupted baseline ------------
        base_trainer = make_trainer()
        base_state = base_trainer.init_state(params)
        for _ in range(step):
            base_state, base_loss = base_trainer.step(base_state, (x, y))
        base_final = float(np.mean(np.asarray(base_loss)))
        assert abs(losses[-1] - base_final) <= 0.05, (
            f"drill final loss {losses[-1]:.4f} vs baseline "
            f"{base_final:.4f}"
        )

        # -- and the sim prices the whole story inside the budget ------------
        from benchmarks.sim_collectives import recovery_sweep

        rows = recovery_sweep([1 << 20, 64 << 20])
        assert all(r["overhead_ok"] for r in rows if r["world"] >= 32), (
            "replication wire overhead broke the 5% acceptance bound"
        )
    finally:
        sup.stop()
        injector.stop()
        _kill_all(procs)
        _kill_all(replacements)
        srv.stop()


def test_chaos_drill_sigstop_straggler_demoted_then_promoted(tmp_path):
    """Satellite 3: a FaultPlan ``slow`` event's cross-process spelling —
    the chaos injector SIGSTOP-duty-cycles a real worker, its
    self-reported step walltimes inflate ~4x, and the supervisor's
    slow-rank rule demotes the genuinely straggling process to a relay
    (epoch bump), then promotes it back after SIGCONT.  Control-plane
    only: no engine is needed to decide membership."""
    world = 4
    logic = CoordinatorLogic(world, slow_factor=2.0)
    srv = CoordinatorServer(world, port=0, logic=logic).start()
    sup = Supervisor(
        logic,
        journal_path=str(tmp_path / "sup.journal"),
        config=LivenessConfig(timeout_s=3.0, period_s=0.25, grace=2),
    )
    procs = _spawn_workers(tmp_path, srv.port, world, step_s=0.1)
    # slow from t≈1 s to t≈9 s at slowdown 4 (stopped 75% of each window):
    # a step of 0.1 s takes 0.4 s, twice what slow_factor=2.0 asks for, and
    # the rule's median of 16 reports needs 8 of them, 3.2 s, to turn
    plan = FaultPlan(
        [FaultEvent(step=1, kind="slow", rank=1, slowdown=4.0),
         FaultEvent(step=9, kind="recover", rank=1)],
        world=world,
        label="drill-sigstop",
    )
    injector = ChaosInjector(plan, step_period_s=1.0)
    try:
        _wait_for_beats(logic, world)
        sup.start(period_s=0.1)
        injector.start({r: p.pid for r, p in procs.items()})

        def wait_relays(want, deadline_s, what):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if sup.worldview().relays == want:
                    return
                time.sleep(0.1)
            raise AssertionError(
                f"{what}: relays={sorted(sup.worldview().relays)}, "
                f"medians={sup.table.medians()}"
            )

        # demotion while the duty cycle runs...
        wait_relays(frozenset({1}), 8.0, "straggler never demoted")
        assert sorted(sup.worldview().alive) == [0, 1, 2, 3], (
            "a straggler is demoted, not dead: SIGSTOP blips inside the "
            "grace window must not kill the rank"
        )
        # ...promotion once SIGCONT lets it catch back up (the rolling
        # median needs a few healthy steps to fall below the factor)
        wait_relays(frozenset(), 20.0, "recovered straggler never promoted")
        st = sup.journal.replay()
        kinds = [d.kind for d in st.decisions]
        demote = next(d for d in st.decisions if d.kind == "demote")
        assert demote.payload["ranks"] == [1]
        assert float(demote.payload["medians"]["1"]) > 0.2  # really slow
        assert "promote" in kinds
        assert "dead" not in kinds, kinds
        assert sup.worldview().epoch >= 2  # demote + promote both bumped
    finally:
        sup.stop()
        injector.stop()
        _kill_all(procs)
        srv.stop()
