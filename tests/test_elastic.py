"""Elastic fault tolerance: detect → re-plan → hot-swap (docs/ELASTIC.md).

Covers the fault model (deterministic injection), the WorldView lifecycle,
the standby plan cache (no-recompile failover, pinned from the dispatch
trace), the EpochMismatch retry contract, elastic ZeRO-1 re-balance
through the checkpoint layout-tag funnel, and the end-to-end CPU
integration drill: a DDP run under an injected FaultPlan — rank dies
mid-run → relay demotion → world shrink → recovery — where every step
completes, the failover swap hits the standby cache, and the final loss
matches an uninterrupted baseline within pinned tolerance.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from adapcc_tpu.comm.engine import CollectiveEngine, EpochMismatch
from adapcc_tpu.coordinator.logic import CoordinatorLogic
from adapcc_tpu.ddp import DDPTrainer, TrainState
from adapcc_tpu.elastic import (
    FaultEvent,
    FaultPlan,
    StandbyPlanCache,
    WorldView,
    degraded_scenarios,
    load_fault_plan,
    reemit_for_active,
    reshard_zero1_snapshot,
    shrink_zero1_trainer_state,
    slow_ranks_from_medians,
)
from adapcc_tpu.models import MLP
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.observability import CollectiveTrace


# --------------------------------------------------------------------------- #
# fault model
# --------------------------------------------------------------------------- #

def test_fault_plan_state_replay_and_masks():
    plan = FaultPlan(
        [
            FaultEvent(step=2, kind="down", rank=5),
            FaultEvent(step=3, kind="slow", rank=1, slowdown=3.0),
            FaultEvent(step=6, kind="recover", rank=5),
            FaultEvent(step=7, kind="recover", rank=1),
        ],
        world=8,
    )
    assert plan.state_at(1).healthy
    assert plan.state_at(2).down == frozenset({5})
    st = plan.state_at(4)
    assert st.down == frozenset({5}) and st.slow_map == {1: 3.0}
    # contribution mask: down AND demoted-slow ranks are out
    assert list(plan.mask_at(4).astype(int)) == [1, 0, 1, 1, 1, 0, 1, 1]
    assert plan.state_at(6).down == frozenset()
    assert plan.state_at(7).healthy
    # json round trip is exact
    assert FaultPlan.from_dict(plan.to_dict()).events == plan.events


def test_fault_plan_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(step=0, kind="explode", rank=0)
    with pytest.raises(ValueError, match="outside world"):
        FaultPlan([FaultEvent(step=0, kind="down", rank=9)], world=8)
    with pytest.raises(ValueError, match="entire world"):
        FaultPlan(
            [FaultEvent(step=0, kind="down", rank=r) for r in range(2)],
            world=2,
        )


def test_fault_plan_seeded_is_deterministic():
    a = FaultPlan.seeded(8, steps=10, seed=7)
    b = FaultPlan.seeded(8, steps=10, seed=7)
    assert a.events == b.events
    assert FaultPlan.seeded(8, steps=10, seed=8).events != a.events


def test_load_fault_plan_env_funnel(tmp_path, monkeypatch):
    from adapcc_tpu.elastic import FAULT_PLAN_ENV

    monkeypatch.delenv(FAULT_PLAN_ENV, raising=False)
    assert load_fault_plan() is None

    path = tmp_path / "plan.json"
    FaultPlan([FaultEvent(step=1, kind="down", rank=2)], world=4).save(str(path))
    monkeypatch.setenv(FAULT_PLAN_ENV, str(path))
    plan = load_fault_plan(world=4)
    assert plan is not None and plan.down_at(1) == frozenset({2})
    # set-but-broken is loud, never a silent healthy run
    with pytest.raises(ValueError, match="world"):
        load_fault_plan(world=8)
    monkeypatch.setenv(FAULT_PLAN_ENV, str(tmp_path / "missing.json"))
    with pytest.raises(FileNotFoundError):
        load_fault_plan()
    bad = tmp_path / "bad.json"
    bad.write_text("not json{")
    monkeypatch.setenv(FAULT_PLAN_ENV, str(bad))
    with pytest.raises(ValueError, match="fault-plan"):
        load_fault_plan()


# --------------------------------------------------------------------------- #
# worldview + slow-rank rule
# --------------------------------------------------------------------------- #

def test_worldview_epoch_bumps_only_on_change():
    wv = WorldView.full(8)
    assert wv.epoch == 0 and not wv.degraded
    wv1 = wv.with_down([3])
    assert wv1.epoch == 1 and wv1.dead == frozenset({3})
    assert wv1.with_down([3]) is wv1  # no change, no bump
    wv2 = wv1.with_relays([5])
    assert wv2.epoch == 2 and wv2.active_list() == [0, 1, 2, 4, 6, 7]
    wv3 = wv2.with_recovered([3])
    assert wv3.epoch == 3 and 3 in wv3.alive
    # relays must be alive; masks follow contributing
    with pytest.raises(ValueError, match="not alive"):
        WorldView(8, alive=frozenset({0, 1}), relays=frozenset({5}), epoch=0)


def test_slow_rank_rule_judges_against_peers():
    base = {r: 0.10 + 0.001 * r for r in range(8)}
    assert slow_ranks_from_medians(base, factor=2.0) == frozenset()
    base[3] = 0.35
    assert slow_ranks_from_medians(base, factor=2.0) == frozenset({3})
    # a uniformly slow world demotes nobody
    uniform = {r: 0.9 for r in range(8)}
    assert slow_ranks_from_medians(uniform, factor=2.0) == frozenset()
    # too few peers: no judgement
    assert slow_ranks_from_medians({0: 0.1, 1: 9.9}, factor=2.0) == frozenset()


def test_coordinator_worldview_and_medians():
    logic = CoordinatorLogic(8, fault_timeout=0.5)
    assert logic.worldview() == WorldView.full(8)
    medians = {r: 0.1 for r in range(8)}
    medians[6] = 0.5
    wv = logic.observe_step_medians(medians)
    assert wv.relays == frozenset({6}) and wv.epoch == 1
    wv = logic.observe_step_medians({r: 0.1 for r in range(8)})
    assert wv.relays == frozenset() and wv.epoch == 2


def test_coordinator_fault_injection_is_deterministic():
    """Injected-dead ranks are dropped at the funnel: the freeze barrier
    and heartbeat barrier shrink, status 0 surfaces with the alive subset
    without waiting out any wall-clock timeout."""
    plan = FaultPlan(
        [
            FaultEvent(step=1, kind="down", rank=3),
            FaultEvent(step=4, kind="recover", rank=3),
        ],
        world=4,
    )
    # huge timeouts: determinism, not clocks, must produce the detection
    logic = CoordinatorLogic(
        4, relay_threshold=30.0, time_slot=0.01, fault_timeout=30.0,
        fault_plan=plan,
    )
    results = {}

    def worker(r):
        active = logic.hook_arrive(step=1, rank=r)
        heart = logic.controller_arrive(step=1, rank=r)
        results[r] = (active, heart)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert all(not t.is_alive() for t in threads), "injection path hung"
    for r in range(4):
        active, (alive, status) = results[r]
        assert sorted(active) == [0, 1, 2], f"rank {r} saw {active}"
        assert status == 0 and sorted(alive) == [0, 1, 2]
    wv = logic.worldview()
    assert wv.dead == frozenset({3}) and wv.epoch >= 1

    # recovery at a later step: full barrier again, status 1
    results2 = {}

    def worker2(r):
        logic.hook_arrive(step=5, rank=r)
        results2[r] = logic.controller_arrive(step=5, rank=r)

    threads = [threading.Thread(target=worker2, args=(r,)) for r in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert {s for _, s in results2.values()} == {1}
    assert logic.worldview().alive == frozenset(range(4))


# --------------------------------------------------------------------------- #
# standby plans + engine epochs
# --------------------------------------------------------------------------- #

def test_degraded_scenarios_cover_ranks_and_hosts():
    ips = {r: f"10.0.0.{r // 2}" for r in range(4)}
    scen = dict(degraded_scenarios(4, ips))
    assert scen["rank0-down"] == frozenset({1, 2, 3})
    assert len([k for k in scen if k.startswith("rank")]) == 4
    host_keys = [k for k in scen if k.startswith("host")]
    assert len(host_keys) == 2
    assert scen["host[10.0.0.1]-down"] == frozenset({0, 1})


def test_reemit_for_active_prunes_clean_and_roots_alive():
    from adapcc_tpu.comm.relay import prune_reduce_rounds

    world = 8
    active = sorted(set(range(world)) - {2, 5})
    s = reemit_for_active(world, active, shape="ring")
    assert s.trees[0].root in active  # a dead root could never broadcast
    rounds = prune_reduce_rounds(s.trees[0], active)
    # dead ranks hang off the prunable tail: the pruned depth is exactly
    # the live chain
    assert len(rounds) == len(active) - 1
    with pytest.raises(ValueError, match="empty active set"):
        reemit_for_active(world, [])


def test_engine_epoch_mismatch_and_swap(mesh4):
    trace = CollectiveTrace()
    eng = CollectiveEngine(mesh4, Strategy.ring(4), trace=trace)
    x = jnp.ones((4, 8), jnp.float32)
    eng.all_reduce(x)  # epoch 0
    assert eng.epoch == 0
    epoch = eng.advance_epoch()
    with pytest.raises(EpochMismatch) as ei:
        eng.all_reduce(x, epoch=epoch - 1)
    assert ei.value.current == epoch and ei.value.issued == epoch - 1
    out = eng.all_reduce(x, epoch=epoch)  # current token passes
    assert float(np.asarray(out)[0, 0]) == 4.0
    with pytest.raises(ValueError, match="world"):
        eng.advance_epoch(Strategy.ring(5))


def test_standby_cache_hit_is_visible_in_trace(mesh4):
    trace = CollectiveTrace()
    eng = CollectiveEngine(mesh4, Strategy.ring(4), trace=trace)
    x = jnp.ones((4, 8), jnp.float32)
    eng.all_reduce(x)  # the healthy full-world program, warm from step 0
    cache = StandbyPlanCache(eng, nbytes=32, top_k=4)
    cache.build()
    warmed = cache.warm((8,), jnp.float32)
    assert len(warmed) == 4 and all(p.warmed for p in warmed)
    plan, epoch = cache.activate([0, 1, 3])  # rank 2 died
    assert epoch == 1 and eng.strategy is plan.strategy
    out = eng.all_reduce(x, active_gpus=[0, 1, 3], epoch=epoch)
    ev = trace.events()[-1]
    assert ev.extra["cache_hit"] is True, "failover dispatch recompiled"
    assert ev.extra["epoch"] == 1
    assert float(np.asarray(out)[0, 0]) == 3.0  # 3 contributors
    # recovery swaps back to the warm base plan
    epoch = cache.restore_full()
    eng.all_reduce(x, epoch=epoch)
    assert trace.events()[-1].extra["cache_hit"] is True


def test_broadcast_rejects_dead_root(mesh4):
    eng = CollectiveEngine(mesh4, Strategy.ring(4))
    x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)
    with pytest.raises(ValueError, match="dead root cannot source"):
        eng.broadcast(x, active_gpus=[1, 2, 3])  # root 0 excluded
    # an alive-root masked broadcast still delivers the root row everywhere
    out = np.asarray(eng.broadcast(x, active_gpus=[0, 1, 3]))
    np.testing.assert_allclose(out, np.tile(np.asarray(x)[0], (4, 1)))


def test_communicator_epoch_retry(tmp_path, mesh4):
    from adapcc_tpu.communicator import Communicator
    from adapcc_tpu.config import CommArgs
    from adapcc_tpu.primitives import ALLREDUCE

    args = CommArgs(
        topology_dir=str(tmp_path),
        strategy_file=str(tmp_path / "strategy.xml"),
        logical_graph=str(tmp_path / "lg.xml"),
    )
    comm = Communicator(args, mesh=mesh4)
    comm.init_threads(ALLREDUCE)
    eng = comm._engine(ALLREDUCE)
    x = jnp.ones((4, 8), jnp.float32)
    token = eng.epoch
    eng.advance_epoch()  # the world moved on under the caller
    # the stale token retries against the refreshed epoch and completes
    out = comm.all_reduce(x, epoch=token)
    assert float(np.asarray(out)[0, 0]) == 4.0
    # a dispatch that NEVER stops mismatching exhausts the bounded budget
    from adapcc_tpu.communicator import EPOCH_RETRY_MAX

    calls = []

    def always_stale(ep):
        calls.append(ep)
        raise EpochMismatch(ep, ep + 1)

    with pytest.raises(EpochMismatch):
        comm._dispatch_with_epoch_retry(always_stale, 0)
    assert len(calls) == EPOCH_RETRY_MAX + 1


# --------------------------------------------------------------------------- #
# elastic ZeRO-1 re-balance
# --------------------------------------------------------------------------- #

def _tiny_params():
    model = MLP(features=(6, 3))
    x = jnp.ones((1, 5), jnp.float32)
    return model, model.init(jax.random.PRNGKey(0), x)


def test_zero1_rebalance_preserves_canonical_content(mesh8, mesh4):
    from adapcc_tpu.checkpoint import TrainCheckpointState
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer, _flatten, _flatten_meta

    _, params = _tiny_params()
    tx = optax.adam(1e-3)
    opt8 = Zero1Optimizer(tx, mesh8)
    m8, o8 = opt8.init(params)
    snap = TrainCheckpointState(
        params=params,
        opt_state=(np.asarray(m8), jax.device_get(o8)),
        extra=opt8.checkpoint_extra(),
    )
    opt4 = Zero1Optimizer(tx, mesh4)
    restored = reshard_zero1_snapshot(snap, params, opt4)
    m4, o4 = restored.opt_state
    meta8 = _flatten_meta(params, 8, 1)
    meta4 = _flatten_meta(params, 4, 1)
    flat8 = np.asarray(m8).reshape(-1)[: meta8.total]
    flat4 = np.asarray(m4).reshape(-1)[: meta4.total]
    np.testing.assert_array_equal(flat8, flat4)
    np.testing.assert_array_equal(
        flat4, np.asarray(_flatten(params, meta4))[: meta4.total]
    )
    # adam count replicates across the new world
    count4 = np.asarray(jax.tree_util.tree_leaves(o4)[0])
    assert count4.shape[0] == 4


def test_zero1_rebalance_guard_blocks_unresharded_snapshot(mesh8, mesh4):
    from adapcc_tpu.checkpoint import TrainCheckpointState
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer

    _, params = _tiny_params()
    tx = optax.adam(1e-3)
    opt8 = Zero1Optimizer(tx, mesh8)
    m8, o8 = opt8.init(params)
    snap8 = TrainCheckpointState(
        params=params,
        opt_state=(np.asarray(m8), jax.device_get(o8)),
        extra=opt8.checkpoint_extra(),
    )
    opt4 = Zero1Optimizer(tx, mesh4)
    # un-resharded world-8 snapshot into a world-4 receiver: the load
    # funnel's layout guard refuses (this is the silent chunk-permutation
    # hazard the elastic path must never reopen)
    receiver = TrainCheckpointState(
        params=params, opt_state=(m8, o8), extra=opt4.checkpoint_extra()
    )
    with pytest.raises(ValueError, match="layout mismatch"):
        receiver.apply_snapshot(snap8.capture_snapshot())
    # untagged snapshots are refused outright
    untagged = TrainCheckpointState(
        params=params, opt_state=(np.asarray(m8), jax.device_get(o8))
    )
    with pytest.raises(ValueError, match="layout tag"):
        reshard_zero1_snapshot(untagged, params, opt4)


def test_zero1_midrun_shrink_is_convergence_equivalent(mesh8, mesh4):
    """ZeRO-1 semantics are world-invariant: training through a mid-run
    8 → 4 shrink (same global batch, resharded optimizer state) must land
    on the same parameters as the uninterrupted world-8 run."""
    model, params = _tiny_params()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    def make(mesh, world):
        tx = optax.adam(1e-2)
        tr = DDPTrainer(loss_fn, tx, mesh, Strategy.ring(world), zero1=True)
        return tr

    t8 = make(mesh8, 8)
    s8 = t8.init_state(params)
    for step in range(2):
        s8, _ = t8.step(s8, (x, y))

    # branch A: uninterrupted world-8 run
    sa = s8
    for step in range(2):
        sa, _ = t8.step(sa, (x, y))

    # branch B: world shrinks to 4 mid-run; shards re-balance through the
    # layout-tag funnel and training continues on the smaller mesh
    t4 = make(mesh4, 4)
    t4.init_state(s8.params)  # constructs the target optimizer geometry
    sb = shrink_zero1_trainer_state(t4, s8)
    for step in range(2):
        sb, _ = t4.step(sb, (x, y))

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        sa.params,
        sb.params,
    )


# --------------------------------------------------------------------------- #
# trainer prewarm / adopt
# --------------------------------------------------------------------------- #

def test_trainer_prewarm_makes_adopt_a_cache_hit(mesh4):
    model, params = _tiny_params()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    tx = optax.sgd(0.1)
    trainer = DDPTrainer(
        loss_fn, tx, mesh4, Strategy.ring(4),
        dynamic_mask=True, sync_mode="schedule",
    )
    state = TrainState.create(params, tx)
    state, _ = trainer.step(state, (x, y))
    base_recompiles = trainer.recompiles

    degraded = reemit_for_active(4, [0, 1, 3])
    assert trainer.prewarm(degraded, state, (x, y))
    assert not trainer.prewarm(degraded, state, (x, y))  # already warm
    warm_recompiles = trainer.recompiles
    assert warm_recompiles == base_recompiles + 1

    mask = jnp.asarray(np.array([True, True, False, True]))
    assert trainer.adopt_strategy(degraded) is True
    state, loss = trainer.step(state, (x, y), active_mask=mask)
    assert np.isfinite(np.asarray(loss)).all()
    assert trainer.recompiles == warm_recompiles, "failover step recompiled"

    # swapping back to the base strategy is also warm (it was compiled at
    # the first step and never evicted)
    assert trainer.adopt_strategy(Strategy.ring(4)) is True
    state, _ = trainer.step(state, (x, y))
    assert trainer.recompiles == warm_recompiles


# --------------------------------------------------------------------------- #
# sim pricing
# --------------------------------------------------------------------------- #

def test_failover_cost_terms():
    from adapcc_tpu.sim.cost_model import (
        LinkCoeffs,
        detection_latency_s,
        failover_cost,
        plan_swap_stall_s,
    )

    coeffs = LinkCoeffs(alpha=1e-6, beta=1.0 / 45e9)
    assert detection_latency_s(1.0, step_time_s=0.2) == pytest.approx(1.1)
    assert plan_swap_stall_s(True) < plan_swap_stall_s(False)
    cost = failover_cost(8, 1 << 20, coeffs, n_down=1, heartbeat_timeout_s=0.5)
    assert cost["degraded_s"] > 0 and cost["healthy_s"] > 0
    # a dead, undetected rank is priced as the timeout, not a hang
    assert cost["undetected_s"] == pytest.approx(0.5)
    slow = failover_cost(
        8, 1 << 20, coeffs, n_down=1, slowdown=4.0, heartbeat_timeout_s=0.5
    )
    assert slow["undetected_s"] > slow["healthy_s"]
    with pytest.raises(ValueError, match="n_down"):
        failover_cost(8, 1 << 20, coeffs, n_down=8)


def test_simulate_fault_plan_timeline_and_determinism():
    from adapcc_tpu.sim.calibrate import load_or_default
    from adapcc_tpu.sim.replay import simulate_fault_plan

    model = load_or_default(world=8)
    plan = FaultPlan(
        [
            FaultEvent(step=2, kind="down", rank=7),
            FaultEvent(step=3, kind="slow", rank=1, slowdown=4.0),
            FaultEvent(step=6, kind="recover", rank=7),
            FaultEvent(step=7, kind="recover", rank=1),
        ],
        world=8,
    )
    rows = simulate_fault_plan(Strategy.ring(8), model, 1 << 20, plan)
    rows2 = simulate_fault_plan(Strategy.ring(8), model, 1 << 20, plan)
    assert [r.to_row() for r in rows] == [r.to_row() for r in rows2]
    assert rows[0].epoch == 0 and not rows[0].swapped
    swaps = [r for r in rows if r.swapped]
    assert [r.step for r in swaps] == [2, 3, 6, 7]
    assert all(r.detection_s > 0 and r.swap_s > 0 for r in swaps)
    assert rows[-1].epoch == 4
    assert len(rows[2].alive) == 7 and rows[3].relays == (1,)
    # world mismatch is loud
    with pytest.raises(ValueError, match="world"):
        simulate_fault_plan(Strategy.ring(4), load_or_default(world=4), 1, plan)


def test_fault_sweep_rows_are_deterministic_and_labeled():
    from benchmarks.sim_collectives import fault_sweep

    rows = fault_sweep(8, [1 << 20], hosts=2)
    rows2 = fault_sweep(8, [1 << 20], hosts=2)
    assert rows == rows2
    assert all(r["mode"] == "simulated" for r in rows)
    phases = {r["phase"] for r in rows}
    assert phases == {"failover", "timeline"}
    summary = [r for r in rows if r["phase"] == "failover"]
    assert {r["scenario"] for r in summary} == {
        "rank-down", "rank-slow", "host-down"
    }
    for r in summary:
        assert r["swap_cached_us"] < r["swap_cold_us"]
        assert r["detection_us"] > 0
    timeline = [r for r in rows if r["phase"] == "timeline"]
    assert any(r["swapped"] for r in timeline)


# --------------------------------------------------------------------------- #
# the end-to-end CPU integration drill (acceptance criteria)
# --------------------------------------------------------------------------- #

def test_elastic_failover_integration(mesh8):
    """Full loop on the virtual pod: DDP training under an injected
    FaultPlan — rank 5 dies mid-run (relay demotion + world shrink),
    later recovers — driven by the coordinator's deterministic detection.
    Every step completes without hanging, the failover swap hits the
    standby cache on BOTH planes (trainer: no recompile; engine:
    ``cache_hit`` in the dispatch trace), and the final loss matches an
    uninterrupted baseline within pinned tolerance."""
    world = 8
    steps = 10
    plan = FaultPlan(
        [
            FaultEvent(step=3, kind="down", rank=5),
            FaultEvent(step=7, kind="recover", rank=5),
        ],
        world=world,
    )
    logic = CoordinatorLogic(
        world, relay_threshold=30.0, time_slot=0.01, fault_timeout=30.0,
        fault_plan=plan,
    )

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
            loss_fn, optax.sgd(0.1), mesh8, Strategy.ring(world),
            dynamic_mask=True, sync_mode="schedule",
        )

    # -- baseline: the uninterrupted run ------------------------------------
    base_trainer = make_trainer()
    base_state = TrainState.create(params, base_trainer.tx)
    for step in range(steps):
        base_state, base_loss = base_trainer.step(base_state, (x, y))

    # -- elastic run: standby plans AOT-compiled at setup --------------------
    trainer = make_trainer()
    state = TrainState.create(params, trainer.tx)
    trace = CollectiveTrace()
    engine = CollectiveEngine(mesh8, Strategy.ring(world), trace=trace)
    cache = StandbyPlanCache(engine, nbytes=x.nbytes, top_k=world)
    cache.build()
    cache.warm((2,), jnp.float32)  # the engine-plane payload below
    state, _ = trainer.step(state, (x, y))  # compile the healthy step
    for splan in cache.ranked():
        trainer.prewarm(splan.strategy, state, (x, y))
    warm_recompiles = trainer.recompiles
    state = TrainState.create(params, trainer.tx)  # restart from scratch
    trainer.reset()

    def negotiate(step):
        """Every rank hits the coordinator funnel; injected-dead arrivals
        are dropped there.  Returns the post-arrival WorldView."""
        threads = [
            threading.Thread(
                target=logic.hook_arrive, kwargs={"step": step, "rank": r}
            )
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert all(not t.is_alive() for t in threads), f"step {step} hung"
        return logic.worldview()

    engine_epoch = engine.epoch
    last_epoch = 0
    losses = []
    payload = jnp.ones((world, 2), jnp.float32)
    for step in range(steps):
        wv = negotiate(step)
        if wv.epoch != last_epoch:
            # detect -> re-plan -> hot-swap, both planes
            if wv.degraded:
                splan, engine_epoch = cache.activate(wv.alive)
                assert splan.warmed, "failover missed the standby cache"
                assert trainer.adopt_strategy(splan.strategy) is True
            else:
                engine_epoch = cache.restore_full()
                assert trainer.adopt_strategy(cache.base_strategy) is True
            last_epoch = wv.epoch
        mask = jnp.asarray(wv.mask())
        state, loss = trainer.step(
            state, (x, y), step_idx=step, active_mask=mask
        )
        losses.append(float(np.mean(np.asarray(loss))))
        # the engine plane runs a collective under the same epoch token
        out = engine.all_reduce(
            payload,
            active_gpus=wv.active_list() if wv.degraded else None,
            epoch=engine_epoch,
        )
        assert float(np.asarray(out)[0, 0]) == len(wv.active_list())

    # every step completed (no hangs): we got a loss per step
    assert len(losses) == steps and all(np.isfinite(losses))
    # the swap hit the standby cache: no trainer recompile after warmup...
    assert trainer.recompiles == warm_recompiles, (
        "the failover step paid a recompile the standby cache should "
        "have absorbed"
    )
    # ...and the engine's failover dispatch replayed a warm program
    failover_events = [
        e for e in trace.events()
        if e.primitive == "allreduce" and e.extra.get("epoch") == 1
    ]
    assert failover_events, "no dispatch recorded under the failover epoch"
    assert failover_events[0].extra["cache_hit"] is True

    # the world recovered: the last epoch runs full-world again
    assert logic.worldview().alive == frozenset(range(world))

    # convergence equivalence: the masked steps excluded rank 5's shard,
    # so trajectories differ — but training carried through and landed
    # within the pinned envelope of the uninterrupted baseline
    final = losses[-1]
    base_final = float(np.mean(np.asarray(base_loss)))
    assert abs(final - base_final) <= 0.05, (
        f"elastic final loss {final:.4f} vs baseline {base_final:.4f}"
    )


# --------------------------------------------------------------------------- #
# review-hardening regressions
# --------------------------------------------------------------------------- #

def test_late_old_step_arrival_does_not_regress_worldview():
    """A relay worker landing its arrival for an OLDER step replays that
    step's barrier but must not roll the WorldView back to the older fault
    state (or clobber independently installed relay demotions)."""
    plan = FaultPlan(
        [
            FaultEvent(step=6, kind="down", rank=2),
        ],
        world=4,
    )
    logic = CoordinatorLogic(
        4, relay_threshold=30.0, time_slot=0.01, fault_timeout=30.0,
        fault_plan=plan,
    )
    # an independent slow-rank demotion (not from the plan)
    logic.observe_step_medians({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.5})
    assert logic.worldview().relays == frozenset({3})

    # fast ranks reach step 6: the plan kills rank 2
    threads = [
        threading.Thread(target=logic.hook_arrive, kwargs={"step": 6, "rank": r})
        for r in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    wv = logic.worldview()
    assert wv.dead == frozenset({2}) and wv.relays == frozenset({3})
    epoch = wv.epoch

    # a straggler lands its arrival for the OLD healthy step 4: the world
    # picture must not regress (rank 2 stays dead, rank 3 stays demoted).
    # Alone at step 4's barrier it is the sole leader, which leaves after
    # ``fault_timeout`` with its singleton list: the fold into the world
    # picture is done before that wait, so the wait is cut to a moment for
    # this one arrival (the healthy round above kept the 30 s)
    logic.fault_timeout = 0.05
    logic.hook_arrive(step=4, rank=1)
    wv2 = logic.worldview()
    assert wv2.dead == frozenset({2}), "old-step arrival resurrected a dead rank"
    assert wv2.relays == frozenset({3}), "old-step arrival dropped a demotion"
    assert wv2.epoch == epoch, "old-step arrival churned the epoch"


def test_reemit_inherits_incumbent_data_plane(mesh4):
    base = Strategy.ring(4)
    base.chunk_bytes = 123_456
    degraded = reemit_for_active(4, [0, 1, 3], like=base)
    assert degraded.chunk_bytes == 123_456
    assert degraded.wire_dtype == base.wire_dtype
    # the standby cache threads the engine's incumbent through build()
    eng = CollectiveEngine(mesh4, base)
    cache = StandbyPlanCache(eng, nbytes=32)
    for plan in cache.build():
        assert plan.strategy.chunk_bytes == 123_456, plan.label


def test_simulate_fault_plan_stamps_step0_fault():
    from adapcc_tpu.sim.calibrate import load_or_default
    from adapcc_tpu.sim.replay import simulate_fault_plan

    plan = FaultPlan([FaultEvent(step=0, kind="down", rank=1)], world=4)
    rows = simulate_fault_plan(
        Strategy.ring(4), load_or_default(world=4), 1 << 16, plan
    )
    assert rows[0].swapped and rows[0].epoch == 1
    assert rows[0].detection_s > 0 and rows[0].swap_s > 0


def test_epoch_retry_first_attempt_is_immediate(tmp_path, mesh4):
    import time as _time

    from adapcc_tpu.communicator import (
        EPOCH_RETRY_BACKOFF_S,
        Communicator,
    )
    from adapcc_tpu.config import CommArgs

    args = CommArgs(
        topology_dir=str(tmp_path),
        strategy_file=str(tmp_path / "strategy.xml"),
        logical_graph=str(tmp_path / "lg.xml"),
    )
    comm = Communicator(args, mesh=mesh4)
    calls = []

    def one_mismatch(ep):
        calls.append(ep)
        if len(calls) == 1:
            raise EpochMismatch(ep, ep + 1)
        return "ok"

    t0 = _time.perf_counter()
    assert comm._dispatch_with_epoch_retry(one_mismatch, 0) == "ok"
    # the single-swap race resolves without paying any backoff sleep
    assert _time.perf_counter() - t0 < EPOCH_RETRY_BACKOFF_S
    assert calls == [0, 1]


def test_train_ddp_rejects_fault_plan_outside_ddp_mode(tmp_path, monkeypatch):
    from adapcc_tpu.elastic import FAULT_PLAN_ENV
    from adapcc_tpu.workloads.train_ddp import main as train_main

    path = tmp_path / "plan.json"
    FaultPlan([FaultEvent(step=1, kind="down", rank=1)], world=4).save(str(path))
    monkeypatch.setenv(FAULT_PLAN_ENV, str(path))
    with pytest.raises(ValueError, match="requires --dp-mode ddp"):
        train_main(["--dp-mode", "zero1", "--steps", "1"])


# --------------------------------------------------------------------------- #
# redundant shard placement + durable recovery (PR 13, docs/RECOVERY.md)
# --------------------------------------------------------------------------- #

def test_replica_placement_prefers_off_host_and_balances():
    from adapcc_tpu.elastic.redundancy import replica_placement

    # 2 hosts x 4 ranks: every holder must sit on the OTHER host (a host
    # loss must never take a shard and all its replicas together)
    ips = {r: f"10.0.0.{r // 4}" for r in range(8)}
    placement = replica_placement(8, ips, replicas=1)
    for r, holders in placement.items():
        assert len(holders) == 1
        assert ips[holders[0]] != ips[r]
        assert holders[0] != r
    # balance: the 4 same-host primaries spread over 4 distinct off-host
    # holders instead of piling onto one neighbor
    host0_holders = [placement[r][0] for r in range(4)]
    assert len(set(host0_holders)) == 4
    # single-host world (the CPU rig): ring-neighbor fallback
    flat = replica_placement(4, None, replicas=1)
    assert flat == {0: (1,), 1: (2,), 2: (3,), 3: (0,)}
    # k=2 keeps holders distinct and never self
    k2 = replica_placement(4, None, replicas=2)
    for r, holders in k2.items():
        assert len(set(holders)) == 2 and r not in holders
    # validation
    with pytest.raises(ValueError, match="replicas"):
        replica_placement(2, None, replicas=2)
    with pytest.raises(ValueError, match="world"):
        replica_placement(0, None, replicas=0)


def test_shard_replicas_env_funnel(monkeypatch):
    from adapcc_tpu.elastic.redundancy import shard_replicas

    monkeypatch.delenv("ADAPCC_SHARD_REPLICAS", raising=False)
    assert shard_replicas() == 1
    assert shard_replicas(default=0) == 0
    monkeypatch.setenv("ADAPCC_SHARD_REPLICAS", "2")
    assert shard_replicas(default=0) == 2
    monkeypatch.setenv("ADAPCC_SHARD_REPLICAS", "chatty")
    with pytest.raises(ValueError, match="ADAPCC_SHARD_REPLICAS"):
        shard_replicas()
    monkeypatch.setenv("ADAPCC_SHARD_REPLICAS", "-1")
    with pytest.raises(ValueError, match=">= 0"):
        shard_replicas()


def test_replica_store_capture_freshness_and_reconstruct(mesh4):
    from adapcc_tpu.elastic.redundancy import ShardReplicaStore
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer

    _, params = _tiny_params()
    opt = Zero1Optimizer(optax.adam(1e-3), mesh4)
    master, opt_state = opt.init(params)
    pair = (np.asarray(master), jax.device_get(opt_state))

    store = ShardReplicaStore(4, replicas=1)
    # repair before any capture refuses loudly (replication must run
    # before the first failure it is supposed to survive)
    with pytest.raises(KeyError, match="no replica held"):
        store.payload_for(2)
    store.capture(pair, step=7)
    assert store.captures == 1 and store.replica_step(2) == 7

    # simulate rank 2's shard being lost: zero its rows, then reconstruct
    lost_master = pair[0].copy()
    lost_master[2] = 0.0
    lost_opt = jax.tree_util.tree_map(
        lambda leaf: _zero_row(leaf, 2, 4), pair[1]
    )
    fixed_master, fixed_opt = store.reconstruct(
        (lost_master, lost_opt), dead=[2], step=7
    )
    np.testing.assert_array_equal(fixed_master, pair[0])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        fixed_opt,
        pair[1],
    )
    # the freshness guard: a replica stamped with a different step refuses
    # loudly rather than silently rewinding one shard's optimizer state
    with pytest.raises(ValueError, match="rewind"):
        store.reconstruct((lost_master, lost_opt), dead=[2], step=8)
    with pytest.raises(ValueError, match="outside world"):
        store.reconstruct((lost_master, lost_opt), dead=[9])
    # store construction guards
    with pytest.raises(ValueError, match="replicas >= 1"):
        ShardReplicaStore(4, replicas=0)


def _zero_row(leaf, rank, world):
    arr = np.asarray(leaf)
    if arr.ndim >= 1 and arr.shape[0] == world:
        arr = arr.copy()
        arr[rank] = 0
    return arr


def test_zero1_replica_repair_is_convergence_equivalent(mesh4):
    """The acceptance property on the data plane: kill a rank's shard
    mid-run, repair it from the in-fabric replica (NO checkpoint reload),
    and training continues exactly like the uninterrupted run."""
    from adapcc_tpu.elastic import recover_zero1_trainer_state

    model, params = _tiny_params()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    def make():
        return DDPTrainer(
            loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4),
            zero1=True, shard_replicas=1,
        )

    t = make()
    s = t.init_state(params)
    assert t.replica_store is not None
    for _ in range(2):
        s, _ = t.step(s, (x, y))
    # the piggyback window ran every step, stamped with the completed step
    assert t.replica_store.captures == 2
    assert t.replica_store.replica_step(1) == 2

    # branch B: rank 1's shard is lost (its HBM died with it) and is
    # repaired from the step-2 replica; training resumes on the repaired
    # state (repair FIRST — later captures overwrite the held rows, which
    # is exactly what the freshness guard polices)
    master, opt_state = np.asarray(s.opt_state[0]), jax.device_get(
        s.opt_state[1]
    )
    master = master.copy()
    master[1] = np.nan  # the dead rank's single-owner state is GONE
    opt_state = jax.tree_util.tree_map(
        lambda leaf: _nan_row(leaf, 1, 4), opt_state
    )
    broken = TrainState(
        params=s.params, opt_state=(master, opt_state),
        step=s.step, model_state=s.model_state,
    )
    sb = recover_zero1_trainer_state(t, broken, dead=[1], store=t.replica_store)
    for _ in range(2):
        sb, _ = t.step(sb, (x, y), step_idx=2)

    # branch A: the uninterrupted twin on an identical fresh trainer
    ta = make()
    ta.init_state(params)
    sa = s
    for _ in range(2):
        sa, _ = ta.step(sa, (x, y), step_idx=2)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        sa.params,
        sb.params,
    )


def _nan_row(leaf, rank, world):
    arr = np.asarray(leaf)
    if arr.ndim >= 1 and arr.shape[0] == world and np.issubdtype(
        arr.dtype, np.floating
    ):
        arr = arr.copy()
        arr[rank] = np.nan
    return arr


def test_grow_zero1_trainer_state_roundtrips_through_funnel(mesh8, mesh4):
    """The rejoin path's grow-back: a world-4 ZeRO-1 state re-balances
    onto the full world-8 mesh through the same layout-guard funnel as a
    shrink, preserving canonical content exactly."""
    from adapcc_tpu.elastic import grow_zero1_trainer_state
    from adapcc_tpu.parallel.fsdp import _flatten_meta

    model, params = _tiny_params()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    t4 = DDPTrainer(loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4), zero1=True)
    s4 = t4.init_state(params)
    for _ in range(2):
        s4, _ = t4.step(s4, (x, y))

    t8 = DDPTrainer(loss_fn, optax.adam(1e-2), mesh8, Strategy.ring(8), zero1=True)
    t8.init_state(s4.params)
    s8 = grow_zero1_trainer_state(t8, s4)
    meta4 = _flatten_meta(params, 4, 1)
    meta8 = _flatten_meta(params, 8, 1)
    flat4 = np.asarray(s4.opt_state[0]).reshape(-1)[: meta4.total]
    flat8 = np.asarray(s8.opt_state[0]).reshape(-1)[: meta8.total]
    np.testing.assert_array_equal(flat4, flat8)
    # and training continues on the grown world, convergence-equivalent
    sa, sb = s4, s8
    for _ in range(2):
        sa, _ = t4.step(sa, (x, y), step_idx=2)
        sb, _ = t8.step(sb, (x, y), step_idx=2)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6
        ),
        sa.params,
        sb.params,
    )
    # direction guards: a grow that shrinks (or vice versa) is refused
    with pytest.raises(ValueError, match="grow_zero1_trainer_state"):
        grow_zero1_trainer_state(t4, s8)
    with pytest.raises(ValueError, match="shrink_zero1_trainer_state"):
        shrink_zero1_trainer_state(t8, s4)


def test_trainer_shard_replicas_validation(mesh4, monkeypatch):
    def loss_fn(p, batch):
        return jnp.mean(p["w"] ** 2)

    with pytest.raises(ValueError, match="requires zero1=True"):
        DDPTrainer(
            loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4),
            shard_replicas=1,
        )
    # malformed env dies at construction, not at the first capture
    monkeypatch.setenv("ADAPCC_SHARD_REPLICAS", "many")
    with pytest.raises(ValueError, match="ADAPCC_SHARD_REPLICAS"):
        DDPTrainer(
            loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4), zero1=True,
        )


def test_replication_overhead_pricing_bounds():
    """The sim terms behind make recovery-bench: k=1 upkeep under 5% of
    step comm at the default config, repair strictly cheaper than a
    checkpoint reload, replication off exactly free."""
    from adapcc_tpu.sim.cost_model import (
        DEFAULT_COEFFS,
        ICI,
        LinkCoeffs,
        recovery_cost,
        replica_repair_time,
        replication_overhead_time,
    )

    coeffs = LinkCoeffs(*DEFAULT_COEFFS[ICI])
    nbytes = 64 << 20
    assert replication_overhead_time(8, 3 * nbytes, coeffs, replicas=0) == 0.0
    one = replication_overhead_time(8, 3 * nbytes, coeffs, replicas=1)
    two = replication_overhead_time(8, 3 * nbytes, coeffs, replicas=2)
    assert 0.0 < one < two
    cost = recovery_cost(32, nbytes, coeffs)
    assert cost["replication_overhead_ratio"] < 0.05
    assert cost["replica_repair_s"] < cost["ckpt_reload_s"]
    assert cost["repair_speedup"] > 1.0
    # warm swap is the point: a cold repair pays the compile on top
    assert replica_repair_time(8, nbytes, coeffs, standby_cached=False) > (
        replica_repair_time(8, nbytes, coeffs, standby_cached=True)
    )
    with pytest.raises(ValueError, match="replicas"):
        replication_overhead_time(2, nbytes, coeffs, replicas=2)
    with pytest.raises(ValueError, match="save_interval"):
        recovery_cost(8, nbytes, coeffs, save_interval_steps=0)
