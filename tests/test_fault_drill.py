"""End-to-end fault drill (VERDICT r4 item 5).

The reference's fault story (proto/rpc_server.py:48-62 + README "fault
tolerance"): a dead rank misses the per-step controller heartbeat, the
coordinator's fault timeout expires, the surviving ranks receive the alive
subset (status 0) and the collectives continue with it instead of hanging;
torchrun-elastic then restarts the world from the newest checkpoint.

This drill exercises the whole chain in one test: healthy negotiated steps →
a rank stops heartbeating mid-training → controller status 0 with the alive
subset → DDPTrainer continues on the masked step (dead rank's gradient
excluded, verified against a hand-computed oracle) → checkpoint save →
elastic-restart restore into a fresh full-world trainer.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from adapcc_tpu.checkpoint import (
    TrainCheckpointState,
    load_checkpoint,
    save_checkpoint,
)
from adapcc_tpu.coordinator.logic import CoordinatorLogic
from adapcc_tpu.ddp import DDPTrainer, TrainState
from adapcc_tpu.models import MLP
from adapcc_tpu.strategy.ir import Strategy


def _controller_round(logic, step, ranks):
    """Per-rank controller heartbeats in threads (each blocks on the
    barrier/timeout); returns {rank: (active, status)}.  The threads
    heartbeat together: every rank's deadline runs from its own arrival, so
    on a loaded host a thread started late would outlive the first one's
    deadline and the ranks would disagree on who is alive."""
    results = {}
    together = threading.Barrier(len(ranks))

    def arrive(r):
        together.wait(timeout=60)
        results[r] = logic.controller_arrive(step=step, rank=r)

    threads = [threading.Thread(target=arrive, args=(r,)) for r in ranks]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def test_fault_drill_heartbeat_to_masked_step_to_restart(mesh8, tmp_path):
    world = 8
    model = MLP(features=(4, 2))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(world, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(world, 2)), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x[:1])

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    lr = 0.1
    tx = optax.sgd(lr)
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(world), dynamic_mask=True
    )
    state = TrainState.create(params, tx)

    # -- phase 1: healthy steps under coordinator negotiation ---------------
    # Nobody is late here, so no deadline may decide a round: eight threads
    # on a host shared with five other test workers do not arrive inside
    # milliseconds of each other.  The collective is priced at a minute, so
    # the leader waits 15 s and more before it buys a partial one, and the
    # relay and fault deadlines are a minute too.
    logic = CoordinatorLogic(
        world, relay_threshold=60.0, time_slot=0.01, fault_timeout=60.0,
        accumulated_size=60.0, accumulated_bandwidth=float(world),
    )
    for step_idx in range(2):
        hook_threads = [
            threading.Thread(target=logic.hook_arrive, kwargs={"step": step_idx, "rank": r})
            for r in range(world)
        ]
        for t in hook_threads:
            t.start()
        for t in hook_threads:
            t.join()
        out = _controller_round(logic, step_idx, range(world))
        statuses = {s for _, s in out.values()}
        assert statuses == {1}, "healthy round must report status 1"
        active, _ = out[0]
        mask = np.zeros((world,), bool)
        mask[sorted(active)] = True
        assert mask.all()
        state, loss = trainer.step(
            state, (x, y), step_idx=step_idx, active_mask=jnp.asarray(mask)
        )
        assert np.isfinite(np.asarray(loss)).all()

    # -- phase 2: rank 5 dies mid-training; heartbeat timeout fires ---------
    # the short heartbeat deadline only here, where a rank is really silent
    dead = 5
    survivors = [r for r in range(world) if r != dead]
    logic.heartbeat_timeout = 1.0
    out = _controller_round(logic, 2, survivors)
    alive_sets = {tuple(sorted(a)) for a, _ in out.values()}
    statuses = {s for _, s in out.values()}
    assert statuses == {0}, "fault timeout must surface status 0"
    assert alive_sets == {tuple(survivors)}, "alive subset must exclude the dead rank"

    # -- phase 3: surviving subset continues through the SAME compiled step --
    mask = np.zeros((world,), bool)
    mask[survivors] = True
    params_before = jax.tree_util.tree_map(np.asarray, state.params)
    state, loss = trainer.step(
        state, (x, y), step_idx=2, active_mask=jnp.asarray(mask)
    )
    assert np.isfinite(np.asarray(loss)).all()

    # oracle: update = lr * mean over SURVIVING ranks' per-shard gradients
    def shard_grad(r):
        return jax.grad(loss_fn)(
            jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(params_before),
                [jnp.asarray(l) for l in jax.tree_util.tree_leaves(params_before)],
            ),
            (x[r : r + 1], y[r : r + 1]),
        )

    grads = [shard_grad(r) for r in survivors]
    mean_g = jax.tree_util.tree_map(
        lambda *gs: np.mean(np.stack([np.asarray(g) for g in gs]), axis=0), *grads
    )
    expect = jax.tree_util.tree_map(
        lambda p, g: p - lr * g, params_before, mean_g
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        ),
        state.params,
        expect,
    )

    # -- phase 4: checkpoint + elastic restart into a fresh full world ------
    ckpt_file = str(tmp_path / "drill.ckpt")
    save_checkpoint(
        TrainCheckpointState(
            params=state.params, opt_state=state.opt_state, epoch=0,
            step=int(state.step),
        ),
        ckpt_file,
    )
    restored = TrainCheckpointState(params=params, opt_state=tx.init(params))
    assert load_checkpoint(restored, ckpt_file)
    trainer2 = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(world))
    state2 = TrainState(
        params=restored.params, opt_state=restored.opt_state, step=restored.step
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        state2.params,
        state.params,
    )
    state2, loss2 = trainer2.step(state2, (x, y))
    assert np.isfinite(np.asarray(loss2)).all()
    assert int(state2.step) == int(state.step) + 1
