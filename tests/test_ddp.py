"""DDP plane: bucketing round-trip, hook sync semantics, end-to-end training."""

import jax
import jax.numpy as jnp
import numpy as np
import re
import optax
import pytest
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.ddp import DDPTrainer, TrainState, build_bucket_plan
from adapcc_tpu.ddp.bucketing import flatten_to_buckets, unflatten_from_buckets
from adapcc_tpu.ddp.hook import GradSyncHook
from adapcc_tpu.models import MLP
from adapcc_tpu.strategy.ir import Strategy


def tree_close(a, b):
    jax.tree_util.tree_map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5), a, b)


# --------------------------------------------------------------------------- #
# bucketing
# --------------------------------------------------------------------------- #

def test_bucket_roundtrip():
    tree = {
        "a": jnp.arange(12.0).reshape(3, 4),
        "b": {"w": jnp.ones((5, 5)), "bias": jnp.zeros((5,))},
    }
    plan = build_bucket_plan(tree, bucket_cap_mb=100)
    buckets = flatten_to_buckets(plan, tree)
    assert sum(b.size for b in buckets) == 12 + 25 + 5
    back = unflatten_from_buckets(plan, buckets)
    tree_close(tree, back)


def test_bucket_cap_splits():
    # ~4KB leaves with 0.004MB cap → multiple buckets
    tree = [jnp.ones((1024,)) for _ in range(4)]
    plan = build_bucket_plan(tree, bucket_cap_mb=0.004)
    assert plan.num_buckets == 4
    assert all(s == 1024 for s in plan.bucket_sizes)
    # chunk heuristic: small buckets get size/4 bytes
    assert plan.chunk_bytes[0] == 1024  # 4096 bytes / 4


def test_bucket_chunk_heuristic_large():
    tree = [jnp.ones((4 * 1024 * 1024,))]  # 16 MB > 10 MB threshold
    plan = build_bucket_plan(tree, bucket_cap_mb=100)
    assert plan.chunk_bytes[0] == 4 * 1024 * 1024


# --------------------------------------------------------------------------- #
# hook sync inside shard_map
# --------------------------------------------------------------------------- #

def test_hook_sync_matches_mean(mesh8):
    strategy = Strategy.ring(8, num_trans=2)
    hook = GradSyncHook(strategy)
    grads = {
        "w": jnp.stack([jnp.full((3, 3), float(r + 1)) for r in range(8)]),
        "b": jnp.stack([jnp.full((7,), float(r + 1)) for r in range(8)]),
    }
    mask = jnp.ones((8,), dtype=bool)

    fn = jax.shard_map(
        hook.sync, mesh=mesh8, in_specs=(P(RANKS_AXIS), P()), out_specs=P(RANKS_AXIS), check_vma=False
    )
    out = fn(grads, mask)
    tree_close(out["w"], jnp.full((8, 3, 3), 4.5))  # mean of 1..8
    tree_close(out["b"], jnp.full((8, 7), 4.5))


def test_hook_sync_subset_average(mesh8):
    strategy = Strategy.binary(8)
    hook = GradSyncHook(strategy)
    grads = {"w": jnp.stack([jnp.full((4,), float(r + 1)) for r in range(8)])}
    mask = jnp.asarray([True, True, False, True, False, False, False, False])

    fn = jax.shard_map(
        hook.sync, mesh=mesh8, in_specs=(P(RANKS_AXIS), P()), out_specs=P(RANKS_AXIS), check_vma=False
    )
    out = fn(grads, mask)
    tree_close(out["w"], jnp.full((8, 4), (1 + 2 + 4) / 3))


# --------------------------------------------------------------------------- #
# end-to-end DDP training
# --------------------------------------------------------------------------- #

def make_regression_task(seed=0, n=256, d=8):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(d, 1))
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (x @ w + 0.01 * rng.normal(size=(n, 1))).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(y)


def test_ddp_training_loss_decreases(mesh8):
    model = MLP(features=(16, 1))
    x, y = make_regression_task()
    params = model.init(jax.random.PRNGKey(0), x[:1])

    def loss_fn(params, batch):
        bx, by = batch
        pred = model.apply(params, bx)
        return jnp.mean((pred - by) ** 2)

    trainer = DDPTrainer(
        loss_fn,
        optax.adam(1e-2),
        mesh8,
        Strategy.ring(8, num_trans=2),
        use_xla_fastpath=False,
    )
    state = TrainState.create(params, trainer.tx)

    losses = []
    for i in range(30):
        state, loss = trainer.step(state, (x, y), step_idx=i)
        losses.append(float(jnp.mean(loss)))
    assert losses[-1] < losses[0] * 0.2, losses[::10]


def test_ddp_matches_single_device_sgd(mesh8):
    """DP over 8 shards with AVG sync ≡ full-batch gradient descent."""
    model = MLP(features=(4, 1))
    x, y = make_regression_task(n=64)
    params = model.init(jax.random.PRNGKey(1), x[:1])
    tx = optax.sgd(0.1)

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    trainer = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False)
    state = TrainState.create(params, tx)
    state, _ = trainer.step(state, (x, y), step_idx=0)

    # single-device oracle
    ref_state = TrainState.create(params, tx)
    g = jax.grad(loss_fn)(ref_state.params, (x, y))
    updates, _ = tx.update(g, ref_state.opt_state, ref_state.params)
    ref_params = optax.apply_updates(ref_state.params, updates)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6),
        state.params,
        ref_params,
    )


def test_async_relay_folds_straggler_gradients(mesh8):
    """Async (non-BSP) relay mode, reference commu.py:160-170,427-431: a rank
    masked out of step k must still deliver its step-k gradients — they fold
    into the step-k+1 allreduce.  BSP mode keeps the drop semantics."""
    # loss p·mean(b) per rank → grad = mean of the rank's batch shard,
    # independent of p, so the SGD trajectory is computable by hand
    def loss_fn(p, b):
        return p["w"] * jnp.mean(b)

    world, lr = 8, 1.0
    rng = np.random.default_rng(0)
    batch0 = jnp.asarray(rng.normal(size=(world, 4)), jnp.float32)
    batch1 = jnp.asarray(rng.normal(size=(world, 4)), jnp.float32)
    params = {"w": jnp.zeros(())}
    mask_k = jnp.asarray([True] * 7 + [False])  # rank 7 misses step 0
    full = jnp.ones((world,), dtype=bool)

    shard_means = np.asarray(batch0).reshape(world, -1).mean(axis=1)
    shard_means1 = np.asarray(batch1).reshape(world, -1).mean(axis=1)

    def run(bsp):
        tx = optax.sgd(lr)
        tr = DDPTrainer(
            loss_fn, tx, mesh8, Strategy.ring(world), use_xla_fastpath=False,
            bsp=bsp, dynamic_mask=True,
        )
        st = TrainState.create(params, tx)
        st, _ = tr.step(st, batch0, active_mask=mask_k)
        st, _ = tr.step(st, batch1, active_mask=full)
        return float(st.params["w"])

    # step 0: active ranks average their 7 shard-mean grads
    g0 = shard_means[:7].mean()
    # step 1 async: all 8 grads plus rank 7's banked step-0 grad, /8
    g1_async = (shard_means1.sum() + shard_means[7]) / world
    g1_bsp = shard_means1.mean()

    np.testing.assert_allclose(run(bsp=False), -lr * (g0 + g1_async), rtol=1e-5)
    np.testing.assert_allclose(run(bsp=True), -lr * (g0 + g1_bsp), rtol=1e-5)


def test_async_relay_accumulates_across_consecutive_misses(mesh8):
    """A rank masked out twice banks both steps' gradients and delivers the
    sum when readmitted."""
    def loss_fn(p, b):
        return p["w"] * jnp.mean(b)

    world, lr = 8, 1.0
    rng = np.random.default_rng(3)
    batches = [jnp.asarray(rng.normal(size=(world, 2)), jnp.float32) for _ in range(3)]
    params = {"w": jnp.zeros(())}
    tx = optax.sgd(lr)
    tr = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(world), use_xla_fastpath=False,
        bsp=False, dynamic_mask=True,
    )
    st = TrainState.create(params, tx)
    miss = jnp.asarray([True] * 7 + [False])
    st, _ = tr.step(st, batches[0], active_mask=miss)
    st, _ = tr.step(st, batches[1], active_mask=miss)
    st, _ = tr.step(st, batches[2])  # full world by default

    m = [np.asarray(b).reshape(world, -1).mean(axis=1) for b in batches]
    g0 = m[0][:7].mean()
    g1 = m[1][:7].mean()
    g2 = (m[2].sum() + m[0][7] + m[1][7]) / world
    np.testing.assert_allclose(
        float(st.params["w"]), -lr * (g0 + g1 + g2), rtol=1e-5
    )


def test_trainer_rejects_mask_misconfigurations(mesh8):
    loss = lambda p, b: jnp.zeros(())  # noqa: E731
    with pytest.raises(ValueError, match="dynamic-mask"):
        DDPTrainer(
            loss, optax.sgd(0.1), mesh8, Strategy.ring(8),
            communicator=object(), dynamic_mask=False,
        )
    with pytest.raises(ValueError, match="active mask"):
        DDPTrainer(
            loss, optax.sgd(0.1), mesh8, Strategy.ring(8),
            bsp=False, dynamic_mask=False,
        )


def test_trainer_rebuild_recompiles(mesh8):
    model = MLP(features=(4, 1))
    x, y = make_regression_task(n=64)
    params = model.init(jax.random.PRNGKey(2), x[:1])

    def loss_fn(p, batch):
        bx, by = batch
        return jnp.mean((model.apply(p, bx) - by) ** 2)

    trainer = DDPTrainer(loss_fn, optax.sgd(0.1), mesh8, Strategy.ring(8), use_xla_fastpath=False)
    state = TrainState.create(params, trainer.tx)
    state, _ = trainer.step(state, (x, y))
    trainer.rebuild(Strategy.binary(8, num_trans=2))
    assert trainer._compiled is None
    state, loss = trainer.step(state, (x, y))
    assert np.isfinite(float(jnp.mean(loss)))


def test_scan_steps_matches_sequential(mesh4):
    """n scanned steps in one dispatch == n sequential step() calls."""
    import optax

    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.models.mlp import MLP
    from adapcc_tpu.strategy.ir import Strategy

    model = MLP(features=(8, 4))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 6)), jnp.float32)
    y = jnp.asarray(np.random.default_rng(1).integers(0, 4, size=(8,)))
    params = model.init(jax.random.PRNGKey(0), x)

    def loss_fn(p, batch):
        xb, yb = batch
        logits = model.apply(p, xb)
        return optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()

    tx = optax.sgd(1e-2)
    t_seq = DDPTrainer(loss_fn, tx, mesh4, Strategy.ring(4))
    t_scan = DDPTrainer(loss_fn, tx, mesh4, Strategy.ring(4))

    s_seq = TrainState.create(params, tx)
    losses_seq = []
    for _ in range(3):
        s_seq, loss = t_seq.step(s_seq, (x, y))
        losses_seq.append(np.asarray(loss))
    s_scan, losses_scan = t_scan.scan_steps(TrainState.create(params, tx), (x, y), 3)

    assert losses_scan.shape == (4, 3)
    np.testing.assert_allclose(
        np.stack(losses_seq, axis=1), np.asarray(losses_scan), atol=1e-6
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_scan.params), jax.tree_util.tree_leaves(s_seq.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_scan_steps_rejects_dynamic_modes(mesh4):
    import optax

    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy

    tx = optax.sgd(1e-2)
    t = DDPTrainer(lambda p, b: jnp.sum(p["w"] * b), tx, mesh4, Strategy.ring(4), bsp=False)
    state = TrainState.create({"w": jnp.ones(())}, tx)
    with pytest.raises(ValueError, match="scan_steps"):
        t.scan_steps(state, jnp.ones((4, 1)), 2)


def test_rebuild_invalidates_scan_cache(mesh4):
    import optax

    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy

    tx = optax.sgd(1e-2)
    t = DDPTrainer(
        lambda p, b: jnp.sum((p["w"] - jnp.mean(b)) ** 2), tx, mesh4, Strategy.ring(4)
    )
    state = TrainState.create({"w": jnp.ones(())}, tx)
    t.scan_steps(state, jnp.ones((4, 2)), 2)
    assert t._scan_cache, "scan program should be cached"
    t.rebuild(Strategy.binary(4))
    assert not t._scan_cache, "rebuild must drop scanned programs too"


# ---------------------------------------------------------------- grad accum


def test_accum_steps_match_full_batch(mesh8):
    """accum_steps=2 must reproduce the accum_steps=1 trajectory exactly:
    for a mean loss, the mean over equal microbatches is the batch mean."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(6, 4)) * 0.3, jnp.float32)}

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    x = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    tx = optax.adam(1e-2)

    def run(accum):
        tr = DDPTrainer(
            loss_fn, tx, mesh8, Strategy.ring(8), accum_steps=accum,
        )
        st = TrainState.create(jax.tree_util.tree_map(jnp.array, params), tx)
        losses = []
        for _ in range(3):
            st, loss = tr.step(st, (x, y))
            losses.append(float(jnp.mean(loss)))
        return st, losses

    st1, l1 = run(1)
    st2, l2 = run(2)
    np.testing.assert_allclose(l2, l1, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(st2.params["w"]), np.asarray(st1.params["w"]), rtol=1e-6, atol=1e-7
    )


def test_accum_steps_rejects_nondivisible(mesh8):
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tr = DDPTrainer(
        loss_fn, optax.sgd(0.1), mesh8, Strategy.ring(8), accum_steps=3,
    )
    st = TrainState.create({"w": jnp.ones((4, 2))}, optax.sgd(0.1))
    batch = jnp.ones((16, 4))  # 2 per rank, not divisible by 3
    with pytest.raises(ValueError, match="not divisible by accum_steps"):
        tr.step(st, batch)


def test_accum_steps_in_scan_steps(mesh8):
    """Accumulation composes with the scanned multi-step dispatch."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.sgd(0.05)
    tr = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), accum_steps=2)
    st = TrainState.create({"w": jnp.ones((4, 2))}, tx)
    batch = jnp.asarray(np.random.default_rng(1).normal(size=(16, 4)), jnp.float32)
    st, losses = tr.scan_steps(st, batch, 3)
    assert losses.shape == (8, 3)
    l = np.asarray(losses).mean(axis=0)
    assert l[-1] < l[0]


# ---------------------------------------------------------- driver dp modes


@pytest.mark.parametrize("mode", ["fsdp", "zero1"])
def test_train_ddp_sharded_dp_modes(mode, capsys):
    """--dp-mode fsdp/zero1 run the sharded-state data plane end to end;
    the fsdp leg genuinely shards (min-shard-elems lowered for the mlp)."""
    from adapcc_tpu.workloads.train_ddp import main as ddp_main

    ddp_main([
        "--model", "mlp", "--steps", "4", "--batch", "16",
        "--dp-mode", mode, "--entry_point", "-1", "--world", "4",
        "--min-shard-elems", "1",
    ])
    out = capsys.readouterr().out
    assert f"mode={mode}" in out and "step    3" in out
    if mode == "fsdp":
        m = re.search(r"fsdp: (\d+)/(\d+) leaves sharded", out)
        assert m and int(m.group(1)) > 0, out


def test_train_ddp_zero1_ring_cli(capsys):
    """--zero1-ring rides the Pallas ring data plane through the CLI."""
    from adapcc_tpu.workloads.train_ddp import main as ddp_main

    ddp_main([
        "--model", "mlp", "--steps", "2", "--batch", "16",
        "--dp-mode", "zero1", "--zero1-ring", "--entry_point", "-1",
        "--world", "4",
    ])
    out = capsys.readouterr().out
    assert "mode=zero1" in out and "step    1" in out


def test_train_ddp_zero1_ring_requires_zero1_mode():
    from adapcc_tpu.workloads.train_ddp import main as ddp_main

    with pytest.raises(ValueError, match="--zero1-ring requires"):
        ddp_main([
            "--model", "mlp", "--steps", "1", "--dp-mode", "ddp",
            "--zero1-ring", "--entry_point", "-1", "--world", "4",
        ])


def test_train_ddp_sharded_mode_rejects_relay_flags():
    """The incompatible-flag error fires before any AdapCC/coordinator side
    effects (no gRPC server or engine is started for the doomed run)."""
    from adapcc_tpu.workloads.train_ddp import main as ddp_main

    with pytest.raises(ValueError, match="require --dp-mode ddp"):
        ddp_main([
            "--model", "mlp", "--steps", "1", "--dp-mode", "fsdp",
            "--coordinator", "--entry_point", "-1", "--world", "4",
        ])


# ---------------------------------------------------------- zero1 composition


def test_zero1_ddp_matches_plain_ddp(mesh8):
    """zero1=True reproduces the replicated trainer's trajectory exactly —
    adaptive sync + sharded optimizer is a memory layout, not new math."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(rng.normal(size=(6, 4)) * 0.3, jnp.float32),
        "b": jnp.zeros((4,), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    tx = optax.adam(1e-2)

    plain = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8))
    z = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), zero1=True)
    sp, sz = plain.init_state(params), z.init_state(params)
    # the zero1 state is genuinely sharded: 1/8 of the flat master per device
    master, _ = sz.opt_state
    assert master.shape[0] == 8
    assert master.addressable_shards[0].data.shape == (1, master.shape[1])
    for i in range(3):
        sp, lp = plain.step(sp, (x, y), step_idx=i)
        sz, lz = z.step(sz, (x, y), step_idx=i)
        np.testing.assert_allclose(
            np.asarray(jnp.mean(lz)), np.asarray(jnp.mean(lp)), rtol=1e-6
        )
    for k in params:
        np.testing.assert_allclose(
            np.asarray(sz.params[k]), np.asarray(sp.params[k]), rtol=2e-5, atol=2e-6
        )


def test_zero1_ddp_scan_steps(mesh8):
    """zero1 composes with the scanned multi-step dispatch."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.sgd(0.05)
    tr = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), zero1=True)
    st = tr.init_state({"w": jnp.ones((4, 2), jnp.float32)})
    batch = jnp.asarray(np.random.default_rng(1).normal(size=(16, 4)), jnp.float32)
    st, losses = tr.scan_steps(st, batch, 3)
    l = np.asarray(losses).mean(axis=0)
    assert l[-1] < l[0]


def test_trainer_checkpoint_extra_stamps_zero1_layout(mesh8):
    """DDPTrainer.checkpoint_extra stamps the constructed optimizer's layout
    tag (enforced by checkpoint.py's apply_snapshot guard); non-zero1
    trainers pass the extra through untouched, and calling before
    init_state raises rather than guessing the geometry."""

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.sgd(0.1)
    plain = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8))
    assert plain.checkpoint_extra({"note": "kept"}) == {"note": "kept"}

    z = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), zero1=True)
    with pytest.raises(ValueError, match="init_state"):
        z.checkpoint_extra()
    z.init_state({"w": jnp.ones((4, 2), jnp.float32)})
    extra = z.checkpoint_extra({"note": "kept"})
    assert extra["note"] == "kept"
    tag = extra["zero1_layout"]
    assert tag == z._zero1_opt.layout_metadata()
    assert tag["ring"] is False and tag["world"] == 8


def test_zero1_ddp_with_relay_mask(mesh8):
    """zero1 + runtime relay masking: a straggler step still updates from
    the active subset's averaged gradients, states stay consistent."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.sgd(0.1)
    p0 = {"w": jnp.ones((4, 2), jnp.float32)}
    batch = jnp.asarray(np.random.default_rng(2).normal(size=(16, 4)), jnp.float32)
    mask = jnp.asarray([True] * 7 + [False])

    tr = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), zero1=True, dynamic_mask=True,
    )
    st = tr.init_state(p0)
    st, _ = tr.step(st, batch, active_mask=mask)
    # oracle: the replicated trainer under the SAME mask — the masked-step
    # trajectory must match exactly, not just stay finite
    plain = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), dynamic_mask=True)
    sp = plain.init_state(p0)
    sp, _ = plain.step(sp, batch, active_mask=mask)
    np.testing.assert_allclose(
        np.asarray(st.params["w"]), np.asarray(sp.params["w"]), rtol=2e-6
    )


def test_zero1_ddp_rejects_replicated_state(mesh8):
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.sgd(0.1)
    tr = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8), zero1=True)
    bad = TrainState.create({"w": jnp.ones((4, 2))}, tx)
    with pytest.raises(ValueError, match="init_state"):
        tr.step(bad, jnp.ones((16, 4)))


def test_accum_zero1_schedule_mode_compose(mesh8):
    """The full stack in one program — microbatch accumulation, bucketed
    strategy-tree allreduce (no psum fastpath), and the ZeRO-1 sharded
    update — matches the plain replicated psum trainer exactly."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    rng = np.random.default_rng(5)
    params = {
        "w": jnp.asarray(rng.normal(size=(6, 4)) * 0.3, jnp.float32),
        "b": jnp.zeros((4,), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(16, 6)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    tx = optax.adam(1e-2)

    full = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.binary(8), accum_steps=2, zero1=True,
        use_xla_fastpath=False,  # force the bucketed masked-ppermute schedule
    )
    plain = DDPTrainer(loss_fn, tx, mesh8, Strategy.ring(8))
    sf, sp = full.init_state(params), plain.init_state(params)
    for i in range(3):
        sf, lf = full.step(sf, (x, y), step_idx=i)
        sp, lp = plain.step(sp, (x, y), step_idx=i)
        np.testing.assert_allclose(
            float(jnp.mean(lf)), float(jnp.mean(lp)), rtol=1e-6
        )
    for k in params:
        np.testing.assert_allclose(
            np.asarray(sf.params[k]), np.asarray(sp.params[k]), rtol=2e-5, atol=2e-6
        )


# --------------------------------------------------------------------------- #
# stateful loss (SyncBN batch_stats through the compiled step)
# --------------------------------------------------------------------------- #

def _bn_net_and_loss():
    import flax.linen as nn

    class BNNet(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Dense(16)(x)
            x = nn.BatchNorm(
                use_running_average=not train,
                axis_name=RANKS_AXIS if train else None,
                momentum=0.9,
            )(x)
            return nn.Dense(4)(nn.relu(x))

    net = BNNet()

    def loss_fn(p, ms, batch):
        x, y = batch
        logits, upd = net.apply(
            {"params": p, "batch_stats": ms}, x, train=True,
            mutable=["batch_stats"],
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return ce.mean(), upd["batch_stats"]

    return net, loss_fn


@pytest.mark.parametrize("kind", ["plain", "stateful", "zero1"])
def test_the_step_is_traced_once_over_its_first_calls(mesh4, kind):
    """``init_state`` commits what it makes from nothing (the step counter,
    the optimizer's count, a caller's fresh ``model_state``) to the mesh, as
    the step's own outputs are: left uncommitted they typed the first call's
    arguments differently from the second's, and every run traced and
    compiled its step twice."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(8,)))
    if kind == "stateful":
        net, loss_fn = _bn_net_and_loss()
        v0 = net.init(jax.random.PRNGKey(0), x[:1], train=True)
        tr = DDPTrainer(loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4), stateful_loss=True)
        state = tr.init_state(v0["params"], model_state=v0["batch_stats"])
    else:
        params = {"w": jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)}

        def loss_fn(p, b):
            return jnp.mean((b[0] @ p["w"] - jax.nn.one_hot(b[1], 4)) ** 2)

        tr = DDPTrainer(loss_fn, optax.adam(1e-2), mesh4, Strategy.ring(4), zero1=kind == "zero1")
        state = tr.init_state(params)
    for _ in range(3):
        state, loss = tr.step(state, (x, y))
    assert np.isfinite(np.asarray(loss)).all()
    assert tr._compiled._cache_size() == 1


def test_stateful_loss_syncbn_stats_update(mesh4):
    """SyncBN under the adaptive DDP step (reference torchvision-BN DDP,
    main_elastic.py:243-244): batch_stats ride TrainState.model_state,
    update every step, and — because the model psums statistics over the
    mesh axis — stay identical to the full-batch single-device stats."""
    net, loss_fn = _bn_net_and_loss()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(8,)))
    v0 = net.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(1e-2)
    tr = DDPTrainer(loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True)
    state = tr.init_state(v0["params"], model_state=v0["batch_stats"])
    s0 = jax.tree_util.tree_map(np.asarray, state.model_state)
    state, _ = tr.step(state, (x, y))

    moved = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()), state.model_state, s0
    )
    assert any(m > 0 for m in jax.tree_util.tree_leaves(moved))

    # oracle: SyncBN's cross-rank mean/var over [8/4 per rank] must equal
    # the single-device full-batch statistics (same first step, world=1)
    mean = np.asarray(x @ np.asarray(v0["params"]["Dense_0"]["kernel"])
                      + np.asarray(v0["params"]["Dense_0"]["bias"])).mean(0)
    got = np.asarray(state.model_state["BatchNorm_0"]["mean"])
    np.testing.assert_allclose(got, 0.1 * mean, rtol=1e-4, atol=1e-5)


def test_stateful_loss_scan_steps_carries_stats(mesh4):
    net, loss_fn = _bn_net_and_loss()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(8,)))
    v0 = net.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(1e-2)
    tr = DDPTrainer(loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True)
    st_scan = tr.init_state(v0["params"], model_state=v0["batch_stats"])
    st_scan, _ = tr.scan_steps(st_scan, (x, y), 3)

    tr2 = DDPTrainer(loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True)
    st_loop = tr2.init_state(v0["params"], model_state=v0["batch_stats"])
    for _ in range(3):
        st_loop, _ = tr2.step(st_loop, (x, y))
    tree_close(st_scan.model_state, st_loop.model_state)
    tree_close(st_scan.params, st_loop.params)


def test_stateful_loss_accum_carries_stats(mesh4):
    """accum_steps>1 threads model_state through the microbatch scan carry:
    two sequential microbatches must produce the same running stats as two
    manual applications of the EMA update."""
    net, loss_fn = _bn_net_and_loss()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(8,)))
    v0 = net.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(1e-2)
    tr = DDPTrainer(
        loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True, accum_steps=2
    )
    state = tr.init_state(v0["params"], model_state=v0["batch_stats"])
    state, _ = tr.step(state, (x, y))

    # oracle: SyncBN sees the full cross-rank microbatch at each of the two
    # scan iterations; both microbatches share identical global statistics
    # only if the data does — here they differ, so a carry bug (stats from
    # one microbatch only, or the pre-scan stats) produces a different EMA
    h = np.asarray(x @ np.asarray(v0["params"]["Dense_0"]["kernel"])
                   + np.asarray(v0["params"]["Dense_0"]["bias"]))
    # microbatch m on rank r is x[r*2+m]; microbatch m's global batch is
    # ranks' rows [0*2+m, 1*2+m, 2*2+m, 3*2+m]
    m0, m1 = h[0::2].mean(0), h[1::2].mean(0)
    want = 0.9 * (0.9 * 0.0 + 0.1 * m0) + 0.1 * m1
    got = np.asarray(state.model_state["BatchNorm_0"]["mean"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_stateful_loss_masked_step_semantics(mesh4):
    """Relay/masked steps with a stateful loss: the active mask gates
    GRADIENT sync only — the SyncBN statistics still pmean over the full
    axis (a straggler's forward ran on real data), so the committed stats
    equal the full-batch stats while the parameter update excludes the
    masked rank's gradient contribution."""
    net, loss_fn = _bn_net_and_loss()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, size=(8,)))
    v0 = net.init(jax.random.PRNGKey(0), x[:1], train=True)
    tx = optax.sgd(1e-2)

    tr_mask = DDPTrainer(
        loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True,
        dynamic_mask=True,
    )
    st = tr_mask.init_state(v0["params"], model_state=v0["batch_stats"])
    mask = jnp.array([True, True, True, False])
    st_m, _ = tr_mask.step(st, (x, y), active_mask=mask)

    # full-world reference on an identical trainer
    tr_full = DDPTrainer(
        loss_fn, tx, mesh4, Strategy.ring(4), stateful_loss=True,
        dynamic_mask=True,
    )
    st_f, _ = tr_full.step(
        tr_full.init_state(v0["params"], model_state=v0["batch_stats"]),
        (x, y), active_mask=jnp.ones(4, bool),
    )

    # stats identical (full-axis pmean either way) ...
    tree_close(st_m.model_state, st_f.model_state)
    # ... but the params differ: rank 3's gradients were excluded
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        st_m.params, st_f.params,
    )
    assert any(d > 0 for d in jax.tree_util.tree_leaves(diffs))


def test_zero1_ring_ddp_matches_xla_path(mesh8):
    """DDPTrainer(zero1=True, zero1_ring=True): the Pallas-ring data plane
    trains to the same params as the XLA path (VERDICT r4 item 4)."""
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    tx = optax.adam(0.05)
    p0 = {"w": jnp.ones((4, 2), jnp.float32)}
    batch = jnp.asarray(np.random.default_rng(5).normal(size=(16, 4)), jnp.float32)

    states = {}
    for ring in (False, True):
        tr = DDPTrainer(
            loss_fn, tx, mesh8, Strategy.ring(8), zero1=True, zero1_ring=ring,
        )
        st = tr.init_state(p0)
        for _ in range(2):
            st, loss = tr.step(st, batch)
        states[ring] = st
    np.testing.assert_allclose(
        np.asarray(states[True].params["w"]),
        np.asarray(states[False].params["w"]),
        rtol=2e-6, atol=1e-7,
    )


def test_zero1_ring_requires_zero1():
    import optax
    from adapcc_tpu.strategy.ir import Strategy

    with pytest.raises(ValueError, match="zero1_ring"):
        DDPTrainer(
            lambda p, b: jnp.zeros(()), optax.sgd(0.1),
            jax.sharding.Mesh(np.array(jax.devices()[:8]), (RANKS_AXIS,)),
            Strategy.ring(8), zero1_ring=True,
        )


# --------------------------------------------------------------------------- #
# the step's spans and the sync's gauges (docs/OBSERVABILITY.md)
# --------------------------------------------------------------------------- #

STEP_SPANS = ("step.prepare", "step.enqueue", "step.finish")


def _tiny_trainer(mesh, **kw):
    tx = optax.sgd(0.1)
    trainer = DDPTrainer(
        lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx, mesh, Strategy.ring(mesh.devices.size), **kw
    )
    state = TrainState.create({"w": jnp.ones((4, 2), jnp.float32)}, tx)
    batch = jnp.ones((2 * mesh.devices.size, 4), jnp.float32)
    return trainer, state, batch


def _step_timings(trainer):
    t = trainer.hook.metrics.snapshot()["timings"]
    return {k: t[k]["count"] for k in STEP_SPANS if k in t}


def test_step_without_a_profile_records_no_span(mesh4):
    from adapcc_tpu.utils import default_registry

    trainer, state, batch = _tiny_trainer(mesh4)
    assert trainer.hook.metrics is default_registry()

    def spans():
        # the compile path's timings are always on (the first step is a build); the spans are not
        timings = default_registry().snapshot()["timings"]
        return {k: v for k, v in timings.items() if not k.startswith(("compile.", "step.build"))}

    before = spans()
    for _ in range(3):
        state, _ = trainer.step(state, batch)
    assert spans() == before


def test_profiled_steps_tile_the_call_and_carry_the_step_index(mesh4, profile):
    import time

    trainer, state, batch = _tiny_trainer(mesh4)
    state, _ = trainer.step(state, batch)  # compile outside the profile
    n, host = 5, {}
    with profile() as prof:
        for _ in range(n):
            idx = trainer._host_step
            t0 = time.perf_counter()
            state, loss = trainer.step(state, batch)
            host[idx] = time.perf_counter() - t0
    assert _step_timings(trainer) == {k: n for k in STEP_SPANS}
    by_step = {}
    for name, start, dur, stats in prof.spans():
        if name.startswith("adapcc.step."):
            by_step.setdefault(stats["step"], []).append((name[len("adapcc."):], start, dur))
    assert sorted(by_step) == sorted(host) == list(range(1, n + 1))
    for idx, spans in by_step.items():
        spans.sort(key=lambda s: s[1])
        assert tuple(s[0] for s in spans) == STEP_SPANS  # consecutive, in order
        for (_, a, da), (_, b, _) in zip(spans, spans[1:]):
            assert a + da <= b  # flat: no span inside another
        assert sum(s[2] for s in spans) / 1e9 <= host[idx]
    # a second session in the same process starts from zero
    state, _ = trainer.step(state, batch)
    with profile("again"):
        for _ in range(2):
            state, _ = trainer.step(state, batch)
    assert _step_timings(trainer) == {k: 2 for k in STEP_SPANS}


def _sync_gauges(mesh, grads, **hook_kw):
    from adapcc_tpu.utils import MetricsRegistry

    metrics = MetricsRegistry()
    world = mesh.devices.size
    hook = GradSyncHook(Strategy.ring(world), metrics=metrics, **hook_kw)
    stacked = jax.tree_util.tree_map(lambda g: jnp.stack([g] * world), grads)
    jax.jit(jax.shard_map(
        lambda t: hook.sync(jax.tree_util.tree_map(lambda v: v[0], t), None),
        mesh=mesh, in_specs=(P(RANKS_AXIS),), out_specs=P(), check_vma=False,
    )).lower(stacked)  # the gauges are set at trace time
    g = metrics.snapshot()["gauges"]
    return hook, g["grad_sync.bytes"], g["grad_sync.calls"]


@pytest.mark.parametrize("overlap", ["off", "bucket"])
@pytest.mark.parametrize("use_xla_fastpath", [True, False])
def test_grad_sync_gauges_on_every_path(mesh4, overlap, use_xla_fastpath, monkeypatch):
    monkeypatch.delenv("ADAPCC_OVERLAP", raising=False)
    monkeypatch.delenv("ADAPCC_WIRE_DTYPE", raising=False)
    grads = {"w": jnp.ones((96, 32), jnp.float32), "b": jnp.ones((32,), jnp.float32),
             "e": jnp.ones((64, 8), jnp.float32)}
    param_bytes = sum(g.nbytes for g in grads.values())
    kw = dict(overlap=overlap, use_xla_fastpath=use_xla_fastpath, bucket_cap_mb=0.004)
    hook, nbytes, calls = _sync_gauges(mesh4, grads, **kw)
    assert nbytes == param_bytes
    if overlap == "off" and use_xla_fastpath:
        assert hook._plan is None and calls == len(grads)  # one psum per leaf
    else:
        assert nbytes == hook._plan.total_bytes and calls == hook._plan.num_buckets > 1
    # the bf16 wire halves the bytes and leaves the calls
    _, wire_bytes, wire_calls = _sync_gauges(mesh4, grads, compress="bf16", **kw)
    assert (wire_bytes, wire_calls) == (param_bytes / 2, calls)


def test_the_compiled_step_carries_the_names_the_device_trace_is_read_by(mesh4):
    """docs/OBSERVABILITY.md, names on the device side: the module, the
    four scopes and, through the flash path, the three kernels."""
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

    model = GPT2(GPT2Config(vocab_size=64, max_seq=128, n_layer=1, n_head=1, d_model=32, attention="flash"))
    tokens = jnp.zeros((4, 128), jnp.int32)
    tx = optax.sgd(0.1)
    trainer = DDPTrainer(lambda p, b: lm_loss(model.apply(p, b), b), tx, mesh4, Strategy.ring(4))
    state = TrainState.create(model.init(jax.random.PRNGKey(0), tokens[:1]), tx)
    text = trainer._build().lower(state, tokens).as_text(debug_info=True)
    assert "module @jit_ddp_step" in text
    for name in ('"grad_sync/psum"', '"optimizer/', "jvp(GPT2)/lm_head/", "jvp(loss)/", "transpose(jvp(loss))/",
                 # the kernels sit in jitted calls that the layers share: named at the call, and inside it
                 "attn/jit(_fwd_call)", "attn/jit(_bwd_call)", '"flash_fwd/', '"flash_bwd_dq/', '"flash_bwd_dkv/'):
        assert name in text, name
