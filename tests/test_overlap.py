"""Overlapped gradient sync (docs/OVERLAP.md): mode resolution, bucket-plan
edge cases + observability, chunked engine entries, parity (bitwise for the
bucket-rolling schedule, accumulation-order tolerance for the microbatch
pipeline), ZeRO-1 chunked collectives, cost-model pricing, the overlap
sweep's determinism, and the tuner's measured overlap axis."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.ddp import (
    DDPTrainer,
    OVERLAP_ENV,
    OVERLAP_MODES,
    TrainState,
    build_bucket_plan,
    resolve_overlap_mode,
)
from adapcc_tpu.ddp.bucketing import flatten_to_buckets, unflatten_from_buckets
from adapcc_tpu.ddp.hook import GradSyncHook
from adapcc_tpu.strategy.ir import Strategy


def _linear_workload(rng_seed=0, din=16, dout=8, batch=32):
    rng = np.random.default_rng(rng_seed)
    params = {
        "w": jnp.asarray(rng.normal(size=(din, dout)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(dout,)), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(batch, din)), jnp.float32)

    def loss_fn(p, b):
        return jnp.mean((b @ p["w"] + p["b"]) ** 2)

    return loss_fn, params, x


# --------------------------------------------------------------------------- #
# mode resolution
# --------------------------------------------------------------------------- #


def test_resolve_overlap_mode_precedence(monkeypatch):
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    assert resolve_overlap_mode() == "off"
    assert resolve_overlap_mode("bucket") == "bucket"
    monkeypatch.setenv(OVERLAP_ENV, "microbatch")
    assert resolve_overlap_mode("bucket") == "microbatch"  # env wins
    assert resolve_overlap_mode(None) == "microbatch"


def test_resolve_overlap_mode_malformed_env_raises(monkeypatch):
    monkeypatch.setenv(OVERLAP_ENV, "bucketed")
    with pytest.raises(ValueError, match="ADAPCC_OVERLAP"):
        resolve_overlap_mode("off")


def test_resolve_overlap_mode_bad_arg_raises(monkeypatch):
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    with pytest.raises(ValueError, match="expected one of"):
        resolve_overlap_mode("rolling")


def test_overlap_mode_vocabulary_pinned():
    """One vocabulary across the DDP plane, the cost model, and the tuner
    (string literals on purpose — the drift test IS the coupling)."""
    from adapcc_tpu.sim.cost_model import OVERLAP_MODE_CANDIDATES
    from adapcc_tpu.tuner.policy import HOOK_OVERLAP_MODES

    assert set(OVERLAP_MODES) == set(OVERLAP_MODE_CANDIDATES)
    assert set(OVERLAP_MODES) == set(HOOK_OVERLAP_MODES)


# --------------------------------------------------------------------------- #
# bucket-plan edge cases (satellite: build_bucket_plan coverage)
# --------------------------------------------------------------------------- #


def test_bucket_plan_oversized_leaf_gets_own_bucket():
    # 8 KB cap; the 64 KB leaf cannot split and must land alone, counted
    tree = [jnp.ones((1024,)), jnp.ones((16 * 1024,)), jnp.ones((1024,))]
    plan = build_bucket_plan(tree, bucket_cap_mb=8 / 1024)
    assert plan.oversized_leaves == 1
    big_bucket = plan.leaf_bucket[1]
    assert plan.bucket_sizes[big_bucket] == 16 * 1024  # alone in its bucket
    back = unflatten_from_buckets(plan, flatten_to_buckets(plan, tree))
    for a, b in zip(tree, back):
        assert np.array_equal(a, b)


def test_bucket_plan_scalar_and_empty_shape_leaves():
    tree = {"s": jnp.asarray(3.0), "v": jnp.ones((7,)), "t": jnp.asarray(1.0)}
    plan = build_bucket_plan(tree, bucket_cap_mb=100)
    assert sum(plan.bucket_sizes) == 9
    assert plan.oversized_leaves == 0
    back = unflatten_from_buckets(plan, flatten_to_buckets(plan, tree))
    assert np.asarray(back["s"]).shape == ()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), tree, back
    )


def test_bucket_plan_empty_pytree_raises_loudly():
    with pytest.raises(ValueError, match="no leaves"):
        build_bucket_plan({}, bucket_cap_mb=100)


def test_bucket_plan_deterministic_across_processes():
    """Two processes building the plan from the same model structure must
    agree on every table (the compiled programs exchange bucket vectors):
    dict insertion order must not leak in — pytrees sort dict keys."""
    a = {"w1": jnp.ones((300,)), "w2": jnp.ones((500,)), "b": jnp.ones((9,))}
    b = dict(reversed(list(a.items())))  # different insertion order
    pa = build_bucket_plan(a, bucket_cap_mb=0.001)
    pb = build_bucket_plan(b, bucket_cap_mb=0.001)
    for field in (
        "leaf_shapes", "leaf_bucket", "bucket_sizes", "chunk_bytes",
        "bucket_bytes", "oversized_leaves",
    ):
        assert getattr(pa, field) == getattr(pb, field)


def test_bucket_plan_bucket_bytes_accounting():
    tree = [jnp.ones((1024,), jnp.float32) for _ in range(4)]
    plan = build_bucket_plan(tree, bucket_cap_mb=0.004)
    assert plan.bucket_bytes == (4096,) * 4
    assert plan.total_bytes == 4 * 4096
    # the chunk heuristic the engine now honors: small buckets -> size/4
    assert plan.chunk_bytes == (1024,) * 4


# --------------------------------------------------------------------------- #
# chunked engine entry points (satellite: chunk_bytes plumbed end to end)
# --------------------------------------------------------------------------- #


def test_chunked_allreduce_bitwise_and_dispatch_count(mesh8, monkeypatch):
    """The new engine entry splits the payload into per-chunk collectives
    (the per-bucket chunk_bytes finally reaching the engine) without
    changing a single bit of the result."""
    import adapcc_tpu.comm.engine as engine

    strategy = Strategy.ring(8)
    x = jnp.arange(8 * 1000, dtype=jnp.float32).reshape(8, 1000)
    mask = jnp.ones((8,), dtype=jnp.bool_)
    calls = []
    inner = engine._tree_allreduce_chunk

    def counting(seg, *a, **kw):
        calls.append(int(seg.size))
        return inner(seg, *a, **kw)

    monkeypatch.setattr(engine, "_tree_allreduce_chunk", counting)

    def run(chunk_bytes):
        calls.clear()
        fn = jax.jit(jax.shard_map(
            lambda t, m: engine.chunked_allreduce_shard(
                t[0], m, strategy, axis_name=RANKS_AXIS,
                chunk_bytes=chunk_bytes,
            )[None],
            mesh=mesh8, in_specs=(P(RANKS_AXIS), P()),
            out_specs=P(RANKS_AXIS), check_vma=False,
        ))
        return np.asarray(fn(x, mask)), list(calls)

    whole, whole_calls = run(chunk_bytes=1 << 20)
    chunked, chunk_calls = run(chunk_bytes=1024)  # 256 floats per chunk
    assert whole_calls == []  # single chunk falls through to allreduce_shard
    assert chunk_calls == [256, 256, 256, 232]  # independent dispatches
    assert np.array_equal(whole, chunked)  # bitwise


def test_chunked_allreduce_bitwise_on_multi_tree_strategy(mesh8):
    """Bitwise parity must survive MULTI-tree strategies: the chunked
    dispatch splits by tree share at the whole-payload boundaries before
    chunking, so element→tree assignment (and the per-round add order)
    matches the unchunked dispatch exactly."""
    import adapcc_tpu.comm.engine as engine

    strategy = Strategy.ring(8, num_trans=2)
    assert len(strategy.trees) > 1
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(8, 999)), jnp.float32)
    mask = jnp.ones((8,), dtype=jnp.bool_)

    def run(fn, **kw):
        f = jax.jit(jax.shard_map(
            lambda t, m: fn(
                t[0], m, strategy, axis_name=RANKS_AXIS, **kw
            )[None],
            mesh=mesh8, in_specs=(P(RANKS_AXIS), P()),
            out_specs=P(RANKS_AXIS), check_vma=False,
        ))
        return np.asarray(f(x, mask))

    whole = run(engine.allreduce_shard)
    chunked = run(engine.chunked_allreduce_shard, chunk_bytes=512)
    assert np.array_equal(whole, chunked)


def test_chunked_allreduce_env_override_wins(mesh8, monkeypatch):
    """ADAPCC_RING_CHUNK_BYTES overrides the per-bucket chunk size — the
    one chunk-knob precedence ladder (docs/RING.md)."""
    import adapcc_tpu.comm.engine as engine

    monkeypatch.setenv("ADAPCC_RING_CHUNK_BYTES", "2048")
    calls = []
    inner = engine._tree_allreduce_chunk
    monkeypatch.setattr(
        engine, "_tree_allreduce_chunk",
        lambda seg, *a, **kw: (calls.append(int(seg.size)), inner(seg, *a, **kw))[1],
    )
    x = jnp.ones((8, 1024), jnp.float32)
    fn = jax.jit(jax.shard_map(
        lambda t, m: engine.chunked_allreduce_shard(
            t[0], m, Strategy.ring(8), axis_name=RANKS_AXIS,
            chunk_bytes=256,  # the plan's value, overridden by the env
        )[None],
        mesh=mesh8, in_specs=(P(RANKS_AXIS), P()),
        out_specs=P(RANKS_AXIS), check_vma=False,
    ))
    fn(x, jnp.ones((8,), dtype=jnp.bool_))
    assert calls == [512, 512]  # 2048 B / 4 = 512 floats per chunk


# --------------------------------------------------------------------------- #
# hook: bucket-rolling parity + the chunk-flow trace + observability
# --------------------------------------------------------------------------- #


def _hook_sync(mesh8, grads, **hook_kwargs):
    hook = GradSyncHook(Strategy.ring(8), **hook_kwargs)
    fn = jax.jit(jax.shard_map(
        lambda t: hook.sync(
            jax.tree_util.tree_map(lambda v: v[0], t), None
        ),
        mesh=mesh8, in_specs=(P(RANKS_AXIS),), out_specs=P(),
        check_vma=False,
    ))
    return fn(grads), hook


@pytest.mark.parametrize("sync_mode", ["schedule", "psum"])
def test_hook_bucket_overlap_bitwise(mesh8, sync_mode, monkeypatch):
    """Acceptance parity: the bucket-rolling schedule's synced gradients
    are bitwise-identical to the non-overlapped sync on both data planes."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    rng = np.random.default_rng(3)
    grads = {
        "w": jnp.asarray(rng.normal(size=(8, 96, 32)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(8, 32)), jnp.float32),
    }
    kw = dict(
        use_xla_fastpath=sync_mode == "psum", mode=sync_mode,
        bucket_cap_mb=0.004,
    )
    base, _ = _hook_sync(mesh8, grads, **kw)
    rolled, hook = _hook_sync(mesh8, grads, overlap="bucket", **kw)
    assert hook.overlap == "bucket"
    for a, b in zip(
        jax.tree_util.tree_leaves(base), jax.tree_util.tree_leaves(rolled)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_hook_chunk_bytes_flow_into_dispatch_trace(mesh8, monkeypatch):
    """Satellite: the plan's per-bucket chunk sizes — and their env
    override — are visible in the dispatch trace, asserting the
    plan → engine flow instead of trusting it."""
    from adapcc_tpu.utils.observability import CollectiveTrace

    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    grads = {"w": jnp.ones((8, 4096), jnp.float32)}
    trace = CollectiveTrace()
    _, hook = _hook_sync(
        mesh8, grads, use_xla_fastpath=False, mode="schedule",
        bucket_cap_mb=0.004, overlap="bucket", trace=trace,
    )
    (ev,) = [e for e in trace.events() if e.primitive == "grad_sync"]
    assert ev.impl == "schedule[bucket]"
    assert ev.extra["plan_chunk_bytes"] == list(hook._plan.chunk_bytes)
    assert ev.extra["chunk_bytes"] == list(hook._plan.chunk_bytes)  # no env
    assert ev.extra["buckets"] == hook._plan.num_buckets
    assert ev.extra["overlap"] == "bucket"
    assert ev.extra["exposed_comm_s"] > 0.0
    # the env override rewrites the resolved column, not the plan's
    monkeypatch.setenv("ADAPCC_RING_CHUNK_BYTES", "1024")
    trace2 = CollectiveTrace()
    _, hook2 = _hook_sync(
        mesh8, grads, use_xla_fastpath=False, mode="schedule",
        bucket_cap_mb=0.004, overlap="bucket", trace=trace2,
    )
    (ev2,) = [e for e in trace2.events() if e.primitive == "grad_sync"]
    assert ev2.extra["plan_chunk_bytes"] == list(hook2._plan.chunk_bytes)
    assert ev2.extra["chunk_bytes"] == [1024] * hook2._plan.num_buckets


def test_bucket_plan_observability_metrics(mesh8, monkeypatch):
    """Satellite: bucket count, byte histogram, and oversized-leaf
    occurrences land in the MetricsRegistry at plan-record time."""
    from adapcc_tpu.utils.observability import MetricsRegistry

    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    grads = {
        "big": jnp.ones((8, 8192), jnp.float32),   # 32 KB > 8 KB cap
        "s1": jnp.ones((8, 512), jnp.float32),
        "s2": jnp.ones((8, 512), jnp.float32),
    }
    metrics = MetricsRegistry()
    _, hook = _hook_sync(
        mesh8, grads, use_xla_fastpath=False, mode="schedule",
        bucket_cap_mb=8 / 1024, metrics=metrics,
    )
    snap = metrics.snapshot()
    assert snap["gauges"]["bucket_plan.num_buckets"] == hook._plan.num_buckets
    assert snap["gauges"]["bucket_plan.total_bytes"] == hook._plan.total_bytes
    assert snap["counters"]["bucket_plan.oversized_leaves"] == 1
    # bytes are a unitless sample, not a timing in seconds
    assert "bucket_plan.bucket_bytes" not in snap["timings"]
    hist = snap["samples"]["bucket_plan.bucket_bytes"]
    assert hist["count"] == hook._plan.num_buckets
    assert hist["max"] == max(hook._plan.bucket_bytes)


# --------------------------------------------------------------------------- #
# trainer parity + guard rails
# --------------------------------------------------------------------------- #


def _run_trainer(mesh8, overlap, *, accum=1, steps=3, zero1=False, **kw):
    loss_fn, params, x = _linear_workload()
    tx = optax.adam(1e-2)
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False,
        sync_mode="schedule", overlap=overlap, accum_steps=accum,
        zero1=zero1, **kw,
    )
    state = (
        trainer.init_state(params) if zero1 else TrainState.create(params, tx)
    )
    for s in range(steps):
        state, loss = trainer.step(state, x, step_idx=s)
    return trainer, state


def test_trainer_bucket_overlap_whole_step_parity(mesh8, monkeypatch):
    """Whole-step parity for the bucket schedule.  The synced GRADIENTS are
    bitwise-identical (test_hook_bucket_overlap_bitwise — the acceptance
    contract); across the two *different* compiled step programs XLA may
    fuse/reassociate the surrounding arithmetic differently, so the
    multi-step params are held to fp32-tight tolerance instead."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    _, s_off = _run_trainer(mesh8, "off")
    _, s_b = _run_trainer(mesh8, "bucket")
    for a, b in zip(
        jax.tree_util.tree_leaves(s_off.params),
        jax.tree_util.tree_leaves(s_b.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


#: the asserted accumulation-order tolerance of the microbatch pipeline
#: (sum of synced deltas vs sync of summed deltas, fp32)
MICROBATCH_RTOL = 2e-5
MICROBATCH_ATOL = 1e-6


def test_trainer_microbatch_overlap_within_tolerance(mesh8, monkeypatch):
    """Acceptance parity: the pipelined scan matches the baseline within
    the documented accumulation-order tolerance (asserted, not eyeballed)."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    _, s_off = _run_trainer(mesh8, "off", accum=4)
    _, s_m = _run_trainer(mesh8, "microbatch", accum=4)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_off.params),
        jax.tree_util.tree_leaves(s_m.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            rtol=MICROBATCH_RTOL, atol=MICROBATCH_ATOL,
        )


def test_trainer_microbatch_scan_steps(mesh4, monkeypatch):
    """The pipelined schedule survives the scanned multi-step program."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    loss_fn, params, x = _linear_workload(batch=16)
    tx = optax.sgd(0.1)

    def final(overlap):
        tr = DDPTrainer(
            loss_fn, tx, mesh4, Strategy.ring(4), use_xla_fastpath=False,
            sync_mode="schedule", overlap=overlap, accum_steps=2,
        )
        st, losses = tr.scan_steps(TrainState.create(params, tx), x, 3)
        assert losses.shape == (4, 3)
        return st

    s_off, s_m = final("off"), final("microbatch")
    for a, b in zip(
        jax.tree_util.tree_leaves(s_off.params),
        jax.tree_util.tree_leaves(s_m.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            rtol=MICROBATCH_RTOL, atol=MICROBATCH_ATOL,
        )


def test_microbatch_pipelined_threads_stateful_loss(mesh8, monkeypatch):
    """Stateful losses must see every microbatch sequentially in the
    pipelined scan too — including microbatch 0's update, which must seed
    the scan carry (torch grad-accum semantics, the trainer's contract)."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    loss_fn_plain, params, x = _linear_workload()
    tx = optax.sgd(0.1)

    def stateful_loss(p, ms, b):
        # count microbatches and fold the running batch mean into state —
        # any dropped microbatch shifts both
        count, mean = ms
        return loss_fn_plain(p, b), (count + 1, mean + jnp.mean(b))

    def run(overlap):
        tr = DDPTrainer(
            stateful_loss, tx, mesh8, Strategy.ring(8),
            use_xla_fastpath=False, sync_mode="schedule",
            overlap=overlap, accum_steps=4, stateful_loss=True,
        )
        st = TrainState.create(
            params, tx,
            model_state=(jnp.zeros((), jnp.int32), jnp.zeros(())),
        )
        st, _ = tr.step(st, x)
        return st.model_state

    count_off, mean_off = run("off")
    count_m, mean_m = run("microbatch")
    assert int(count_m) == int(count_off) == 4  # every microbatch counted
    np.testing.assert_allclose(
        np.asarray(mean_m), np.asarray(mean_off), rtol=1e-6
    )


def test_microbatch_guard_rails(mesh8, monkeypatch):
    """Satellite: every incompatible combination rejects at construction."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    loss_fn, params, x = _linear_workload()
    tx = optax.sgd(0.1)

    def build(**kw):
        return DDPTrainer(
            loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False,
            overlap="microbatch", **kw,
        )

    with pytest.raises(ValueError, match="accum_steps >= 2"):
        build()
    with pytest.raises(ValueError, match="BSP"):
        build(accum_steps=2, bsp=False, dynamic_mask=True)
    with pytest.raises(ValueError, match="error_feedback"):
        build(accum_steps=2, grad_compress="int8", error_feedback=True)
    with pytest.raises(ValueError, match="GNS|gns|unsynced"):
        build(accum_steps=2, measure_gns=True)


def test_bucket_overlap_composes_with_error_feedback(mesh8, monkeypatch):
    """Satellite guard rail, the positive half: bucket rolling only changes
    dispatch granularity, so the error-feedback residual threads through
    the pipelined path unchanged — same training trajectory as the
    baseline EF run (fp32-tight: the two compiled programs may fuse the
    surrounding arithmetic differently, see the whole-step parity test)."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    monkeypatch.delenv("ADAPCC_WIRE_DTYPE", raising=False)
    _, s_off = _run_trainer(
        mesh8, "off", grad_compress="int8", error_feedback=True
    )
    _, s_b = _run_trainer(
        mesh8, "bucket", grad_compress="int8", error_feedback=True
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_off.params),
        jax.tree_util.tree_leaves(s_b.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


def test_env_override_steers_trainer(monkeypatch, mesh8):
    monkeypatch.setenv(OVERLAP_ENV, "bucket")
    loss_fn, params, x = _linear_workload()
    trainer = DDPTrainer(
        loss_fn, optax.sgd(0.1), mesh8, Strategy.ring(8),
        use_xla_fastpath=False, overlap="off",
    )
    assert trainer.overlap == "bucket"
    assert trainer.hook.overlap == "bucket"


# --------------------------------------------------------------------------- #
# ZeRO-1: chunked reduce-scatter / all-gather
# --------------------------------------------------------------------------- #


def test_zero1_optimizer_rejects_microbatch(mesh8):
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer

    with pytest.raises(ValueError, match="microbatch"):
        Zero1Optimizer(optax.sgd(0.1), mesh8, overlap="microbatch")


def test_zero1_optimizer_rejects_ring_plus_bucket(mesh8):
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer

    with pytest.raises(ValueError, match="chunk"):
        Zero1Optimizer(optax.sgd(0.1), mesh8, ring=True, overlap="bucket")


def test_even_chunk_bounds_cover_everything():
    from adapcc_tpu.ddp.overlap import even_chunk_bounds

    for total, n in ((10, 3), (8, 8), (7, 20), (0, 4), (5, 1)):
        bounds = even_chunk_bounds(total, n)
        assert sum(length for _, length in bounds) == total
        off = 0
        for o, length in bounds:
            assert o == off
            off += length
        # near-equal: max/min differ by at most one element
        lengths = [length for _, length in bounds if length]
        if lengths:
            assert max(lengths) - min(lengths) <= 1


def test_zero1_train_step_bucket_overlap_bitwise(mesh8, monkeypatch):
    """The chunked RS/AG pair preserves the identity layout: params AND the
    flat master match the single-collective path bit for bit."""
    from adapcc_tpu.parallel import Zero1Optimizer, zero1_train_step

    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    loss_fn, params, x = _linear_workload(din=64, dout=32)
    tx = optax.adam(1e-2)

    def run(overlap, chunk_bytes=None):
        opt = Zero1Optimizer(
            tx, mesh8, overlap=overlap, overlap_chunk_bytes=chunk_bytes
        )
        master, opt_state = opt.init(params)
        step = zero1_train_step(loss_fn, opt, mesh8)
        p = params
        for _ in range(3):
            p, master, opt_state, _ = step(p, master, opt_state, x)
        return p, master, opt

    p0, m0, _ = run("off")
    p1, m1, opt = run("bucket", chunk_bytes=512)  # force several chunks
    assert opt.overlap_chunks() > 1
    assert np.array_equal(np.asarray(m0), np.asarray(m1))
    for a, b in zip(
        jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(p1)
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_trainer_zero1_bucket_overlap_parity(mesh8, monkeypatch):
    """DDPTrainer(zero1=True) composes with the bucket schedule: the hook's
    rolling sync is bitwise, the zero1 tail's chunked all-gather is
    layout-identical; across XLA program boundaries the fused arithmetic
    may reassociate, so whole-state parity is asserted at fp32-tight
    tolerance."""
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    _, s_off = _run_trainer(mesh8, "off", zero1=True)
    _, s_b = _run_trainer(mesh8, "bucket", zero1=True)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_off.params),
        jax.tree_util.tree_leaves(s_b.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


# --------------------------------------------------------------------------- #
# cost model: overlapped_step_time / exposed_comm_floor_s
# --------------------------------------------------------------------------- #


def _coeffs(world=8):
    from adapcc_tpu.sim.calibrate import load_or_default
    from adapcc_tpu.sim.cost_model import bottleneck_ring_coeffs

    return bottleneck_ring_coeffs(load_or_default(world=world), world)


def test_overlapped_step_time_off_exposes_everything():
    from adapcc_tpu.sim.cost_model import overlapped_step_time

    r = overlapped_step_time(8, 64 << 20, _coeffs(), 1e-3, overlap="off")
    assert r["exposed_comm_s"] == pytest.approx(r["comm_s"])
    assert r["step_time_s"] == pytest.approx(1e-3 + r["comm_s"])


def test_bucket_overlap_strictly_reduces_exposed_comm():
    """The acceptance property, straight from the model: for a comm-bound
    step the bucket schedule's exposed comm is strictly below the
    baseline's."""
    from adapcc_tpu.sim.cost_model import overlapped_step_time

    coeffs = _coeffs()
    G = 128 << 20
    buckets = [G / 16] * 16
    off = overlapped_step_time(
        8, G, coeffs, 0.0, overlap="off", bucket_bytes=buckets
    )
    compute_s = 0.25 * off["comm_s"]  # comm-bound
    rolled = overlapped_step_time(
        8, G, coeffs, compute_s, overlap="bucket", bucket_bytes=buckets
    )
    assert rolled["exposed_comm_s"] < off["exposed_comm_s"]
    # compute-bound: exposure collapses to the last bucket's drain
    heavy = overlapped_step_time(
        8, G, coeffs, 100.0 * off["comm_s"], overlap="bucket",
        bucket_bytes=buckets,
    )
    assert heavy["exposed_comm_s"] == pytest.approx(heavy["drain_s"])


def test_microbatch_pricing_is_honest_about_wire_volume():
    from adapcc_tpu.sim.cost_model import overlapped_step_time

    coeffs = _coeffs()
    G = 64 << 20
    off = overlapped_step_time(8, G, coeffs, 1e-3, accum=4, overlap="off")
    mb = overlapped_step_time(8, G, coeffs, 1e-3, accum=4, overlap="microbatch")
    assert mb["comm_s"] == pytest.approx(4 * off["comm_s"])  # accum x bytes
    # with compute dwarfing comm, only the drain stays exposed
    big = overlapped_step_time(8, G, coeffs, 10.0, accum=4, overlap="microbatch")
    assert big["exposed_comm_s"] == pytest.approx(big["drain_s"])


def test_exposed_comm_floor_ordering():
    from adapcc_tpu.sim.cost_model import exposed_comm_floor_s

    coeffs = _coeffs()
    G = 64 << 20
    buckets = [G / 8] * 8
    off = exposed_comm_floor_s(8, G, coeffs, "off", buckets)
    bucket = exposed_comm_floor_s(8, G, coeffs, "bucket", buckets)
    micro = exposed_comm_floor_s(8, G, coeffs, "microbatch", buckets)
    assert bucket < off
    assert micro == pytest.approx(off)  # deltas are gradient-sized


def test_overlapped_step_time_validation():
    from adapcc_tpu.sim.cost_model import overlapped_step_time

    coeffs = _coeffs()
    with pytest.raises(ValueError, match="overlap"):
        overlapped_step_time(8, 1024, coeffs, 0.0, overlap="rolling")
    with pytest.raises(ValueError, match="accum"):
        overlapped_step_time(8, 1024, coeffs, 0.0, accum=0)
    with pytest.raises(ValueError, match="compute_s"):
        overlapped_step_time(8, 1024, coeffs, -1.0)


# --------------------------------------------------------------------------- #
# the overlap sweep (make overlap-bench)
# --------------------------------------------------------------------------- #


def test_overlap_sweep_deterministic():
    from benchmarks.sim_collectives import overlap_sweep

    a = overlap_sweep(8, [16 << 20, 128 << 20])
    b = overlap_sweep(8, [16 << 20, 128 << 20])
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert all(r["mode"] == "simulated" for r in a)


def test_overlap_sweep_comm_bound_bucket_strictly_decreasing():
    """Acceptance: the artifact shows exposed comm strictly below the
    non-overlapped baseline for every comm-bound bucket-schedule row."""
    from benchmarks.sim_collectives import overlap_sweep

    rows = overlap_sweep(8, [16 << 20, 128 << 20])
    key = lambda r: (
        r["size_bytes"], r["accum"], r["bucket_cap_mb"], r["compute_ratio"]
    )
    baselines = {key(r): r for r in rows if r["overlap"] == "off"}
    comm_bound_bucket = [
        r for r in rows if r["overlap"] == "bucket" and r["comm_bound"]
    ]
    assert comm_bound_bucket, "sweep grid lost its comm-bound configurations"
    for r in comm_bound_bucket:
        assert r["exposed_comm_us"] < baselines[key(r)]["exposed_comm_us"]
        assert r["n_buckets"] > 1


def test_overlap_sweep_cli_mutually_exclusive(capsys):
    from benchmarks.sim_collectives import main

    with pytest.raises(SystemExit):
        main(["--overlap-sweep", "--ring-sweep"])
    with pytest.raises(SystemExit):
        main(["--overlap-sweep", "--tune-replay"])
    with pytest.raises(SystemExit):
        main(["--overlap-sweep", "--wire-dtype", "off,int8"])
    capsys.readouterr()


def test_overlap_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--overlap-sweep", "--world", "8", "--sizes", "16M",
        "--accums", "1,2", "--bucket-caps-mb", "4", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "overlap" for r in rows)
    assert {r["overlap"] for r in rows} == {"off", "bucket", "microbatch"}
    # accum=1 emits no microbatch row (nothing to pipeline over)
    assert not [
        r for r in rows if r["accum"] == 1 and r["overlap"] == "microbatch"
    ]


# --------------------------------------------------------------------------- #
# tuner: the measured overlap axis of the ddp_step cell
# --------------------------------------------------------------------------- #


def _policy(**kw):
    from adapcc_tpu.tuner import TuningDatabase, TuningPolicy

    db = TuningDatabase(persist=False)
    kw.setdefault("epsilon", 0.0)
    kw.setdefault("min_samples", 2)
    return TuningPolicy(db, world=8, topology="overlap-test", **kw), db


def test_hook_path_roundtrip():
    from adapcc_tpu.tuner.policy import hook_overlap_of, hook_path

    assert hook_path("off") == "hook"  # pre-overlap schema preserved
    for mode in OVERLAP_MODES:
        assert hook_overlap_of(hook_path(mode)) == mode
    with pytest.raises(ValueError):
        hook_path("rolling")
    with pytest.raises(ValueError):
        hook_overlap_of("vmem")
    with pytest.raises(ValueError):
        hook_overlap_of("hook-rolling")


def test_ddp_step_candidates_carry_overlap_axis():
    from adapcc_tpu.tuner.policy import hook_overlap_of

    policy, _ = _policy()
    cells = policy.candidates("ddp_step", 16 << 20)
    assert {hook_overlap_of(c.path) for c in cells} == set(OVERLAP_MODES)
    # narrowing: a trainer that cannot compile the microbatch pipeline
    narrowed = policy.candidates(
        "ddp_step", 16 << 20, overlap_modes=("off", "bucket")
    )
    assert {hook_overlap_of(c.path) for c in narrowed} == {"off", "bucket"}


def test_policy_prior_never_flips_overlap():
    """ISSUE acceptance: choose adopts overlap only when measured step
    time improves — with an empty database the prior ties and candidate
    order keeps the baseline schedule."""
    from adapcc_tpu.tuner.policy import hook_overlap_of

    policy, _ = _policy()
    plan = policy.choose("ddp_step", 16 << 20)
    assert hook_overlap_of(plan.key.path) == "off"
    assert plan.source == "prior"


def test_policy_adopts_overlap_from_measured_medians():
    from adapcc_tpu.tuner.policy import hook_overlap_of

    policy, db = _policy()
    nbytes = 16 << 20
    for overlap, t in (("off", 10e-3), ("bucket", 8e-3), ("microbatch", 12e-3)):
        (cell,) = policy.candidates(
            "ddp_step", nbytes, wire_dtypes=("off",), overlap_modes=(overlap,)
        )
        for _ in range(6):
            db.record(cell, t)
    plan = policy.choose("ddp_step", nbytes)
    assert hook_overlap_of(plan.key.path) == "bucket"
    assert plan.source == "measured"


def test_policy_hysteresis_rejects_marginal_overlap_win():
    """A challenger schedule inside the hysteresis margin must NOT unseat
    the incumbent — overlap adoption needs a real measured improvement."""
    from adapcc_tpu.tuner.policy import hook_overlap_of

    policy, db = _policy(hysteresis_margin=0.05)
    nbytes = 16 << 20
    (off_cell,) = policy.candidates(
        "ddp_step", nbytes, wire_dtypes=("off",), overlap_modes=("off",)
    )
    for _ in range(6):
        db.record(off_cell, 10e-3)
    assert policy.choose("ddp_step", nbytes).key == off_cell  # incumbent
    (bucket_cell,) = policy.candidates(
        "ddp_step", nbytes, wire_dtypes=("off",), overlap_modes=("bucket",)
    )
    for _ in range(6):
        db.record(bucket_cell, 9.8e-3)  # 2% better: inside the margin
    assert policy.choose("ddp_step", nbytes).key == off_cell
    for _ in range(6):
        db.record(bucket_cell, 5e-3)  # decisively better: promotes
    assert hook_overlap_of(policy.choose("ddp_step", nbytes).key.path) == "bucket"


def test_trainer_step_cell_stays_in_candidate_grid_per_overlap(
    mesh8, monkeypatch
):
    """The recorded-key-in-candidate-set invariant, extended to the overlap
    axis: whatever schedule the trainer executes, its step cell must be
    rankable by the narrowed grid or the posterior never forms."""
    from adapcc_tpu.tuner import CollectiveTuner, TUNER_MODE_ENV, TuningDatabase

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    loss_fn, params, x = _linear_workload()
    for overlap, accum in (("off", 1), ("bucket", 1), ("microbatch", 2)):
        db = TuningDatabase(persist=False)
        tuner = CollectiveTuner(
            world=8, topology="t", db=db, mode="choose"
        )
        trainer = DDPTrainer(
            loss_fn, optax.sgd(0.1), mesh8, Strategy.ring(8),
            use_xla_fastpath=False, tune=True, tuner=tuner,
            overlap=overlap, accum_steps=accum,
        )
        cell = trainer._step_cell(4096)
        assert cell in tuner.policy.candidates(
            "ddp_step", 4096, overlap_modes=trainer._overlap_modes
        )
        if accum == 1:
            assert "microbatch" not in trainer._overlap_modes


def test_trainer_adopts_overlap_from_measured_medians(
    mesh8, tmp_path, monkeypatch
):
    """End to end: seeded step medians favor the bucket schedule; the
    trainer adopts it (hook + trainer re-steered, step recompiled) at its
    next tune_every boundary."""
    from adapcc_tpu.tuner import CollectiveTuner, TUNER_MODE_ENV, TuningDatabase
    from adapcc_tpu.tuner.policy import NO_CHUNK, hook_path

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    monkeypatch.delenv("ADAPCC_WIRE_DTYPE", raising=False)
    loss_fn, params, x = _linear_workload()
    tx = optax.sgd(0.1)
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="train", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False,
        tune=True, tuner=tuner, tune_every=2,
    )
    state = TrainState.create(params, tx)
    grad_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )
    for overlap, t in (("off", 1.0), ("bucket", 1e-6)):
        for _ in range(5):
            db.record(
                tuner.key_for(
                    "ddp_step", grad_bytes, hook_path(overlap), NO_CHUNK, "off"
                ),
                t,
            )
    assert trainer.overlap == "off"
    for s in range(4):
        state, _ = trainer.step(state, x, step_idx=s)
    assert trainer.overlap == "bucket"        # adopted from measurement
    assert trainer.hook.overlap == "bucket"   # both halves re-steered


def test_trainer_adoption_resteers_zero1_optimizer(
    mesh8, tmp_path, monkeypatch
):
    """Adopting an overlap schedule must re-steer the already-constructed
    Zero1Optimizer too: a stale optimizer would leave the adopted cell's
    step measurements half-applied (chunked hook + unchunked zero1 RS/AG
    or vice versa), corrupting the A/B the adoption ranks on."""
    from adapcc_tpu.tuner import CollectiveTuner, TUNER_MODE_ENV, TuningDatabase
    from adapcc_tpu.tuner.policy import NO_CHUNK, hook_path

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    monkeypatch.delenv(OVERLAP_ENV, raising=False)
    monkeypatch.delenv("ADAPCC_WIRE_DTYPE", raising=False)
    loss_fn, params, x = _linear_workload()
    tx = optax.sgd(0.1)
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="train", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False,
        tune=True, tuner=tuner, tune_every=2, zero1=True,
    )
    state = trainer.init_state(params)
    assert trainer._zero1_opt.overlap == "off"
    grad_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )
    for overlap, t in (("off", 1.0), ("bucket", 1e-6)):
        for _ in range(5):
            db.record(
                tuner.key_for(
                    "ddp_step", grad_bytes, hook_path(overlap), NO_CHUNK, "off"
                ),
                t,
            )
    for s in range(4):
        state, _ = trainer.step(state, x, step_idx=s)
    assert trainer.overlap == "bucket"
    assert trainer._zero1_opt.overlap == "bucket"  # re-steered with it


def test_trainer_env_pinned_overlap_never_steers(
    mesh8, tmp_path, monkeypatch
):
    """ADAPCC_OVERLAP pins the schedule exactly like ADAPCC_WIRE_DTYPE pins
    the codec: the tuner keeps measuring the pinned cell and never adopts
    a different schedule."""
    from adapcc_tpu.tuner import CollectiveTuner, TUNER_MODE_ENV, TuningDatabase
    from adapcc_tpu.tuner.policy import NO_CHUNK, hook_path

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    monkeypatch.delenv("ADAPCC_WIRE_DTYPE", raising=False)
    monkeypatch.setenv(OVERLAP_ENV, "off")
    loss_fn, params, x = _linear_workload()
    tx = optax.sgd(0.1)
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="train", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), use_xla_fastpath=False,
        tune=True, tuner=tuner, tune_every=2,
    )
    state = TrainState.create(params, tx)
    grad_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )
    for _ in range(5):
        db.record(
            tuner.key_for(
                "ddp_step", grad_bytes, hook_path("bucket"), NO_CHUNK, "off"
            ),
            1e-9,  # would win if the axis were free
        )
    for s in range(4):
        state, _ = trainer.step(state, x, step_idx=s)
    assert trainer.overlap == "off"  # pinned: never steered
