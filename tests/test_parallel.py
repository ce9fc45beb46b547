"""Parallelism strategies: ring attention (SP), TP, PP, EP.

Each strategy is validated against a single-device oracle on the virtual
8-device CPU pod — the analog of the reference's fake-multi-node localhost
checks (SURVEY §4.3), applied to the parallel axes the reference lacks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from adapcc_tpu.models.moe import MoEConfig, MoEMLP
from adapcc_tpu.parallel import (
    column_parallel_dense,
    expert_parallel_moe,
    gpt2_tp_rules,
    pipeline_apply,
    ring_attention,
    row_parallel_dense,
    tree_shardings,
)
from adapcc_tpu.parallel.ring_attention import reference_attention
from adapcc_tpu.parallel.tensor import shard_tree


# ---------------------------------------------------------------- ring (SP)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full_attention(mesh8, causal):
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 32, 2, 8
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32) for _ in range(3)
    )
    got = ring_attention(mesh8, q, k, v, causal=causal)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_bf16_and_grads(mesh8):
    """bfloat16 forward stays close to the fp32 oracle and is differentiable."""
    rng = np.random.default_rng(1)
    B, T, H, D = 1, 16, 2, 4
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.bfloat16) for _ in range(3)
    )

    def loss(q, k, v):
        return jnp.sum(ring_attention(mesh8, q, k, v).astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for gi in g:
        assert np.isfinite(np.asarray(gi, dtype=np.float32)).all()
    want = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    got = ring_attention(mesh8, q, k, v).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------- TP


def test_column_row_parallel_pair(mesh8):
    """Column→row sharded matmul chain equals the dense chain."""

    from jax import shard_map

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    b2 = jnp.asarray(rng.normal(size=(8,)), jnp.float32)

    def shard_fn(x, w1, b1, w2, b2):
        h = column_parallel_dense(x, w1, b1)
        h = jax.nn.gelu(h)
        return row_parallel_dense(h, w2, "ranks", b2)

    fn = shard_map(
        shard_fn,
        mesh=mesh8,
        in_specs=(P(), P(None, "ranks"), P("ranks"), P("ranks", None), P()),
        out_specs=P(),
        check_vma=False,
    )
    got = fn(x, w1, b1, w2, b2)
    want = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_gpt2_tp_shardings_preserve_loss(mesh8):
    """GSPMD TP: sharded params give the same loss as replicated params."""
    model_mesh = Mesh(np.array(jax.devices()[:8]), ("model",))
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, size=(2, cfg.max_seq)),
        jnp.int32,
    )
    params = model.init(jax.random.PRNGKey(0), tokens)
    want = lm_loss(model.apply(params, tokens), tokens)

    rules = gpt2_tp_rules("model")
    sharded = shard_tree(params, model_mesh, rules)
    # at least the big kernels must actually be sharded
    flat = jax.tree_util.tree_flatten_with_path(
        tree_shardings(params, model_mesh, rules)
    )[0]
    sharded_paths = [
        "/".join(str(getattr(k, "key", k)) for k in path)
        for path, s in flat
        if s.spec != P()
    ]
    assert any("qkv" in p for p in sharded_paths)
    assert any("fc" in p for p in sharded_paths)

    got = jax.jit(lambda p, t: lm_loss(model.apply(p, t), t))(sharded, tokens)
    np.testing.assert_allclose(float(got), float(want), atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------- PP


def test_pipeline_matches_sequential(mesh8):
    stages = 4
    mesh = Mesh(np.array(jax.devices()[:stages]), ("stages",))
    rng = np.random.default_rng(4)
    D = 16
    w = jnp.asarray(rng.normal(size=(stages, D, D)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(stages, D)) * 0.1, jnp.float32)

    def stage_fn(params, x):
        wi, bi = params
        return jnp.tanh(x @ wi + bi)

    batch = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)
    got = pipeline_apply(stage_fn, (w, b), batch, mesh, num_microbatches=4)

    want = batch
    for s in range(stages):
        want = stage_fn((w[s], b[s]), want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_pipeline_backward_matches_sequential(mesh8):
    """PP training: gradients THROUGH the pipeline (ppermute+scan+psum) must
    equal the sequential stack's — the point of pipeline parallelism is
    training, not just inference."""
    stages, D = 4, 8
    mesh = Mesh(np.array(jax.devices()[:stages]), ("stages",))
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(stages, D, D)) * 0.3, jnp.float32)
    batch = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)
    target = jnp.asarray(rng.normal(size=(8, D)), jnp.float32)

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    def loss_pp(w, b):
        out = pipeline_apply(stage_fn, w, b, mesh, num_microbatches=2)
        return jnp.mean((out - target) ** 2)

    def loss_seq(w, b):
        x = b
        for s in range(stages):
            x = stage_fn(w[s], x)
        return jnp.mean((x - target) ** 2)

    l_pp, g_pp = jax.value_and_grad(loss_pp)(w, batch)
    l_sq, g_sq = jax.value_and_grad(loss_seq)(w, batch)
    np.testing.assert_allclose(float(l_pp), float(l_sq), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_sq), atol=1e-5, rtol=1e-4)
    # and input gradients flow back through the fill/drain schedule too
    gb_pp = jax.grad(loss_pp, argnums=1)(w, batch)
    gb_sq = jax.grad(loss_seq, argnums=1)(w, batch)
    np.testing.assert_allclose(np.asarray(gb_pp), np.asarray(gb_sq), atol=1e-5, rtol=1e-4)


def test_pipeline_training_step_decreases_loss(mesh8):
    """One jitted SGD step through the pipeline reduces the loss."""
    import optax

    stages, D = 2, 8
    mesh = Mesh(np.array(jax.devices()[:stages]), ("stages",))
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.normal(size=(stages, D, D)) * 0.3, jnp.float32)
    batch = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    target = jnp.asarray(rng.normal(size=(4, D)) * 0.1, jnp.float32)
    tx = optax.sgd(0.1)

    def loss(w):
        out = pipeline_apply(
            lambda wi, x: jnp.tanh(x @ wi), w, batch, mesh, num_microbatches=2
        )
        return jnp.mean((out - target) ** 2)

    @jax.jit
    def step(w, opt):
        l, g = jax.value_and_grad(loss)(w)
        u, opt = tx.update(g, opt, w)
        return optax.apply_updates(w, u), opt, l

    opt = tx.init(w)
    losses = []
    for _ in range(10):
        w, opt, l = step(w, opt)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.9, losses


def test_pipeline_single_microbatch(mesh8):
    """Degenerate M=1 still fills/drains correctly."""
    stages = 2
    mesh = Mesh(np.array(jax.devices()[:stages]), ("stages",))
    w = jnp.stack([jnp.eye(4) * (s + 1) for s in range(stages)])

    def stage_fn(wi, x):
        return x @ wi

    batch = jnp.ones((3, 4), jnp.float32)
    got = pipeline_apply(stage_fn, w, batch, mesh, num_microbatches=1)
    np.testing.assert_allclose(np.asarray(got), np.ones((3, 4)) * 2.0, atol=1e-6)


# ---------------------------------------------------------------------- EP


@pytest.mark.slow
def test_expert_parallel_matches_dense_moe(mesh8):
    """With ample capacity (no drops) EP output == single-device MoEMLP."""
    cfg = MoEConfig(
        num_experts=8,
        d_model=16,
        d_hidden=32,
        top_k=2,
        capacity_factor=8.0,
        dtype=jnp.float32,
    )
    mesh = Mesh(np.array(jax.devices()[:8]), ("experts",))
    model = MoEMLP(cfg)
    rng = np.random.default_rng(5)
    B, T = 4, 8
    x = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)

    want_y, want_aux = model.apply(params, x)

    tokens = x.reshape(B * T, cfg.d_model)
    got_y, got_aux = expert_parallel_moe(params, tokens, cfg, mesh)
    np.testing.assert_allclose(
        np.asarray(got_y), np.asarray(want_y.reshape(B * T, cfg.d_model)),
        atol=1e-4, rtol=1e-4,
    )
    assert np.isfinite(float(got_aux))


@pytest.mark.slow
def test_expert_parallel_capacity_drops_are_bounded(mesh8):
    """Tight capacity drops tokens but never produces NaN/garbage."""
    cfg = MoEConfig(
        num_experts=4, d_model=8, d_hidden=16, top_k=2,
        capacity_factor=0.5, dtype=jnp.float32,
    )
    mesh = Mesh(np.array(jax.devices()[:4]), ("experts",))
    model = MoEMLP(cfg)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    y, aux = expert_parallel_moe(params, x.reshape(16, cfg.d_model), cfg, mesh)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(float(aux))


def test_train_moe_workload_ep_training_and_inference(capsys):
    """workloads/train_moe.py: gradients flow through the EP all-to-alls
    (CE collapses on separable clusters) and the reference's timed inference
    loop prints its computation-time line."""
    from adapcc_tpu.workloads.train_moe import build_parser, run

    args = build_parser().parse_args(
        ["--world", "4", "--steps", "25", "--experts", "4", "--dmodel", "32",
         "--dhidden", "64", "--batch", "128", "--classes", "4"]
    )
    first, last = run(args)
    assert last < first * 0.2, (first, last)

    args = build_parser().parse_args(
        ["--world", "4", "--mode", "inference", "--steps", "3",
         "--experts", "4", "--dmodel", "32", "--dhidden", "64", "--batch", "128"]
    )
    run(args)
    assert "computation time:" in capsys.readouterr().out


def test_train_moe_rejects_indivisible_batch():
    from adapcc_tpu.workloads.train_moe import build_parser, run

    args = build_parser().parse_args(["--world", "4", "--batch", "130"])
    with pytest.raises(ValueError, match="divide by world"):
        run(args)


def test_moe_a2a_parity_flat_engine_and_two_level():
    """Satellite of the latency PR: the MoE token exchange is BIT-IDENTICAL
    across all three data planes — the flat `lax.all_to_all` (engine=None),
    the engine-routed path (`engine.expert_a2a`, which adds tracing), and
    the two-level hierarchical DCN x ICI exchange — so routing expert
    traffic through the engine (to be timed/traced/tuned) can never change
    a model's numerics."""
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.two_level import build_two_level_mesh
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils import CollectiveTrace

    cfg = MoEConfig(
        num_experts=8, d_model=16, d_hidden=32, top_k=2,
        capacity_factor=2.0, dtype=jnp.float32,
    )
    model = MoEMLP(cfg)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, cfg.d_model)), jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x[None])

    def exchange(mesh, engine=None):
        # one compiled program for each data plane: run eagerly, the shard_map
        # dispatches (and compiles) its body an operation at a time on 8 devices
        return jax.jit(lambda p, x: expert_parallel_moe(p, x, cfg, mesh, engine=engine))(params, x)

    flat = Mesh(np.array(jax.devices()[:8]), ("experts",))
    y_flat, aux_flat = exchange(flat)

    trace = CollectiveTrace()
    engine = CollectiveEngine(
        flat, Strategy.ring(8), axis_name="experts", trace=trace
    )
    y_eng, aux_eng = exchange(flat, engine)
    np.testing.assert_array_equal(np.asarray(y_eng), np.asarray(y_flat))
    np.testing.assert_array_equal(np.asarray(aux_eng), np.asarray(aux_flat))
    # the engine-routed exchanges were traced: 2 a2as per forward
    moe_events = [
        e for e in trace.events()
        if e.primitive == "all_to_all" and e.impl == "xla[moe]"
    ]
    assert len(moe_events) == 2 and all(e.extra.get("moe") for e in moe_events)

    mesh2x4 = build_two_level_mesh(2, 4)
    y_2l, aux_2l = exchange(mesh2x4)
    np.testing.assert_array_equal(np.asarray(y_2l), np.asarray(y_flat))
    trace2 = CollectiveTrace()
    engine2 = CollectiveEngine(mesh2x4, Strategy.ring(8), trace=trace2)
    y_2le, _ = exchange(mesh2x4, engine2)
    np.testing.assert_array_equal(np.asarray(y_2le), np.asarray(y_flat))
    assert [
        e.impl for e in trace2.events() if e.primitive == "all_to_all"
    ] == ["two_level[moe]"] * 2


def test_moe_engine_world_mismatch_rejected():
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy

    cfg = MoEConfig(
        num_experts=8, d_model=8, d_hidden=16, top_k=1,
        capacity_factor=2.0, dtype=jnp.float32,
    )
    model = MoEMLP(cfg)
    x = jnp.ones((32, cfg.d_model), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x[None])
    mesh8 = Mesh(np.array(jax.devices()[:8]), ("experts",))
    mesh4 = Mesh(np.array(jax.devices()[:4]), ("experts",))
    engine4 = CollectiveEngine(mesh4, Strategy.ring(4), axis_name="experts")
    with pytest.raises(ValueError, match="engine world"):
        expert_parallel_moe(params, x, cfg, mesh8, engine=engine4)


def test_train_moe_feeds_tuner_db_under_all_to_all(tmp_path, monkeypatch):
    """Acceptance pin: a train_moe run with the tuner recording leaves
    all_to_all samples in the tuning database at the MoE exchange
    geometry."""
    from adapcc_tpu.tuner import TuningDatabase
    from adapcc_tpu.workloads.train_moe import build_parser, run

    db_path = str(tmp_path / "tuning.jsonl")
    monkeypatch.setenv("ADAPCC_TUNER", "record")
    monkeypatch.setenv("ADAPCC_TUNER_DB", db_path)
    args = build_parser().parse_args([
        "--world", "4", "--steps", "9", "--experts", "4", "--dmodel", "16",
        "--dhidden", "32", "--batch", "64", "--tune-every", "3",
    ])
    first, last = run(args)
    assert np.isfinite(first) and np.isfinite(last)
    db = TuningDatabase(db_path)
    a2a = [k for k in db.keys() if k.primitive == "all_to_all"]
    assert a2a, "MoE a2a dispatches must land in the tuner db"
    # probe geometry = the dispatch exchange: world*e_loc*capacity*d_model
    from adapcc_tpu.parallel.expert import moe_capacity

    probe_cfg = MoEConfig(
        num_experts=4, d_model=16, d_hidden=32, top_k=2,
        capacity_factor=2.0, dtype=jnp.float32,
    )
    n_loc, e_loc = 64 // 4, 4 // 4
    per_rank = 4 * e_loc * moe_capacity(probe_cfg, n_loc) * 16 * 4
    from adapcc_tpu.tuner.db import size_bucket

    assert a2a[0].size_bucket == size_bucket(per_rank)
    assert db.count(a2a[0]) >= 1  # 3 probes - 1 warmup discard
