"""FSDP (ZeRO-3 via GSPMD) and ZeRO-1 sharded-optimizer tests.

Oracle: replicated single-program training on the same data — sharded state
is a memory layout, not a different algorithm, so losses and params must
match to float tolerance on the virtual 8-device pod.
"""

import jax

import pytest
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.parallel.fsdp import (
    Zero1Optimizer,
    fsdp_shardings,
    fsdp_train_step,
    shard_fsdp,
    zero1_train_step,
)


def _mlp_params(rng, din=16, dh=64, dout=16):
    return {
        "w1": jnp.asarray(rng.normal(size=(din, dh)) * 0.1, jnp.float32),
        "b1": jnp.zeros((dh,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(dh, dout)) * 0.1, jnp.float32),
        "b2": jnp.zeros((dout,), jnp.float32),
    }


def _mlp_loss(p, batch):
    x, y = batch
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    out = h @ p["w2"] + p["b2"]
    return jnp.mean((out - y) ** 2)


def _batch(rng, n=16, din=16, dout=16):
    x = jnp.asarray(rng.normal(size=(n, din)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, dout)), jnp.float32)
    return x, y


# ------------------------------------------------------------------ FSDP/ZeRO-3


def test_fsdp_shardings_pick_largest_divisible_dim(mesh8):
    params = {
        "big": jnp.zeros((24, 512)),     # 512 % 8 == 0 and larger → shard dim 1
        "tall": jnp.zeros((4096, 6)),    # only dim 0 divisible → shard dim 0
        "bias": jnp.zeros((512,)),       # below min_shard_elems → replicated
        "odd": jnp.zeros((630, 63)),     # nothing divisible by 8 → replicated
    }
    sh = fsdp_shardings(params, mesh8, min_shard_elems=2**10)
    assert sh["big"].spec == P(None, RANKS_AXIS)
    assert sh["tall"].spec == P(RANKS_AXIS, None)
    assert sh["bias"].spec == P()
    assert sh["odd"].spec == P()


def test_shard_fsdp_splits_memory(mesh8):
    params = {"w": jnp.ones((8 * 13, 32), jnp.float32)}
    sharded = shard_fsdp(params, mesh8, min_shard_elems=1)
    shard = sharded["w"].addressable_shards[0]
    assert shard.data.shape == (13, 32)  # 1/8 of rows on each device
    np.testing.assert_array_equal(np.asarray(sharded["w"]), np.ones((104, 32)))


def test_fsdp_train_matches_replicated(mesh8):
    rng = np.random.default_rng(0)
    params = _mlp_params(rng)
    tx = optax.adam(1e-2)

    # oracle: plain replicated training
    o_params, o_opt = jax.tree_util.tree_map(jnp.array, params), tx.init(params)

    @jax.jit
    def plain_step(p, o, b):
        loss, g = jax.value_and_grad(_mlp_loss)(p, b)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    # fsdp: sharded params + opt state, same data
    f_params = shard_fsdp(params, mesh8, min_shard_elems=64)
    f_opt = tx.init(f_params)
    step = fsdp_train_step(_mlp_loss, tx, mesh8, donate=False, min_shard_elems=64)

    losses_plain, losses_fsdp = [], []
    for i in range(4):
        b = _batch(np.random.default_rng(100 + i))
        o_params, o_opt, lp = plain_step(o_params, o_opt, b)
        f_params, f_opt, lf = step(f_params, f_opt, b)
        losses_plain.append(float(lp))
        losses_fsdp.append(float(lf))
    np.testing.assert_allclose(losses_fsdp, losses_plain, rtol=1e-5, atol=1e-6)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(f_params[k]), np.asarray(o_params[k]), rtol=1e-5, atol=1e-6
        )
    # the point of FSDP: each device holds 1/8 of the shardable leaves
    assert f_params["w1"].addressable_shards[0].data.shape == (16, 8)
    # adam moments inherit the same sharded layout
    mu = f_opt[0].mu["w1"]
    assert mu.addressable_shards[0].data.shape == (16, 8)


# ------------------------------------------------------------------ ZeRO-1


def test_zero1_matches_plain_adam(mesh8):
    rng = np.random.default_rng(1)
    params = _mlp_params(rng)
    tx = optax.adam(1e-2)
    opt = Zero1Optimizer(tx, mesh8)
    master, opt_state = opt.init(params)
    step = zero1_train_step(_mlp_loss, opt, mesh8)

    o_params, o_opt = jax.tree_util.tree_map(jnp.array, params), tx.init(params)

    @jax.jit
    def plain_step(p, o, b):
        # oracle computes the mean of per-shard gradients = gradient of the
        # mean loss over the global batch only when shards are equal-sized
        # and the loss is a mean — true for the MSE here
        loss, g = jax.value_and_grad(_mlp_loss)(p, b)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    p = params
    for i in range(3):
        b = _batch(np.random.default_rng(200 + i), n=16)
        p, master, opt_state, losses = step(p, master, opt_state, b)
        o_params, o_opt, _ = plain_step(o_params, o_opt, b)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p[k]), np.asarray(o_params[k]), rtol=2e-5, atol=2e-6
        )


def test_zero1_opt_state_is_sharded(mesh8):
    params = _mlp_params(np.random.default_rng(2))
    opt = Zero1Optimizer(optax.adam(1e-3), mesh8)
    master, opt_state = opt.init(params)
    n_total = sum(int(np.prod(v.shape)) for v in params.values())
    shard_len = -(-n_total // 8)  # ceil
    assert master.shape == (8, shard_len)
    assert master.addressable_shards[0].data.shape == (1, shard_len)
    mu = opt_state[0].mu
    assert mu.shape == (8, shard_len)
    assert mu.addressable_shards[0].data.shape == (1, shard_len)


def test_zero1_apply_with_presynced_grads(mesh8):
    """apply() with replicated (already-synced) grads reproduces one plain
    adam step: psum_scatter(g/world) over identical replicas folds back to g."""
    rng = np.random.default_rng(3)
    params = _mlp_params(rng)
    tx = optax.adam(1e-2)
    opt = Zero1Optimizer(tx, mesh8)
    master, opt_state = opt.init(params)
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params
    )
    _, _, new_params = opt.apply(master, opt_state, grads)

    u, _ = tx.update(grads, tx.init(params), params)
    want = optax.apply_updates(params, u)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(want[k]), rtol=2e-5, atol=2e-6
        )


def test_zero1_handles_nondivisible_param_count(mesh8):
    """Padding path: total param count not divisible by world."""
    params = {"w": jnp.ones((3, 5), jnp.float32), "b": jnp.zeros((7,), jnp.float32)}
    tx = optax.sgd(0.5)
    opt = Zero1Optimizer(tx, mesh8)
    master, opt_state = opt.init(params)
    grads = {"w": jnp.full((3, 5), 2.0), "b": jnp.full((7,), 4.0)}
    _, _, new_params = opt.apply(master, opt_state, grads)
    np.testing.assert_allclose(np.asarray(new_params["w"]), np.ones((3, 5)) - 1.0)
    np.testing.assert_allclose(np.asarray(new_params["b"]), np.zeros((7,)) - 2.0)


# ------------------------------------------------------------------ GPT-2 e2e


@pytest.mark.slow
def test_fsdp_gpt2_trains(mesh8):
    """Flagship-model integration: tiny GPT-2 under full FSDP — params and
    adam moments sharded over the pod, loss decreases over a few steps."""
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

    cfg = GPT2Config(vocab_size=128, max_seq=16, n_layer=1, n_head=2, d_model=32)
    model = GPT2(cfg)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.max_seq)), jnp.int32)
    params = shard_fsdp(
        model.init(jax.random.PRNGKey(0), tokens[:1]), mesh8, min_shard_elems=64
    )
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step = fsdp_train_step(
        lambda p, b: lm_loss(model.apply(p, b), b), tx, mesh8,
        donate=False, min_shard_elems=64,
    )
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    # at least one transformer kernel actually sharded across the pod
    leaves = [
        x for x in jax.tree_util.tree_leaves(params)
        if hasattr(x, "sharding") and x.sharding.spec != P()
    ]
    assert leaves, "no GPT-2 leaf was sharded"


def test_zero1_reinit_recompiles(mesh8):
    """init() with a different param tree must invalidate the compiled
    program (stale meta would reshape into the old layout)."""
    tx = optax.sgd(1.0)
    opt = Zero1Optimizer(tx, mesh8)
    a = {"w": jnp.ones((4, 4), jnp.float32)}
    master, st = opt.init(a)
    opt.apply(master, st, {"w": jnp.ones((4, 4))})
    b = {"w": jnp.ones((16, 16), jnp.float32), "b": jnp.zeros((5,), jnp.float32)}
    master_b, st_b = opt.init(b)
    _, _, new_b = opt.apply(master_b, st_b, jax.tree_util.tree_map(jnp.ones_like, b))
    assert new_b["w"].shape == (16, 16) and new_b["b"].shape == (5,)


# ------------------------------------------------------------------ FSDP × TP


def test_fsdp_tp_2d_shardings_and_training(mesh8):
    """2D composition on a (data=4, model=2) mesh: TP claims its Megatron
    dims, FSDP shards a free dim over data; training matches the replicated
    oracle and the qkv kernel is genuinely 2D-sharded."""
    from jax.sharding import Mesh

    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from adapcc_tpu.parallel import gpt2_tp_rules
    from adapcc_tpu.parallel.fsdp import fsdp_tp_shardings, fsdp_tp_train_step

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
    # fp32 so the 2D-sharded reduction order matches the oracle to tolerance
    cfg = GPT2Config(
        vocab_size=128, max_seq=16, n_layer=1, n_head=2, d_model=32,
        dtype=jnp.float32,
    )
    model = GPT2(cfg)
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, cfg.max_seq)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    rules = gpt2_tp_rules("model")

    def loss_fn(p, b):
        return lm_loss(model.apply(p, b), b)

    tx = optax.adam(1e-2)
    sh = fsdp_tp_shardings(params, mesh, rules, min_shard_elems=64)
    # qkv kernel [32, 96]: TP on dim1 (model), FSDP on dim0 (data) → 2D
    qkv = sh["params"]["h0"]["attn"]["qkv"]["kernel"].spec
    assert qkv == P("data", "model"), qkv
    sp = jax.device_put(params, sh)
    opt = tx.init(sp)
    step = fsdp_tp_train_step(loss_fn, tx, mesh, rules, donate=False, min_shard_elems=64)

    # oracle: plain replicated adam on the full batch
    o_params, o_opt = jax.tree_util.tree_map(jnp.array, params), tx.init(params)

    @jax.jit
    def plain(p, o, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    for _ in range(3):
        sp, opt, lf = step(sp, opt, tokens)
        o_params, o_opt, lo = plain(o_params, o_opt, tokens)
        np.testing.assert_allclose(float(lf), float(lo), rtol=2e-5)
    k = sp["params"]["h0"]["attn"]["qkv"]["kernel"]
    np.testing.assert_allclose(
        np.asarray(k), np.asarray(o_params["params"]["h0"]["attn"]["qkv"]["kernel"]),
        rtol=3e-5, atol=3e-6,
    )
    # each device holds 1/8 of the 2D-sharded kernel
    assert k.addressable_shards[0].data.shape == (32 // 4, 96 // 2)
    # adam moments share the 2D layout
    assert opt[0].mu["params"]["h0"]["attn"]["qkv"]["kernel"].sharding.spec == qkv


def test_zero1_ring_matches_xla_path(mesh8):
    """ZeRO-1 on the Pallas ring data plane (ring=True) trains to the same
    params as the XLA psum_scatter/all_gather path (VERDICT r4 item 4)."""
    rng = np.random.default_rng(11)
    params = _mlp_params(rng)
    tx = optax.adam(1e-2)

    runs = {}
    for ring in (False, True):
        opt = Zero1Optimizer(tx, mesh8, ring=ring)
        master, opt_state = opt.init(params)
        step = zero1_train_step(_mlp_loss, opt, mesh8)
        p = jax.tree_util.tree_map(jnp.array, params)
        for i in range(2):
            b = _batch(np.random.default_rng(300 + i), n=16)
            p, master, opt_state, losses = step(p, master, opt_state, b)
        runs[ring] = (p, np.asarray(losses))

    np.testing.assert_allclose(runs[True][1], runs[False][1], rtol=1e-5, atol=1e-6)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(runs[True][0][k]), np.asarray(runs[False][0][k]),
            rtol=2e-5, atol=2e-6,
        )


def test_zero1_ring_apply_presynced(mesh8):
    """The apply() composition site (replicated grads, no RS) also rides the
    ring all-gather and reproduces the XLA-path update."""
    rng = np.random.default_rng(12)
    params = _mlp_params(rng)
    tx = optax.sgd(1e-1)
    grads = jax.tree_util.tree_map(
        lambda v: jnp.asarray(rng.normal(size=v.shape), jnp.float32), params
    )

    outs = {}
    for ring in (False, True):
        opt = Zero1Optimizer(tx, mesh8, ring=ring)
        master, opt_state = opt.init(params)
        _, _, new_params = opt.apply(master, opt_state, grads)
        outs[ring] = new_params
    for k in params:
        np.testing.assert_allclose(
            np.asarray(outs[True][k]), np.asarray(outs[False][k]),
            rtol=1e-6, atol=1e-7,
        )


def test_zero1_checkpoint_layout_guard(mesh8):
    """Resuming with --zero1-ring flipped must fail loudly: ring and
    non-ring masters are chunk-permuted relative to each other."""
    tx = optax.sgd(1e-1)
    flat = Zero1Optimizer(tx, mesh8, ring=False)
    ring = Zero1Optimizer(tx, mesh8, ring=True)

    # the optimizer's stamp key must be one checkpoint.py's load-funnel
    # guard enforces, or a rename silently disables the funnel-side check
    from adapcc_tpu.checkpoint import LAYOUT_GUARD_KEYS

    assert Zero1Optimizer.LAYOUT_KEY in LAYOUT_GUARD_KEYS

    extra = flat.checkpoint_extra({"note": "kept"})
    assert extra["note"] == "kept"
    flat.validate_checkpoint_extra(extra)  # matching layout passes

    with pytest.raises(ValueError, match="layout mismatch"):
        ring.validate_checkpoint_extra(extra)
    with pytest.raises(ValueError, match="no zero1 layout tag"):
        flat.validate_checkpoint_extra({})
    with pytest.raises(ValueError, match="no zero1 layout tag"):
        flat.validate_checkpoint_extra(None)


def test_zero1_restore_roundtrip_and_mismatch(mesh8):
    """restore() places a tagged (master, opt_state) pair and rejects a
    checkpoint saved under the other layout."""
    from types import SimpleNamespace

    rng = np.random.default_rng(5)
    params = _mlp_params(rng)
    tx = optax.sgd(1e-1)
    opt = Zero1Optimizer(tx, mesh8, ring=False)
    master, opt_state = opt.init(params)

    ckpt = SimpleNamespace(
        opt_state=(np.asarray(master), opt_state),
        extra=opt.checkpoint_extra(),
    )
    restored_master, _ = opt.restore(ckpt)
    np.testing.assert_allclose(np.asarray(restored_master), np.asarray(master))

    other = Zero1Optimizer(tx, mesh8, ring=True)
    with pytest.raises(ValueError, match="layout mismatch"):
        other.restore(ckpt)


def test_zero1_ring_chunk_bytes_reaches_the_kernel(mesh8, monkeypatch):
    """The synthesized chunk_bytes flows Zero1Optimizer → zero1_apply_shard
    → ring_all_gather_shard, on every build: the ring collectives are
    faked with their XLA equivalents (rank-ordered all_gather IS the ring's
    gathered layout), recording the granularity they were handed."""
    import adapcc_tpu.comm.pallas_ring as pr
    from jax import lax

    seen = {}

    def fake_ag(x, world, axis_name="ranks", interpret=False, chunk_bytes=None):
        seen["ag_chunk"] = chunk_bytes
        return lax.all_gather(x.reshape(-1), axis_name)

    monkeypatch.setattr(pr, "ring_all_gather_shard", fake_ag)
    rng = np.random.default_rng(13)
    params = _mlp_params(rng)
    grads = jax.tree_util.tree_map(
        lambda v: jnp.asarray(rng.normal(size=v.shape), jnp.float32), params
    )
    opt = Zero1Optimizer(
        optax.sgd(1e-1), mesh8, ring=True, ring_chunk_bytes=1 << 18
    )
    master, opt_state = opt.init(params)
    _, _, ring_params = opt.apply(master, opt_state, grads)
    assert seen["ag_chunk"] == 1 << 18

    # the faked ring reproduces the XLA path's update, so the plumbing test
    # doubles as a semantics pin for the fake itself
    xla = Zero1Optimizer(optax.sgd(1e-1), mesh8)
    m2, s2 = xla.init(params)
    _, _, xla_params = xla.apply(m2, s2, grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(ring_params[k]), np.asarray(xla_params[k]),
            rtol=1e-6, atol=1e-7,
        )
