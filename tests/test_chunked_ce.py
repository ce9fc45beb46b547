"""Chunked vocab cross-entropy: dense-oracle parity for values and all grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss, lm_loss_chunked
from adapcc_tpu.ops.chunked_ce import chunked_lm_loss, chunked_softmax_xent


def _dense_xent(x, w, y, compute_dtype=jnp.float32):
    logits = (x.astype(compute_dtype) @ w.T.astype(compute_dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


@pytest.mark.parametrize("block", [8, 32])
def test_chunked_xent_matches_dense(block):
    rng = np.random.default_rng(0)
    N, D, V = 24, 16, 64
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    got = chunked_softmax_xent(x, w, y, block, jnp.float32)
    want = _dense_xent(x, w, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_chunked_xent_grads_match_dense():
    rng = np.random.default_rng(1)
    N, D, V = 12, 8, 32
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    gx, gw = jax.grad(
        lambda x, w: chunked_softmax_xent(x, w, y, 8, jnp.float32), argnums=(0, 1)
    )(x, w)
    ox, ow = jax.grad(lambda x, w: _dense_xent(x, w, y), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ox), atol=2e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ow), atol=2e-6)


def test_lm_loss_chunked_matches_lm_loss_fp32():
    cfg = GPT2Config(
        vocab_size=64, max_seq=16, n_layer=1, n_head=2, d_model=32,
        dtype=jnp.float32,
    )
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, size=(2, 16)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    dense_loss = lambda p: lm_loss(model.apply(p, tokens), tokens)  # noqa: E731
    chunked_loss = lambda p: lm_loss_chunked(model, p, tokens, block=16)  # noqa: E731
    dense = jax.jit(dense_loss)(params)
    chunked = jax.jit(chunked_loss)(params)
    np.testing.assert_allclose(float(chunked), float(dense), rtol=2e-6)

    # full training gradient (incl. the weight-tied wte double contribution)
    gd = jax.jit(jax.grad(dense_loss))(params)
    gc = jax.jit(jax.grad(chunked_loss))(params)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(gd), jax.tree_util.tree_leaves_with_path(gc)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-6,
            err_msg=jax.tree_util.keystr(pa),
        )


def test_lm_loss_chunked_bf16_close_and_trains():
    """bf16 head (the bench configuration): close to the dense bf16 loss and
    the value decreases under adam on the chunked objective."""
    import optax

    cfg = GPT2Config(vocab_size=64, max_seq=16, n_layer=1, n_head=2, d_model=32)
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, size=(4, 16)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    dense = float(jax.jit(lambda p: lm_loss(model.apply(p, tokens), tokens))(params))
    chunked = float(jax.jit(lambda p: lm_loss_chunked(model, p, tokens, block=16))(params))
    assert abs(dense - chunked) / dense < 0.02

    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(p, o):
        loss, g = jax.value_and_grad(
            lambda p: lm_loss_chunked(model, p, tokens, block=16)
        )(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, loss

    losses = []
    for _ in range(5):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_chunked_xent_nonmultiple_vocab_pads():
    """A prime vocab pays one padded block, with exact dense parity for the
    value and both gradients."""
    rng = np.random.default_rng(4)
    N, D, V = 10, 8, 37  # prime vocab
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    got = chunked_softmax_xent(x, w, y, 16, jnp.float32)
    want = _dense_xent(x, w, y)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    gx, gw = jax.grad(
        lambda x, w: chunked_softmax_xent(x, w, y, 16, jnp.float32), argnums=(0, 1)
    )(x, w)
    ox, ow = jax.grad(lambda x, w: _dense_xent(x, w, y), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ox), atol=2e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ow), atol=2e-6)
    assert gw.shape == (V, D)


def test_sp_chunked_loss_matches_dense_sp(mesh8):
    """The long-context x long-vocab composition: the SP chunked loss equals
    the dense SP loss, and its full training gradient matches."""
    import dataclasses

    from adapcc_tpu.parallel import gpt2_sp_loss_and_grad

    cfg = GPT2Config(
        vocab_size=48, max_seq=32, n_layer=1, n_head=2, d_model=16,
        dtype=jnp.float32, sp_axis="ranks",
    )
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, cfg.vocab_size, size=(2, 32)), jnp.int32
    )
    params = jax.jit(GPT2(dataclasses.replace(cfg, sp_axis=None)).init)(
        jax.random.PRNGKey(0), tokens
    )
    dense = gpt2_sp_loss_and_grad(model, mesh8, loss="dense")
    chunk = gpt2_sp_loss_and_grad(model, mesh8, loss="chunked")
    ld, gd = dense(params, tokens)
    lc, gc = chunk(params, tokens)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gd), jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ------------------------------------------------------------- vocab-parallel


def test_vocab_parallel_chunked_xent_matches_dense(mesh8):
    """8-way vocab-sharded loss + grads match the dense single-device oracle;
    dw comes back sharded (each rank's rows only)."""
    from jax.sharding import PartitionSpec as P

    from adapcc_tpu.ops.chunked_ce import chunked_softmax_xent_shard

    rng = np.random.default_rng(5)
    N, D, V = 16, 8, 64  # 8 ranks x 8 vocab rows
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)

    def per_shard(x, w_shard, y):
        loss, (dx, dw) = jax.value_and_grad(
            lambda x, w: chunked_softmax_xent_shard(
                x, w, y, "ranks", 4, jnp.float32
            ),
            argnums=(0, 1),
        )(x, w_shard)
        return loss[None], dx[None], dw

    loss, dx, dw = jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh8,
            in_specs=(P(), P("ranks"), P()),
            out_specs=(P("ranks"), P("ranks"), P("ranks")),
            check_vma=False,
        )
    )(x, w, y)

    want = _dense_xent(x, w, y)
    np.testing.assert_allclose(np.asarray(loss), float(want), rtol=1e-6)
    ox, ow = jax.grad(lambda x, w: _dense_xent(x, w, y), argnums=(0, 1))(x, w)
    # every rank's dx (psum'd) equals the full dense dx
    for r in range(8):
        np.testing.assert_allclose(np.asarray(dx[r]), np.asarray(ox), atol=2e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ow), atol=2e-6)


def test_vocab_parallel_padded_shard_regression(mesh8):
    """V_local not a multiple of block: targets owned by other ranks fall in
    this rank's pad-tail index range — must contribute nothing (the -inf
    target bug)."""
    from jax.sharding import PartitionSpec as P

    from adapcc_tpu.ops.chunked_ce import chunked_softmax_xent_shard

    rng = np.random.default_rng(6)
    N, D, V = 12, 8, 48  # V_local = 6, block 4 → one padded block per rank
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, D)) * 0.3, jnp.float32)
    y = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)

    def per_shard(x, w_shard, y):
        loss, (dx, dw) = jax.value_and_grad(
            lambda x, w: chunked_softmax_xent_shard(x, w, y, "ranks", 4, jnp.float32),
            argnums=(0, 1),
        )(x, w_shard)
        return loss[None], dx[None], dw

    loss, dx, dw = jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh8,
            in_specs=(P(), P("ranks"), P()),
            out_specs=(P("ranks"), P("ranks"), P("ranks")),
            check_vma=False,
        )
    )(x, w, y)
    want = _dense_xent(x, w, y)
    assert np.isfinite(np.asarray(loss)).all()
    np.testing.assert_allclose(np.asarray(loss), float(want), rtol=1e-6)
    ox, ow = jax.grad(lambda x, w: _dense_xent(x, w, y), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx[0]), np.asarray(ox), atol=2e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(ow), atol=2e-6)


def test_sp_chunked_loss_ulysses_path(mesh8):
    """The chunked SP loss is orthogonal to the attention scheme: parity
    with the dense SP loss holds on the Ulysses program too."""
    import dataclasses

    from adapcc_tpu.parallel import gpt2_sp_loss_and_grad

    cfg = GPT2Config(
        vocab_size=48, max_seq=32, n_layer=1, n_head=8, d_model=16,
        dtype=jnp.float32, sp_axis="ranks", sp_impl="ulysses",
    )
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(0, cfg.vocab_size, size=(2, 32)), jnp.int32
    )
    params = jax.jit(GPT2(dataclasses.replace(cfg, sp_axis=None)).init)(
        jax.random.PRNGKey(0), tokens
    )
    ld, gd = gpt2_sp_loss_and_grad(model, mesh8, loss="dense")(params, tokens)
    lc, gc = gpt2_sp_loss_and_grad(model, mesh8, loss="chunked")(params, tokens)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gd), jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
