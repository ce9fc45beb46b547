"""Sequence-parallel GPT-2 training (parallel/gpt2_sp.py): the sharded step
must be numerically identical to the single-device step — loss AND grads —
for both SP schemes, and must train."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from adapcc_tpu.parallel import gpt2_sp_loss_and_grad, gpt2_sp_train_step

BASE = dict(vocab_size=64, max_seq=32, n_layer=2, n_head=2, d_model=32,
            dtype=jnp.float32)


def _tokens(B=2, T=32, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 64, size=(B, T)), jnp.int32
    )


# ulysses needs n_head % world == 0
FOUR_HEADS = {**BASE, "n_head": 4}


@pytest.fixture(scope="module")
def single_device():
    """``(tokens, params, loss, grads)`` of the plain four-head model on one
    device, each a compiled program run once: both schemes are held to it."""
    tokens = _tokens()
    plain = GPT2(GPT2Config(**FOUR_HEADS))
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), tokens)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(plain.apply(p, tokens), tokens)
    ))(params)
    return tokens, params, loss, grads


@pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
def test_sp_loss_and_grads_match_single_device(mesh4, single_device, sp_impl):
    tokens, params, loss_ref, grads_ref = single_device

    sp_model = GPT2(GPT2Config(**FOUR_HEADS, sp_axis="ranks", sp_impl=sp_impl))
    loss_sp, grads_sp = gpt2_sp_loss_and_grad(sp_model, mesh4)(params, tokens)

    np.testing.assert_allclose(float(loss_sp), float(loss_ref), atol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(grads_ref)
    flat_sp = jax.tree_util.tree_leaves(grads_sp)
    for a, b in zip(flat_sp, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_sp_flash_blocks_match_dense(mesh4):
    tokens = _tokens(seed=1)
    params = jax.jit(GPT2(GPT2Config(**BASE)).init)(jax.random.PRNGKey(0), tokens)
    dense = GPT2(GPT2Config(**BASE, sp_axis="ranks", attention="xla"))
    flash = GPT2(GPT2Config(**BASE, sp_axis="ranks", attention="flash"))
    l_dense, g_dense = gpt2_sp_loss_and_grad(dense, mesh4)(params, tokens)
    l_flash, g_flash = gpt2_sp_loss_and_grad(flash, mesh4)(params, tokens)
    np.testing.assert_allclose(float(l_flash), float(l_dense), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_flash), jax.tree_util.tree_leaves(g_dense)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.slow
def test_sp_train_step_learns(mesh4):
    model = GPT2(GPT2Config(**BASE, sp_axis="ranks"))
    tokens = _tokens(B=8, seed=2)
    params = GPT2(GPT2Config(**BASE)).init(jax.random.PRNGKey(0), tokens)
    tx = optax.adam(1e-2)
    step = gpt2_sp_train_step(model, tx, mesh4)
    opt_state = tx.init(params)
    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_sp_axis_mismatch_rejected(mesh4):
    model = GPT2(GPT2Config(**BASE, sp_axis="other"))
    with pytest.raises(ValueError, match="sp_axis"):
        gpt2_sp_loss_and_grad(model, mesh4)


def test_sp_rejects_dropout(mesh4):
    model = GPT2(GPT2Config(**BASE, sp_axis="ranks", dropout=0.1))
    tokens = _tokens()
    params = jax.jit(GPT2(GPT2Config(**BASE)).init)(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="dropout"):
        gpt2_sp_loss_and_grad(model, mesh4)(params, tokens)


@pytest.mark.slow
def test_dp_x_sp_matches_single_device(mesh4):
    """2D (data, sp) mesh: batch sharded over data, sequence over sp — loss
    and grads must still equal the single-device computation."""
    from jax.sharding import Mesh

    base = {**BASE}
    tokens = _tokens(B=4, seed=7)
    plain = GPT2(GPT2Config(**base))
    params = plain.init(jax.random.PRNGKey(0), tokens)
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: lm_loss(plain.apply(p, tokens), tokens)
    )(params)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "sp"))
    sp_model = GPT2(GPT2Config(**base, sp_axis="sp"))
    loss_2d, grads_2d = gpt2_sp_loss_and_grad(
        sp_model, mesh, axis_name="sp", data_axis="data"
    )(params, tokens)

    np.testing.assert_allclose(float(loss_2d), float(loss_ref), atol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(grads_2d), jax.tree_util.tree_leaves(grads_ref)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.slow
def test_dp_x_sp_train_step_learns(mesh4):
    from jax.sharding import Mesh

    from adapcc_tpu.parallel import gpt2_sp_train_step

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "sp"))
    model = GPT2(GPT2Config(**BASE, sp_axis="sp"))
    tokens = _tokens(B=8, seed=8)
    params = GPT2(GPT2Config(**BASE)).init(jax.random.PRNGKey(0), tokens)
    tx = optax.adam(1e-2)
    step = gpt2_sp_train_step(model, tx, mesh, axis_name="sp", data_axis="data")
    opt_state = tx.init(params)
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6, (losses[0], losses[-1])


def test_dp_x_sp_rejects_unknown_data_axis(mesh4):
    model = GPT2(GPT2Config(**BASE, sp_axis="ranks"))
    with pytest.raises(ValueError, match="data_axis"):
        gpt2_sp_loss_and_grad(model, mesh4, data_axis="nope")
