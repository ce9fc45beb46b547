"""The plain reference against the program at toy widths, and the control:
the reference in the next lower precision, put in the program's place, has
to come out as not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import TINY_LIMITS, tiny_config, tiny_mix

from chipbench import correct, weights
from chipbench.reference import gpt2_ref
from chipbench.runners import train
from chipbench.traffic import generator

SEEDS = (11, 2**31 + 12, 13)


def _program_loss_and_grads(cfg, params, tokens, dtype):
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

    model = GPT2(GPT2Config(
        vocab_size=cfg["vocab_size"], max_seq=cfg["max_seq"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_model=cfg["d_model"], attention=cfg["attention"], dtype=dtype,
    ))
    return jax.value_and_grad(lambda p: lm_loss(model.apply(p, tokens), tokens))(params)


def _ref_cfg(cfg):
    return dict(cfg, layer_norm_epsilon=cfg["assumed"]["layer_norm_epsilon"])


@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    # float32 activations: only the order of summation differs
    (jnp.float32, 1e-5, 2e-3),
    # the program's default, bfloat16 activations over float32 parameters:
    # 8 bits of mantissa through every product and the residual stream
    (jnp.bfloat16, 2e-3, 0.1),
])
def test_program_value_and_grad_agrees_with_the_plain_reference(dtype, loss_tol, grad_tol):
    cfg = tiny_config()
    params = weights.make_params(5, cfg)
    tokens = jnp.asarray(generator.make_rows(tiny_mix(), cfg["vocab_size"], 5)[:2])
    loss, grads = _program_loss_and_grads(cfg, params, tokens, dtype)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = gpt2_ref.loss_and_grads(params, tokens, _ref_cfg(cfg), row_block=1)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < loss_tol
    flat, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    norms = [float(jnp.linalg.norm(g)) for _, g in flat]
    floor = float(np.median(norms))
    for (path, ref), got in zip(flat, jax.tree_util.tree_leaves(grads)):
        err = float(jnp.linalg.norm(got - ref)) / max(float(jnp.linalg.norm(ref)), floor)
        assert err < grad_tol, (jax.tree_util.keystr(path), err)


def test_reference_rows_in_blocks_equal_rows_at_once():
    cfg = tiny_config()
    params = weights.make_params(3, cfg)
    tokens = jnp.asarray(generator.make_rows(tiny_mix(), cfg["vocab_size"], 3)[:4])
    whole = gpt2_ref.loss_and_grads(params, tokens, _ref_cfg(cfg), row_block=4)
    blocks = gpt2_ref.loss_and_grads(params, tokens, _ref_cfg(cfg), row_block=1)
    assert float(whole[0]) == pytest.approx(float(blocks[0]), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(whole[1]), jax.tree_util.tree_leaves(blocks[1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_reference_adamw_steps_equal_optax():
    import optax

    cfg = tiny_config()
    opt = cfg["assumed"]["optimizer"]
    params = weights.make_params(9, cfg)
    rows = generator.make_rows(tiny_mix(), cfg["vocab_size"], 9)[:12].reshape(3, 4, -1)
    ours = jax.jit(lambda p, b: gpt2_ref.train_steps(p, b, _ref_cfg(cfg), opt, "float32", 2))(params, rows)
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"]),
    )
    state, p, losses = tx.init(params), params, []
    for batch in rows:
        loss, grads = gpt2_ref.loss_and_grads(p, jnp.asarray(batch), _ref_cfg(cfg), row_block=2)
        updates, state = tx.update(grads, state, p)
        p = optax.apply_updates(p, updates)
        losses.append(float(loss))
    np.testing.assert_allclose(ours["losses"], losses, rtol=1e-5)
    moved = gpt2_ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, params))
    np.testing.assert_allclose(ours["update_norms"], moved, rtol=2e-3)


@pytest.fixture(scope="module")
def readings():
    """Per seed: the reference's three steps and the control's, at the toy
    cell's own batch."""
    cfg = tiny_config()
    ref_fn = train.reference_fn(cfg, 4)
    ctl_fn = train.reference_fn(cfg, 4, precision="float8")
    out = []
    for seed in SEEDS:
        rows = generator.make_rows(tiny_mix(), cfg["vocab_size"], seed)[:12].reshape(3, 4, -1)
        out.append((
            train.reference_numbers(cfg, rows, seed, ref_fn),
            train.reference_numbers(cfg, rows, seed, ctl_fn),
        ))
    return out


def test_the_float8_control_comes_out_not_correct_on_every_seed(readings):
    """bfloat16 is what the configuration states, so the control is the
    reference with every product's operands in float8 (e4m3, one scale per
    tensor).  It has to fail one of the numbers, not each."""
    for reference, control in readings:
        rows = correct.compare(control, reference, TINY_LIMITS)
        assert not correct.verdict(rows), rows
        assert correct.verdict(correct.compare(reference, reference, TINY_LIMITS))
