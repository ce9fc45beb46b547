"""JoyAI-LLM-Flash's block (``adapcc_tpu/models/joyai_flash.py``) at a small
size on the CPU, the flash kernels in the Pallas interpreter.

The rotation against complex multiplication of the plain pairs and its
relative-position property; the latent mixer with its query rank against the
head-at-a-time form, one mixer for this model and Kimi-Linear; both heads'
logits, both loss terms and every gradient leaf against the plain reference
on seeded weights; the loss's weight at zero; the two uses of the embedding
and the head; the shifted full-length module against the sliced one; the
shares' routed parts plus the shared expert once add up to the uncut layer;
``KimiLinear`` and ``Trinity`` lower to the text they lowered to before this
model came, and this model, Granite's and Phi-4-flash's to what they lowered
to before ``models/lm.py``; the workload trains through ``DDPTrainer.step``.
"""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models import trinity
from adapcc_tpu.models.joyai_flash import (
    JoyAIFlash, JoyAIFlashConfig, MTPModule, initial_model_state, record_step, stateful_loss,
)
from adapcc_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig, MLAMixer, rotate_pairs
from adapcc_tpu.models.moe import routed_experts
from adapcc_tpu.utils.observability import MetricsRegistry, default_registry
from chipbench import weights_mla_lm
from chipbench.reference import joyai_flash_ref, trinity_ref

CFG = JoyAIFlashConfig.tiny()
PROD = joyai_flash_ref._product("float32")
PROGRAMS_OWN = ("dtype", "remat", "experts_held", "mtp_loss_weight")


def file_config(cfg: JoyAIFlashConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's file states it (``config.json`` keys)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name not in PROGRAMS_OWN}
    out.update(num_experts_held=cfg.held, assumed={"mtp_loss_weight": cfg.mtp_loss_weight})
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    return weights_mla_lm.make_params(5, file_config())


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)


@pytest.fixture(scope="module")
def loss_and_grads(params, tokens):
    """``loss_and_grads(cfg, loss)``: ``((value, state), grads)`` of the model's
    loss on the module's weights and tokens, one compiled program for each
    configuration and form of the loss, run once a module: the tests that
    weigh the same step against different things read the one result."""

    @functools.cache
    def step(cfg=CFG, loss="dense"):
        return jax.jit(jax.value_and_grad(stateful_loss(JoyAIFlash(cfg), loss, block=64), has_aux=True))(
            params, initial_model_state(cfg), tokens
        )

    return step


@pytest.fixture(scope="module")
def reference(params, tokens):
    """The plain reference's ``((loss, (main, mtp)), grads)`` on the module's
    weights and tokens, once a module: both forms of the model's loss are held
    to the same numbers."""
    return jax.jit(lambda p, t: joyai_flash_ref.loss_and_grads(p, t, file_config()))(params, tokens)


@pytest.fixture(scope="module")
def heads(params, tokens):
    """``(logits, mtp_logits, sizes)`` of the model on the module's weights and tokens."""
    return jax.jit(JoyAIFlash(CFG).apply)(params, tokens)


def assert_trees_close(got, want, tol=5e-4):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6 + tol * scale, err_msg=jax.tree_util.keystr(path)
        )


# --- the rotation --------------------------------------------------------------


def by_complex_multiplication(x, theta):
    """The plain pairs ``(2i, 2i+1)`` of ``x [B, T, H, D]`` as complex numbers
    times ``exp(i m theta^(-2i/D))``, back in their places; float64."""
    x = np.asarray(x, np.float64)
    T, D = x.shape[1], x.shape[-1]
    angle = np.arange(T)[:, None] * theta ** (-np.arange(0, D, 2) / D)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * angle)[None, :, None, :]
    out = np.empty_like(x)
    out[..., 0::2], out[..., 1::2] = z.real, z.imag
    return out


def by_turns(x, theta):
    """The same rotation written for automatic differentiation: cosines and
    sines times the even and the odd channels, stacked back in their places."""
    T, D = x.shape[1], x.shape[-1]
    angle = jnp.asarray(np.arange(T)[:, None] * theta ** (-np.arange(0, D, 2) / D), jnp.float32)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    pairs = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle), a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)
    return pairs.reshape(x.shape)


@pytest.mark.parametrize("D,theta", [(8, 10000.0), (64, 32000000.0)], ids=["8-at-1e4", "64-at-32e6"])
def test_the_rotation_is_complex_multiplication_of_the_plain_pairs(D, theta):
    x = jax.random.normal(jax.random.PRNGKey(D), (2, 50, 3, D))
    got = np.asarray(rotate_pairs(x, theta))
    np.testing.assert_allclose(got, by_complex_multiplication(x, theta), atol=2e-6)
    np.testing.assert_array_equal(got[:, 0], np.asarray(x[:, 0]))             # position 0 turns by nothing
    pairs = lambda a: np.hypot(a[..., 0::2], a[..., 1::2])  # noqa: E731
    np.testing.assert_allclose(pairs(got), pairs(np.asarray(x)), rtol=2e-6)   # a turn keeps a pair's length
    # from a channel on: the channels before it stay, the rest turn as a head of their own; and what the turn
    # hands back for a cotangent is the turn back (its own backward pass, not the two rolls' transposes)
    wide = jnp.concatenate([x, x], axis=-1)
    np.testing.assert_allclose(
        np.asarray(rotate_pairs(wide, theta, start=D)), np.concatenate([np.asarray(x), got], axis=-1), atol=1e-6
    )
    back = jax.grad(lambda a: jnp.sum(rotate_pairs(a, theta) * x))(x)
    np.testing.assert_allclose(
        np.asarray(back), np.asarray(jax.grad(lambda a: jnp.sum(by_turns(a, theta) * x))(x)), atol=2e-5
    )
    with pytest.raises(ValueError, match="no pairs"):
        rotate_pairs(wide, theta, start=3)
    # the reference lays the real parts before the imaginary ones: the same numbers, permuted
    ref = np.asarray(joyai_flash_ref.rotate(x[0], theta))
    np.testing.assert_allclose(ref[..., : D // 2], got[0][..., 0::2], atol=2e-6)
    np.testing.assert_allclose(ref[..., D // 2:], got[0][..., 1::2], atol=2e-6)


def test_rotated_scores_depend_on_the_distance_alone():
    """``<R_m q, R_n k>`` is a function of ``m - n``: the same two vectors
    placed at every position score the same along each diagonal."""
    key_q, key_k = jax.random.split(jax.random.PRNGKey(3))
    T, D = 24, 16
    q = jnp.broadcast_to(jax.random.normal(key_q, (1, 1, 1, D)), (1, T, 1, D))
    k = jnp.broadcast_to(jax.random.normal(key_k, (1, 1, 1, D)), (1, T, 1, D))
    scores = np.einsum("md,nd->mn", np.asarray(rotate_pairs(q, 100.0))[0, :, 0], np.asarray(rotate_pairs(k, 100.0))[0, :, 0])
    for distance in range(-T + 1, T):
        diagonal = np.diagonal(scores, -distance)
        np.testing.assert_allclose(diagonal, diagonal[0], atol=2e-5)
    assert np.ptp(scores[:, 0]) > 0.1                                         # and they do depend on it


# --- the latent mixer ------------------------------------------------------------


@pytest.mark.parametrize("T", [40, 256])
def test_the_latent_mixer_with_its_query_rank_and_rotation_is_the_head_at_a_time_form(params, T):
    """Queries up from a normed latent of 24, scores over 16 + 8 channels (the
    8 rotated, the keys' 8 the same for every head), values over 16; a row
    inside one tile of the kernel and a row over two."""
    p = params["params"]["layers_1"]["self_attn"]
    assert p["q_a_proj"]["kernel"].shape == (32, 24) and p["q_b_proj"]["kernel"].shape == (24, 2 * 24)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T, 32)), jnp.float32)
    got = jax.jit(MLAMixer(CFG).apply)({"params": p}, x)
    plain = jax.jit(
        lambda p, x, turn: jnp.stack([joyai_flash_ref.mla_mixer(row, p, file_config(), PROD, turn=turn) for row in x]),
        static_argnums=2,
    )
    want = plain(p, x, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    unrotated = plain(p, x, False)
    assert float(jnp.max(jnp.abs(unrotated - want))) > 1e-5           # and the rotation is no small thing beside the tolerance


def test_one_mixer_serves_both_models(params):
    """``MLAMixer`` under Kimi-Linear's configuration given the query rank and
    the rotation is this model's mixer on the same weights; as published
    (no rank, ``mla_use_nope``) it has one query projection."""
    p = params["params"]["layers_1"]["self_attn"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 40, 32)), jnp.float32)
    kimi = KimiLinearConfig.tiny(q_lora_rank=24, mla_use_nope=False, rope_theta=CFG.rope_theta, rms_norm_eps=CFG.rms_norm_eps)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(MLAMixer(kimi).apply)({"params": p}, x)),
        np.asarray(jax.jit(MLAMixer(CFG).apply)({"params": p}, x)),
    )
    published = MLAMixer(KimiLinearConfig.tiny())
    shapes = jax.eval_shape(published.init, jax.random.PRNGKey(0), x)["params"]
    assert "q_proj" in shapes and "q_a_proj" not in shapes and "q_a_proj" in p
    # and a whole Kimi-Linear with the two keys it used to refuse runs, and is another function
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 256, (1, 40)), jnp.int32)
    turned = KimiLinearConfig.tiny(mla_use_nope=False)
    weights = jax.jit(KimiLinear(turned).init)(jax.random.PRNGKey(1), tokens)
    rotated, _ = jax.jit(KimiLinear(turned).apply)(weights, tokens)
    plain, _ = jax.jit(KimiLinear(KimiLinearConfig.tiny()).apply)(weights, tokens)
    assert float(jnp.max(jnp.abs(rotated - plain))) > 1e-5


# --- the model -------------------------------------------------------------------


def test_the_weight_maker_makes_the_tree_the_model_reads(params):
    shapes = jax.eval_shape(JoyAIFlash(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    module = params["params"]["mtp"]
    assert set(module) == {"enorm", "hnorm", "eh_proj", "block", "shared_head_norm"}
    assert module["eh_proj"]["kernel"].shape == (64, 32) and "router" in module["block"]["mlp"]
    assert "embed_tokens" not in module and "lm_head" not in module        # the trunk's own, not copies


def test_both_heads_logits_match_the_plain_reference(params, tokens, heads):
    logits, mtp_logits, sizes = heads
    assert logits.shape == mtp_logits.shape == (2, 40, 256)
    plain = jax.jit(lambda p, row: joyai_flash_ref.logits_fn(p, row, file_config()))
    for row in range(2):
        want, want_mtp = plain(params, tokens[row])
        np.testing.assert_allclose(np.asarray(logits[row]), np.asarray(want), atol=5e-6)
        # the sliced module has T - 1 places: the shifted one's last place holds a filler's
        np.testing.assert_allclose(np.asarray(mtp_logits[row, :-1]), np.asarray(want_mtp), atol=5e-6)
    assert sizes.shape == (3, 8) and sizes.sum(axis=1).tolist() == [2 * 40 * 2] * 3      # two trunk layers, the module's
    assert default_registry().snapshot()["gauges"]["mtp.depth"] == 1


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_both_loss_terms_and_every_gradient_leaf_match_the_plain_reference(loss_and_grads, reference, loss):
    (value, state), grads = loss_and_grads(CFG, loss)
    (want, (want_main, want_mtp)), want_grads = reference
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert float(state["loss_main"]) == pytest.approx(float(want_main), rel=1e-6)
    assert float(state["loss_mtp"]) == pytest.approx(float(want_mtp), rel=1e-6)
    assert float(value) == pytest.approx(float(state["loss_main"]) + 0.3 * float(state["loss_mtp"]), rel=1e-6)
    assert state["moe_sizes"].shape == (3, 8) == initial_model_state(CFG)["moe_sizes"].shape
    assert_trees_close(grads, want_grads)
    assert not np.any(np.asarray(grads["params"]["mtp"]["block"]["mlp"]["expert_bias"]))
    assert float(jnp.max(jnp.abs(grads["params"]["mtp"]["eh_proj"]["kernel"]))) > 1e-4


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_recomputing_a_block_changes_no_number(loss_and_grads, remat):
    (value, _), grads = loss_and_grads(CFG)
    (again, _), grads_again = loss_and_grads(dataclasses.replace(CFG, remat=remat))
    assert float(again) == pytest.approx(float(value), rel=1e-6)
    assert_trees_close(grads_again, grads, tol=1e-5)


def test_a_weight_of_zero_gives_the_trunks_gradient_and_nothing_on_the_modules_own_leaves(
    params, tokens, loss_and_grads
):
    (value, state), grads = loss_and_grads(dataclasses.replace(CFG, mtp_loss_weight=0.0))
    assert float(value) == float(state["loss_main"]) and float(state["loss_mtp"]) > 0
    assert not any(np.any(np.asarray(g)) for g in jax.tree_util.tree_leaves(grads["params"]["mtp"]))

    def trunk_alone(p):
        logits, _, _ = JoyAIFlash(CFG).apply(p, tokens)
        return jnp.mean(      # the mean next-token cross-entropy, the textbook way
            jax.nn.logsumexp(logits[:, :-1], axis=-1)
            - jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
        )

    assert_trees_close(grads, jax.jit(jax.grad(trunk_alone))(params), tol=1e-5)


def test_the_embeddings_and_the_heads_gradients_are_the_sums_of_their_two_uses(params, tokens, loss_and_grads):
    """``embed_tokens`` is read for the trunk's input and for the module's
    merge, ``lm_head`` by both losses: what each gets back for ``L`` is what
    it gets for ``L_main`` plus 0.3 of what it gets for ``L_mtp``, and neither
    part is nothing."""
    model = JoyAIFlash(CFG)

    def term(name):
        return jax.jit(jax.grad(lambda p: stateful_loss(model)(p, None, tokens)[1][name]))(params)["params"]

    main, mtp = term("loss_main"), term("loss_mtp")
    total = loss_and_grads(CFG)[1]["params"]
    for leaf in (lambda g: g["embed_tokens"]["embedding"], lambda g: g["lm_head"]):
        assert float(jnp.max(jnp.abs(leaf(main)))) > 1e-5 and float(jnp.max(jnp.abs(leaf(mtp)))) > 1e-5
        np.testing.assert_allclose(np.asarray(leaf(total)), np.asarray(leaf(main) + 0.3 * leaf(mtp)), atol=1e-7)
    assert not any(np.any(np.asarray(g)) for g in jax.tree_util.tree_leaves(main["mtp"]))


def test_the_shifted_full_length_module_is_the_sliced_one_on_the_places_that_count(params, tokens, heads):
    """The module over all ``T`` places with a filler last, against the module
    over the ``T - 1`` places that exist: the block is causal, so whatever
    stands in the last place moves no place before it, and the loss reads
    places ``0 .. T-3`` alone."""
    p = params["params"]
    trunk = jnp.asarray(np.random.default_rng(7).normal(size=(2, 40, 32)), jnp.float32)
    nxt = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 32)), jnp.float32)
    module = jax.jit(lambda a, b: MTPModule(CFG).apply({"params": p["mtp"]}, a, b)[0])
    full = module(trunk, nxt)
    other_filler = module(trunk.at[:, -1].set(9.0), nxt.at[:, -1].set(-9.0))
    sliced = module(trunk[:, :-1], nxt[:, :-1])
    np.testing.assert_allclose(np.asarray(full[:, :-1]), np.asarray(other_filler[:, :-1]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(full[:, :-1]), np.asarray(sliced), atol=3e-6)
    assert float(jnp.max(jnp.abs(full[:, -1] - other_filler[:, -1]))) > 1e-3
    # and the loss's module term is blind to the filler: the last token moved, the places 0..T-3 answer as before
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % CFG.vocab_size)
    _, mtp_logits, _ = heads
    _, mtp_moved, _ = jax.jit(JoyAIFlash(CFG).apply)(params, moved)
    np.testing.assert_allclose(np.asarray(mtp_logits[:, :-2]), np.asarray(mtp_moved[:, :-2]), atol=1e-6)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(params):
    """What each of four chips computes for its two experts (``expert_offset``
    0, 2, 4, 6), plus what they all compute alike (the shared expert) counted
    once, is the uncut reference's expert FFN; and a share through the model,
    the module's expert layer among them, is the reference given that share."""
    p = params["params"]["mtp"]["block"]["mlp"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(96, 32)), jnp.float32)
    keys = joyai_flash_ref.router_keys(file_config())
    whole = trinity_ref.sparse_ffn(x, p, keys, PROD)
    shared = p["shared_experts"]
    total = trinity_ref.gated_mlp(
        x, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"], shared["down_proj"]["kernel"], PROD
    )
    ids, weights = trinity_ref.route(x, p, keys, PROD)
    given = 0
    for offset in range(0, 8, 2):
        stacked = {k: p[f"experts_{k}"][offset:offset + 2] for k in ("w1", "w3", "w2")}
        part, sizes = routed_experts(
            x, ids, weights, stacked, offset=offset, num_experts=8, act=jax.nn.silu, dtype=jnp.float32
        )
        total, given = total + part, given + int(sizes.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-6)
    assert given == 96 * 2
    held = dataclasses.replace(CFG, experts_held=2, expert_offset=4)
    cut = jax.tree_util.tree_map(lambda a: a, params)
    layers = [cut["params"]["layers_1"], cut["params"]["layers_2"], cut["params"]["mtp"]["block"]]
    for layer in layers:
        for k in ("experts_w1", "experts_w3", "experts_w2"):
            layer["mlp"][k] = layer["mlp"][k][4:6]
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 40)), jnp.int32)
    logits, mtp_logits, sizes = jax.jit(JoyAIFlash(held).apply)(cut, toks)
    want, want_mtp = jax.jit(
        lambda p, row: joyai_flash_ref.logits_fn(p, row, file_config(held, expert_offset=4))
    )(cut, toks[0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want), atol=5e-6)
    np.testing.assert_allclose(np.asarray(mtp_logits[0, :-1]), np.asarray(want_mtp), atol=5e-6)
    assert sizes.shape == (3, 2)


def test_the_config_reads_config_json_and_refuses_what_it_does_not_implement():
    import json
    from pathlib import Path

    body = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/joyai-flash-ep16.json").read_text())
    cfg = JoyAIFlashConfig.from_config(body, experts_held=body["num_experts_held"])
    assert (cfg.num_hidden_layers, cfg.expert_layers, cfg.held, cfg.num_experts, cfg.vocab_size) == (5, 5, 16, 256, 16160)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.intermediate_size, cfg.moe_intermediate_size) == (
        2048, 1536, 512, 7168, 768
    )
    assert (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.rope_theta) == (192, 128, 32000000)
    assert (cfg.route_scale, cfg.route_norm, cfg.num_experts_per_tok, cfg.mla_use_nope) == (2.5, True, 8, False)
    assert JoyAIFlashConfig().expert_layers == 40 and JoyAIFlashConfig().kinds == ("mla",) * 40
    for refused in (
        dict(n_group=2), dict(tie_word_embeddings=True), dict(rope_scaling={"type": "yarn", "factor": 4.0}),
        dict(num_nextn_predict_layers=2), dict(num_nextn_predict_layers=0), dict(rope_interleave=False),
        dict(scoring_func="softmax"), dict(attention_bias=True),
    ):
        with pytest.raises(ValueError, match="published joyai_llm_flash settings"):
            JoyAIFlashConfig.tiny(**refused)
    with pytest.raises(ValueError, match="experts"):
        JoyAIFlashConfig.tiny(experts_held=4, expert_offset=6)
    with pytest.raises(ValueError, match="remat"):
        JoyAIFlashConfig.tiny(remat="some")


# --- the models that were here lower to what they lowered to ---------------------

#: sha256 of the lowered loss-and-gradient step at ca7fb08 (the parent of PR 34), by ``lowered_digest`` below;
#: ``kimi-linear-dense`` as PR 43's own tree lowers it, which means to alter it (q's and k's ``l2norm`` rides the
#: short convolution's two kernels; PR 42 had replaced it for those kernels, PR 40 for the flat layout); the latent
#: layers alone still lower to ca7fb08's text.  **Every digest below that holds a flash kernel (all but Trinity's two,
#: whose tiny configuration runs XLA's attention) is PR 47's own tree's, which means to alter them**: lse and delta cross
#: the three kernels' boundary as rows ``[B.H, 1, T]``; with PR 47's ``ops/flash_attention.py`` put back to its parent's
#: (fd18a10) every one reads what it read, so nothing else in the steps moved
_PARENT_LOWERED = {
    "trinity-dense": "37a8171073a0b4a3dacc27e8b8545e81591e23a904e1e308408aa025b260041e",
    "trinity-chunked": "a932872d3198be9fdbb2f0ba03f5d0f9a185c0419a89343cf30e19254da6bb44",
    "kimi-linear-dense": "381407bc9ef371da2597e551469826792547c71b39cedc5b31f19d946d7c9d40",
    "kimi-linear-latent-layers-chunked": "c190f8562dbb1058f1b43073c6a1e7de844e79a0ee06d6bbd343d073dd7d571f",
}


#: the same for the three models whose ``stateful_loss`` had nothing holding it, through each model's own
#: ``stateful_loss`` and first ``model_state`` at its ``Config.tiny()``: made on 41e404e (the parent of PR 44, which
#: moved the loss's dense/chunked fork into ``models/lm.py``), before any model file was touched
_LOWERED_AT_41E404E = {
    "joyai-flash-dense": "6efa895b0e5b2e1d4c020a81c005c45b8810c539fc764cf04376aca294700191",
    "joyai-flash-chunked": "0c3010442611fb40d7560db9a8c55f6fca04a0c4e09b496a20b7e4efa8159d32",
    "granite-hybrid-dense": "5038e2e12b56794bd9b15bac4ed94906b27919e64cf88f0c1512f47fe46788e2",
    "granite-hybrid-chunked": "9f4fdd14cbe334ad6899abd63bca33f663af055e095c1e350c6db1fb27425184",
    "phi4-flash-dense": "26e9609176ad57d1398e97aa9a7e574df3258eb31faf901f5932b03efe934dc2",
    "phi4-flash-chunked": "7b46afdc92228f253ec57f392b187720354bef433e295c210aa764a04be2d09c",
}


def lowered_digest(model, loss, stateful_loss, first_state):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    step = jax.jit(jax.value_and_grad(stateful_loss(model, loss, block=64), has_aux=True))
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = step.lower(params, first_state, jax.ShapeDtypeStruct((2, 40), jnp.int32)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("case", sorted({**_PARENT_LOWERED, **_LOWERED_AT_41E404E}))
def test_kimi_linear_and_trinity_lower_to_the_parents_text(case):
    """PR 34 gave the latent mixer a query rank and a rotation, widened the
    expert models' loop (``train_lm.train`` since PR 44) and added a model
    with a loss of its own: ``Trinity``'s and ``KimiLinear``'s
    loss-and-gradient steps (tiny sizes, ``trinity.stateful_loss``, both forms
    of the loss) lower to the parent's text, character for character.  PR 44
    moved the loss's fork and the modules the models share into
    ``models/lm.py``: JoyAI's, Granite's and Phi-4-flash's steps, each through
    its own ``stateful_loss`` and first ``model_state``, lower to what they
    lowered to before it.  A change that means to alter them replaces the
    digest and says so."""
    from adapcc_tpu.models import granite_hybrid, phi4_flash

    def routed(model):
        return model, trinity.stateful_loss, trinity.initial_model_state(model.cfg)

    latent_alone = KimiLinearConfig.tiny(num_hidden_layers=2, kda_layers=(), full_attn_layers=(1, 2))
    name, loss = case.rsplit("-", 1)
    model, loss_of, first_state = {
        "trinity": routed(trinity.Trinity(trinity.TrinityConfig.tiny())),
        "kimi-linear": routed(KimiLinear(KimiLinearConfig.tiny())),
        "kimi-linear-latent-layers": routed(KimiLinear(latent_alone)),
        "joyai-flash": (JoyAIFlash(CFG), stateful_loss, initial_model_state(CFG)),
        "granite-hybrid": (
            granite_hybrid.GraniteHybrid(granite_hybrid.GraniteHybridConfig.tiny()), granite_hybrid.stateful_loss,
            granite_hybrid.initial_model_state(),
        ),
        "phi4-flash": (phi4_flash.Phi4Flash(phi4_flash.Phi4FlashConfig.tiny()), phi4_flash.stateful_loss, {}),
    }[name]
    assert lowered_digest(model, loss, loss_of, first_state) == {**_PARENT_LOWERED, **_LOWERED_AT_41E404E}[case]


# --- the workload ------------------------------------------------------------------


def test_the_workload_trains_through_ddptrainer_and_hands_out_both_terms_and_the_counts(capsys):
    from adapcc_tpu.workloads.train_joyai_flash import build_parser, run

    report = {}
    first, last = run(build_parser().parse_args(
        ["--epochs", "3", "--world", "2", "--experts-held", "4", "--expert-offset", "2"]
    ), report)
    assert last < first - 0.5, (first, last)
    out = capsys.readouterr().out
    assert "joyai_flash:" in out and "experts 2..6 of 8 held" in out and "assignments here" in out
    state = report["state"].model_state
    sizes = np.asarray(state["moe_sizes"])
    assert sizes.shape == (3, 4) and sizes.sum() > 0          # two expert layers of the trunk, the module's
    assert 0 < float(state["loss_main"]) < float(state["loss_mtp"])      # two tokens on is the harder guess
    assert report["trainer"].donate_state is True


def test_a_steps_terms_and_counts_become_the_programs_samples():
    metrics = MetricsRegistry()
    for step in range(3):
        record_step(
            {"moe_sizes": np.array([[3, 1], [0, 0], [2, 2]]), "loss_main": 5.0 - step, "loss_mtp": np.float32(6.0)},
            metrics,
        )
    samples = metrics.snapshot()["samples"]
    assert (samples["lm.loss_main"]["count"], samples["lm.loss_main"]["mean"]) == (3, 4.0)
    assert samples["lm.loss_mtp"]["mean"] == 6.0
    assert samples["moe.assignments_here"]["count"] == 9 and samples["moe.load_max_over_mean"]["count"] == 6
