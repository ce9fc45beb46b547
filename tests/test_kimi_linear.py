"""Kimi-Linear's block (``adapcc_tpu/models/kimi_linear.py``) and its KDA
kernel (``adapcc_tpu/ops/kda.py``) at a small size on the CPU, the kernels in
the Pallas interpreter.

The chunked scan against the recurrence a step at a time
(``chipbench/reference/kimi_linear_ref.kda_recurrence``), forward and all five
gradients, at lengths that are no whole number of chunks, with decays from
almost none to almost all, over one to three heads of two rows; the latent mixer against the head-at-a-time form;
the model's logits, loss and every gradient leaf against the plain reference
on seeded weights; the shares' routed parts plus the shared expert once add up
to the uncut layer; the workload trains through ``DDPTrainer.step``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.kimi_linear import KDAMixer, KimiLinear, KimiLinearConfig, MLAMixer, l2norm, o_norm
from adapcc_tpu.models.moe import routed_experts
from adapcc_tpu.models.trinity import initial_model_state, stateful_loss
from adapcc_tpu.ops.kda import chunk_plan, kda
from adapcc_tpu.ops.short_conv import short_conv
from adapcc_tpu.utils.observability import default_registry
from chipbench import weights_hybrid_lm
from chipbench.reference import kimi_linear_ref, trinity_ref

CFG = KimiLinearConfig.tiny()
PROD = kimi_linear_ref._product("float32")


def file_config(cfg: KimiLinearConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's file states it (``config.json`` keys)."""
    flat = ("kda_layers", "full_attn_layers", "linear_attn_num_heads", "linear_attn_head_dim", "short_conv_kernel_size")
    out = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if f.name not in flat + ("dtype", "remat", "experts_held")
    }
    out["linear_attn_config"] = {
        "kda_layers": list(cfg.kda_layers), "full_attn_layers": list(cfg.full_attn_layers),
        "num_heads": cfg.linear_attn_num_heads, "head_dim": cfg.linear_attn_head_dim,
        "short_conv_kernel_size": cfg.short_conv_kernel_size,
    }
    out.update(num_experts_held=cfg.held)
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    return weights_hybrid_lm.make_params(5, file_config())


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)


@pytest.fixture(scope="module")
def reference(params, tokens):
    """The plain reference's ``(loss, grads)`` on the module's weights and
    tokens, once a module: both forms of the model's loss are held to the
    same numbers."""
    return jax.jit(lambda p, t: kimi_linear_ref.loss_and_grads(p, t, file_config()))(params, tokens)


# --- the kernel --------------------------------------------------------------


def heads(x, H):
    """The flat ``[B, T, H d]`` as ``[B, T, H, d]``: how the oracles see a head."""
    return x.reshape(*x.shape[:2], H, -1)


def flat(x):
    return x.reshape(*x.shape[:2], -1)


def scan_inputs(T, seed, decay, B=1, H=2, dk=16, dv=8):
    """The scan's five arguments as the mixer hands them over, a head a block
    of channels of ``[B, T, H d]``, and a cotangent for its output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (flat(kimi_linear_ref.l2norm(jax.random.normal(key, (B, T, H, dk)))) for key in ks[:2])
    v = jax.random.normal(ks[2], (B, T, H * dv))
    lo, hi = {"near-one": (1e-5, 1e-3), "near-zero": (5.0, 40.0), "every-rate": (1e-4, 30.0)}[decay]
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H * dk), minval=math.log(lo), maxval=math.log(hi)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, T, H * dv))


def recurrence(q, k, v, g, beta, scale):
    """The recurrence a step at a time over flat arrays: the reference takes a row's ``[T, H, d]``."""
    H = beta.shape[-1]
    rows = [
        kimi_linear_ref.kda_recurrence(heads(q, H)[b], heads(k, H)[b], heads(v, H)[b], heads(g, H)[b], beta[b], scale, PROD)
        for b in range(q.shape[0])
    ]
    return flat(jnp.stack(rows))


_LENGTHS = {"under-a-chunk": 40, "a-chunk-and-a-part": 100, "four-chunks-less-a-part": 200}
# (T, decay, B, H, the one head whose output is weighed or None for all): a row and two heads at every length and
# decay; then two rows of one, two and three heads, so that a head or a row picked by the wrong index reads another's
# numbers, at lengths that need the pad (100, 200); then the last head's output alone, so that what g and beta get back
# for a head other than the first is held to that head's column and the other columns to zero
SCANS = {
    **{f"{length}-{decay}": (T, decay, 1, 2, None)
       for length, T in _LENGTHS.items() for decay in ("near-one", "near-zero", "every-rate")},
    "two-rows-of-one-head": (100, "every-rate", 2, 1, None),
    "two-rows-of-two-heads": (200, "every-rate", 2, 2, None),
    "two-rows-of-three-heads": (100, "every-rate", 2, 3, None),
    "the-last-of-three-heads-alone": (100, "near-one", 2, 3, 2),
}


@pytest.mark.parametrize("case", list(SCANS))
def test_the_chunked_scan_is_the_recurrence_forward_and_in_all_five_gradients(case):
    T, decay, B, H, only = SCANS[case]
    args, mix = scan_inputs(T, T, decay, B=B, H=H)
    if only is not None:
        mix = flat(heads(mix, H) * (jnp.arange(H) == only)[None, None, :, None])
    scale = 0.25
    got = kda(*args, scale=scale)
    want = recurrence(*args, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    grads = jax.grad(lambda *a: jnp.sum(kda(*a, scale=scale) * mix), argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: jnp.sum(recurrence(*a, scale) * mix), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, wants):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, err_msg=f"d{name}")
    if only is not None:
        dg, dbeta = np.asarray(heads(grads[3], H)), np.asarray(grads[4])
        assert np.abs(dg[:, :, only]).max() > 1e-3 and np.abs(dbeta[:, :, only]).max() > 1e-3
        assert not dg[:, :, :only].any() and not dbeta[:, :, :only].any()


def test_the_chunk_follows_the_shape_and_the_scan_leaves_its_gauges():
    assert chunk_plan(8192) == (64, 4, 8192)          # the cell: 128 chunks, four of each head to a grid step
    assert chunk_plan(200) == (64, 4, 256) and chunk_plan(100) == (64, 2, 128) and chunk_plan(40) == (40, 1, 40)
    assert chunk_plan(64 * 6) == (64, 2, 384) and chunk_plan(64 * 7) == (64, 1, 448)
    args, _ = scan_inputs(100, 0, "every-rate")
    kda(*args)
    gauges = default_registry().snapshot()["gauges"]
    assert (gauges["kda.chunk"], gauges["kda.tiles"]) == (64, 2 * 2)
    assert gauges["kda.padded_rows"] == 128 - 100        # the one copy left: the pad along T, where T is no whole chunks
    kda(*scan_inputs(40, 0, "every-rate")[0])
    assert default_registry().snapshot()["gauges"]["kda.padded_rows"] == 0
    with pytest.raises(ValueError, match="kda shapes"):
        kda(args[0], args[1], args[2], args[3][..., :4], args[4])
    with pytest.raises(ValueError, match="kda shapes"):
        kda(*args[:4], args[4][..., :1].repeat(3, axis=-1))      # three heads do not divide q's 32 channels, nor v's 16
    with pytest.raises(ValueError, match="kda shapes"):
        kda(*(heads(x, 2) for x in args[:4]), args[4])           # the 4-D arrays the scan took before: no longer


def test_a_head_size_that_is_no_whole_lane_tiles_is_refused_through_mosaic_and_taken_by_the_interpreter():
    """The kernels read a head as a lane block of the model's ``[B, T, H d]``:
    through Mosaic a head is whole 128-lane tiles, and the scan says so,
    naming the shapes, before any kernel is built.  Heads need not come in
    pairs any more, whatever the dtype.  The interpreter takes any head
    size."""
    args, _ = scan_inputs(40, 1, "every-rate")                      # d_k 16, d_v 8
    with pytest.raises(ValueError, match=r"multiples of 128 .*q \(1, 40, 32\).*v \(1, 40, 16\)"):
        kda(*args, interpret=False)
    wide_v = (args[0], args[1], jnp.zeros((1, 40, 2 * 128)), args[3], args[4])
    with pytest.raises(ValueError, match=r"d_k 16 and d_v 128 must be multiples of 128"):
        kda(*wide_v, interpret=False)                               # d_v alone a whole tile: d_k still is not
    wide_k, _ = scan_inputs(40, 1, "every-rate", dk=128, dv=8)
    with pytest.raises(ValueError, match=r"d_k 128 and d_v 8 must be multiples of 128"):
        kda(*wide_k, interpret=False)
    np.testing.assert_allclose(
        np.asarray(kda(*args, scale=0.25, interpret=True)), np.asarray(recurrence(*args, 0.25)), atol=1e-4
    )


def test_a_batch_of_rows_scans_each_from_a_zero_state():
    args, _ = scan_inputs(70, 3, "every-rate", B=2)
    both = kda(*args)
    for b in range(2):
        alone = kda(*(x[b:b + 1] for x in args))
        np.testing.assert_allclose(np.asarray(both[b:b + 1]), np.asarray(alone), atol=1e-6)


# --- the two mixers ----------------------------------------------------------


@pytest.mark.parametrize("T", [40, 256])
def test_the_latent_mixer_is_the_head_at_a_time_form(params, T):
    """Scores over 16 + 8 channels (8 of them the same for every head), values
    over 16; a row inside one tile of the kernel and a row over two."""
    p = params["params"]["layers_2"]["self_attn"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T, 32)), jnp.float32)
    got = MLAMixer(CFG).apply({"params": p}, x)
    want = jax.jit(lambda p, x: jnp.stack([kimi_linear_ref.mla_mixer(row, p, file_config(), PROD) for row in x]))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("H", [2, 4, 32])
@pytest.mark.parametrize("norm", ["l2norm", "o_norm"])
def test_the_flat_norms_are_the_per_head_norms_they_replace(norm, H):
    """``l2norm`` and ``o_norm`` on ``[B, T, H D]`` against the forms over the
    last axis of ``[B, T, H, D]`` that stood in the mixer (the reference's
    own ``l2norm`` and ``rms_norm``), float32 at 1e-6: values, the input's
    gradient and the weight's."""
    D = 16
    ks = jax.random.split(jax.random.PRNGKey(H), 3)
    x, mix = jax.random.normal(ks[0], (2, 9, H * D)) * 3.0, jax.random.normal(ks[1], (2, 9, H * D))
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (D,))
    if norm == "l2norm":
        got, want = (lambda x, w: l2norm(x, H)), (lambda x, w: flat(kimi_linear_ref.l2norm(heads(x, H))))
    else:
        got, want = (lambda x, w: o_norm(x, w, H, 1e-5)), (lambda x, w: flat(trinity_ref.rms_norm(heads(x, H), w, 1e-5)))
    np.testing.assert_allclose(np.asarray(got(x, scale)), np.asarray(want(x, scale)), atol=1e-6)
    grads = jax.grad(lambda x, w: jnp.sum(got(x, w) * mix), argnums=(0, 1))(x, scale)
    wants = jax.grad(lambda x, w: jnp.sum(want(x, w) * mix), argnums=(0, 1))(x, scale)
    np.testing.assert_allclose(np.asarray(grads[0]), np.asarray(wants[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(grads[1]), np.asarray(wants[1]), atol=1e-5 if norm == "o_norm" else 0)
    assert got(x.astype(jnp.bfloat16), scale).dtype == jnp.bfloat16


def test_a_flat_norm_keeps_its_input_and_a_statistic_a_head_for_the_backward_pass():
    """What a norm holds from the forward pass to the backward one is held for
    the whole step (the cell recomputes nothing): the input as it came and
    ``[B, T, H]`` float32, nothing ``H D`` wide in float32."""
    x = jnp.ones((1, 8, 4 * 16), jnp.bfloat16)
    for fn in (lambda x: l2norm(x, 4), lambda x: o_norm(x, jnp.ones((16,)), 4, 1e-5)):
        _, pull = jax.vjp(fn, x)
        kept = [leaf for leaf in jax.tree_util.tree_leaves(pull) if hasattr(leaf, "shape") and leaf.size >= x.size]
        assert [(leaf.shape, leaf.dtype) for leaf in kept] == [(x.shape, jnp.bfloat16)]


#: ``KDAMixer``'s parameter tree at the tiny configuration as the parent commit made it (names, shapes):
#: ``chipbench/weights_hybrid_lm.py`` and the plain reference read it
PARENT_KDA_TREE = {
    "A_log": (2,), "dt_bias": (32,), "o_norm": {"scale": (16,)},
    "q_conv": (4, 32), "k_conv": (4, 32), "v_conv": (4, 32),
    "q_proj": {"kernel": (32, 32)}, "k_proj": {"kernel": (32, 32)}, "v_proj": {"kernel": (32, 32)},
    "f_a_proj": {"kernel": (32, 16)}, "f_b_proj": {"kernel": (16, 32)}, "b_proj": {"kernel": (32, 2)},
    "g_a_proj": {"kernel": (32, 16)}, "g_b_proj": {"kernel": (16, 32)}, "o_proj": {"kernel": (32, 32)},
}


def test_the_parents_parameter_tree_initialises_and_loads_into_the_mixer_unchanged(params):
    made = KDAMixer(CFG).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))["params"]
    assert jax.tree_util.tree_map(lambda a: a.shape, made) == PARENT_KDA_TREE
    np.testing.assert_array_equal(np.asarray(made["o_norm"]["scale"]), np.ones(16))
    loaded = jax.tree_util.tree_map(
        lambda shape: jnp.full(shape, 0.01), PARENT_KDA_TREE, is_leaf=lambda node: isinstance(node, tuple)
    )
    assert KDAMixer(CFG).apply({"params": loaded}, jnp.ones((1, 8, 32))).shape == (1, 8, 32)
    for layer in ("layers_0", "layers_1", "layers_3"):        # and the weight maker's tree is that tree
        assert jax.tree_util.tree_map(lambda a: a.shape, params["params"][layer]["self_attn"]) == PARENT_KDA_TREE


@pytest.mark.parametrize("T", [40, 100])
def test_the_kda_mixer_is_the_reference_a_step_at_a_time_in_value_and_every_gradient(params, T):
    """The mixer on flat arrays (projections, the convolutions' kernels with
    q's and k's norm inside them, the flat decay, the scan through the
    interpreter, the flat ``o_norm``, the gate) against
    the reference that reshapes to heads and runs the recurrence a step at a
    time: the output, the input's gradient and every parameter's."""
    p = params["params"]["layers_1"]["self_attn"]
    x = jnp.asarray(np.random.default_rng(T).normal(size=(2, T, 32)), jnp.float32)
    mix = jnp.asarray(np.random.default_rng(T + 1).normal(size=(2, T, 32)), jnp.float32)
    ours = lambda p, x: KDAMixer(CFG).apply({"params": p}, x)                                                # noqa: E731
    theirs = lambda p, x: jnp.stack([kimi_linear_ref.kda_mixer(row, p, file_config(), PROD) for row in x])  # noqa: E731
    np.testing.assert_allclose(np.asarray(ours(p, x)), np.asarray(jax.jit(theirs)(p, x)), atol=2e-6)
    got = jax.grad(lambda p, x: jnp.sum(ours(p, x) * mix), argnums=(0, 1))(p, x)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(theirs(p, x) * mix), argnums=(0, 1)))(p, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6 + 5e-4 * float(jnp.max(jnp.abs(b))), err_msg=jax.tree_util.keystr(path)
        )


def test_the_short_convolution_is_causal_and_depthwise():
    """The mixer's convolution is :func:`ops.short_conv.short_conv`, the silu
    inside it: against the sum written out a step at a time and against the
    reference's own convolution, each under the silu."""
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 9, 3)), jnp.float32)
    taps = jnp.asarray(np.random.default_rng(5).normal(size=(4, 3)), jnp.float32)
    y = np.asarray(short_conv(x, taps))
    for t in range(9):
        want = sum(np.asarray(taps)[j] * np.asarray(x)[0, t - 3 + j] for j in range(4) if t - 3 + j >= 0)
        np.testing.assert_allclose(y[0, t], want / (1.0 + np.exp(-want)), atol=1e-6)
    np.testing.assert_allclose(y[0], np.asarray(jax.nn.silu(kimi_linear_ref.short_conv(x[0], taps))), atol=1e-6)


# --- the model ---------------------------------------------------------------


def test_the_weight_maker_makes_the_tree_the_model_reads(params):
    shapes = jax.eval_shape(KimiLinear(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    assert CFG.kinds == ("kda", "kda", "mla", "kda") == weights_hybrid_lm.layer_kinds(file_config())
    scan = params["params"]["layers_0"]["self_attn"]
    rate = np.repeat(np.exp(np.asarray(scan["A_log"])), 16) * np.asarray(jax.nn.softplus(scan["dt_bias"]))
    assert 0.15 < np.exp(-rate).min() and np.exp(-rate).max() < 0.9995     # a step forgets neither all nor nothing
    assert np.abs(np.asarray(scan["q_conv"])).max() <= 0.5


def test_logits_match_the_plain_reference(params, tokens):
    logits, sizes = KimiLinear(CFG).apply(params, tokens)
    want = jax.jit(
        lambda p, t: jnp.stack([kimi_linear_ref.logits_fn(p, row, file_config()) for row in t])
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=5e-6)
    assert sizes.shape == (3, 8) and sizes.sum(axis=1).tolist() == [2 * 40 * 2] * 3


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(params, tokens, reference, loss):
    model = KimiLinear(CFG)
    (value, state), grads = jax.value_and_grad(stateful_loss(model, loss, block=64), has_aux=True)(
        params, initial_model_state(CFG), tokens
    )
    want, want_grads = reference
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert state["moe_sizes"].shape == (3, 8)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6 + 5e-4 * scale, err_msg=jax.tree_util.keystr(path)
        )
    assert not np.any(np.asarray(grads["params"]["layers_1"]["mlp"]["expert_bias"]))


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(params):
    """What each of four chips computes for its two experts (``expert_offset``
    0, 2, 4, 6), plus what they all compute alike (the shared expert) counted
    once, is the uncut reference's expert FFN; and a share through the model
    is the reference given that share."""
    p = params["params"]["layers_1"]["mlp"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(96, 32)), jnp.float32)
    keys = kimi_linear_ref.router_keys(file_config())
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
    for name in ("layers_1", "layers_2", "layers_3"):
        for k in ("experts_w1", "experts_w3", "experts_w2"):
            cut["params"][name]["mlp"][k] = params["params"][name]["mlp"][k][4:6]
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 40)), jnp.int32)
    logits, sizes = KimiLinear(held).apply(cut, toks)
    want = jax.jit(lambda p, row: kimi_linear_ref.logits_fn(p, row, file_config(held, expert_offset=4)))(cut, toks[0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want), atol=5e-6)
    assert sizes.shape == (3, 2)


def test_the_config_reads_config_json_and_refuses_what_it_does_not_implement():
    import json
    from pathlib import Path

    body = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/kimi-linear-ep32.json").read_text())
    cfg = KimiLinearConfig.from_config(body, experts_held=body["num_experts_held"])
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda") and (cfg.held, cfg.num_experts) == (8, 256)
    assert (cfg.hidden_size, cfg.linear_attn_head_dim, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) == (2304, 128, 192)
    assert KimiLinearConfig().kinds.count("mla") == 7 and KimiLinearConfig().kinds[3] == "mla"
    with pytest.raises(ValueError, match="sigmoid"):
        KimiLinearConfig.tiny(moe_router_activation_func="softmax")
    with pytest.raises(ValueError, match="exactly one"):
        KimiLinearConfig.tiny(num_hidden_layers=5)
    with pytest.raises(ValueError, match="experts"):
        KimiLinearConfig.tiny(experts_held=4, expert_offset=6)


def test_the_workload_trains_through_ddptrainer_and_hands_the_counts_out(capsys):
    from adapcc_tpu.workloads.train_kimi_linear import build_parser, run

    report = {}
    first, last = run(build_parser().parse_args(
        ["--epochs", "3", "--world", "2", "--experts-held", "4", "--expert-offset", "2"]
    ), report)
    assert last < first - 0.5, (first, last)
    out = capsys.readouterr().out
    assert "experts 2..6 of 8 held" in out and "'mla'" in out and "assignments here" in out
    sizes = np.asarray(report["state"].model_state["moe_sizes"])
    assert sizes.shape == (3, 4) and sizes.sum() > 0
    assert report["trainer"].donate_state is True
