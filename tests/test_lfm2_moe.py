"""LFM2-24B-A2B's block (``adapcc_tpu/models/lfm2_moe.py``) and the gated form
of the short convolution (``adapcc_tpu/ops/short_conv.gated_short_conv``) at a
small size on the CPU, the kernels in the Pallas interpreter.

The gated convolution against three shifted products, forward and in all four
gradients (the ``B``, ``C`` and ``x`` thirds and the taps), at rows that fill
their blocks and rows that do not, channels that are whole lane tiles and
channels that are not, a batch of rows each from zeros; the mixer and the whole
model against the plain reference on seeded weights (logits, loss, first
gradient by leaf, three AdamW steps); bfloat16 in the reference's place fails;
each fault of the reference is another function; the four shares of the
experts add up to the uncut layer; the expert layer's rows at the cell's
numbers by hand; the configuration reads the catalog's row and refuses what is
not implemented; the workload trains through ``DDPTrainer.step``; the accepted
convolution kernels trace to what they traced to.

One module-scoped fixture holds the weights, the tokens, the reference's loss
and gradients and the program's: every comparison reads them (PERF.md section
7 item 27: a result computed once a module).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models import moe
from adapcc_tpu.models.lfm2_moe import (
    Lfm2Moe, Lfm2MoeConfig, ShortConvMixer, SparseExperts, initial_model_state, stateful_loss,
)
from adapcc_tpu.ops import short_conv as sc
from adapcc_tpu.ops.short_conv import gated_short_conv, plan_for
from adapcc_tpu.utils.observability import default_registry
from chipbench import correct, weights_lfm2_lm
from chipbench.reference import lfm2_moe_ref
from chipbench.reference.gpt2_ref import leaf_norms

# every kind of layer once: a convolution over the dense MLP, attention over the experts, a convolution over the experts
CFG = Lfm2MoeConfig.tiny(layers_held=(1, 2, 3))
PROD = lfm2_moe_ref._product("float32")
OPT = {"clip_norm": 1.0, "learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
ROOT = Path(__file__).resolve().parents[1]


def file_config(cfg: Lfm2MoeConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's file states it (``config.json``
    keys, the published depth beside the layers held)."""
    keys = (
        "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "conv_L_cache", "num_experts", "num_experts_per_tok", "norm_eps", "norm_topk_prob",
        "routed_scaling_factor", "use_expert_bias", "expert_offset",
    )
    out = {k: getattr(cfg, k) for k in keys}
    out.update(
        layer_types=list(cfg.layer_types), layers_held=list(cfg.held_layers), num_experts_held=cfg.held,
        num_hidden_layers=len(cfg.held_layers), rope_parameters={"rope_theta": cfg.rope_theta, "rope_type": cfg.rope_type},
        published={"num_hidden_layers": cfg.num_hidden_layers, "num_dense_layers": cfg.num_dense_layers},
        assumed={"head_dim": cfg.head_size},
    )
    out.update(over)
    return out


def sharp(params):
    """The seeded weights with every projection *into* a mixer or the router
    eight times as large: at 32 channels a normed row times a normal(0, 0.02)
    matrix is 0.1 where at the published 2,048 it is 0.9, so the gates, the
    scores and the router's logits would all but vanish and no piece of the
    mathematics could be told from another."""
    wide = ("in_proj", "q_proj", "k_proj", "v_proj")

    def scaled(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        return 8.0 * leaf if "router" in names or any(w in names for w in wide) else leaf

    return jax.tree_util.tree_map_with_path(scaled, params)


@pytest.fixture(scope="module")
def world():
    """Weights by the seed, tokens, and both sides' loss and gradients on them."""
    params = sharp(weights_lfm2_lm.make_params(5, file_config()))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)
    faulted = jax.jit(lambda p, t, knob: lfm2_moe_ref.loss_and_grads(p, t, file_config(), "float32", knob))
    reference = faulted(params, tokens, lfm2_moe_ref.knobs(file_config()))
    grad_fn = {
        loss: jax.jit(jax.value_and_grad(stateful_loss(Lfm2Moe(CFG), loss, block=64), has_aux=True))
        for loss in ("dense", "chunked")
    }
    program = {loss: fn(params, initial_model_state(CFG), tokens) for loss, fn in grad_fn.items()}
    return {
        "params": params, "tokens": tokens, "reference": reference, "program": program, "faulted": faulted,
        "grad_fn": grad_fn["dense"],
    }


# --- the kernels ---------------------------------------------------------------


def three_shifts(bcx, taps):
    """The oracle: ``C * sum_i taps[i] (B x)_{t-(K-1)+i}`` as ``K`` shifted
    products of a zero-padded float32 array, a row at a time."""
    B, C, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    K, T = taps.shape[0], bcx.shape[1]
    z = jnp.pad(B * x, ((0, 0), (K - 1, 0), (0, 0)))
    return C * sum(taps[i] * z[:, i:i + T] for i in range(K))


# (rows of the batch, T, channels a third, taps, longest block of rows or None for the program's own)
# the branches the walk takes: rows that fill their blocks or not, channels that are whole lane tiles or not
# (another tap count and a single block are ``short_conv``'s own tests': the walk is shared)
CONVS = {
    "two-blocks-two-lane-tiles-filled": (2, 64, 256, 3, 32),      # 256 lanes walked at once, as on the chip
    "two-blocks-and-no-lane-tile-filled": (2, 40, 96, 3, 32),
}


@pytest.mark.parametrize("case", list(CONVS))
def test_the_gated_convolution_is_three_shifted_products_forward_and_in_all_four_gradients(case):
    """Tolerance: float32 both sides; the kernel sums the taps in another
    order than the oracle and the taps' gradient over the rows in another:
    1e-5 of the largest value compared."""
    Bt, T, C, K, rows = CONVS[case]
    k = jax.random.split(jax.random.PRNGKey(T + C), 3)
    bcx, dy = jax.random.normal(k[0], (Bt, T, 3 * C)), jax.random.normal(k[1], (Bt, T, C))
    taps = jax.random.uniform(k[2], (K, C), jnp.float32, -0.5, 0.5)
    with pytest.MonkeyPatch.context() as patch:
        if rows is not None:
            patch.setattr(sc, "_ROWS", rows)     # the test steers the plan: the program has no option for it
            assert plan_for(T, C, K)[0].rows == rows and plan_for(T, C, K)[1] == 2 * rows
        got = gated_short_conv(bcx, taps)
        grads = jax.grad(lambda a, t: jnp.sum(gated_short_conv(a, t) * dy), argnums=(0, 1))(bcx, taps)
    want = three_shifts(bcx, taps)
    wants = jax.grad(lambda a, t: jnp.sum(three_shifts(a, t) * dy), argnums=(0, 1))(bcx, taps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5 * float(jnp.abs(want).max()))
    thirds = zip(("dB", "dC", "dx"), jnp.split(grads[0], 3, axis=-1), jnp.split(wants[0], 3, axis=-1))
    for name, a, b in [*thirds, ("dtaps", grads[1], wants[1])]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)


def test_a_batch_of_rows_starts_each_from_zeros_and_a_packed_join_resets_nothing():
    """Two rows of a batch know nothing of each other, and a row's first
    steps see zeros before it; two walks packed end to end into one row are
    one sequence: the second walk's first two outputs read the first walk's
    last inputs, as the three shifted products do."""
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    bcx = jax.random.normal(k[0], (2, 48, 3 * 128))
    taps = jax.random.uniform(k[1], (3, 128), jnp.float32, -0.5, 0.5)
    both = gated_short_conv(bcx, taps)
    for b in range(2):
        np.testing.assert_array_equal(np.asarray(both[b:b + 1]), np.asarray(gated_short_conv(bcx[b:b + 1], taps)))
    B, C, x = jnp.split(bcx, 3, axis=-1)
    np.testing.assert_allclose(np.asarray(both[:, 0]), np.asarray(C[:, 0] * taps[2] * B[:, 0] * x[:, 0]), atol=1e-6)
    packed = jnp.concatenate([bcx[0], bcx[1]])[None]
    joined = gated_short_conv(packed, taps)
    np.testing.assert_allclose(np.asarray(joined), np.asarray(three_shifts(packed, taps)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(joined[0, :48]), np.asarray(both[0]), atol=1e-6)
    assert float(jnp.abs(joined[0, 48:50] - both[1, :2]).max()) > 1e-3        # the first walk's last inputs are still there
    np.testing.assert_allclose(np.asarray(joined[0, 50:]), np.asarray(both[1, 2:]), atol=1e-6)


def test_the_gated_call_records_itself_and_refuses_what_is_no_three_thirds():
    metrics = default_registry()
    before = metrics.snapshot()["counters"].get("conv.gated_calls", 0)
    gated_short_conv(jnp.ones((1, 40, 3 * 256)), jnp.ones((3, 256)))
    snap = metrics.snapshot()
    assert snap["counters"]["conv.gated_calls"] == before + 1
    assert (snap["gauges"]["gconv.block_rows"], snap["gauges"]["gconv.lane_tiles"]) == (64, 2)
    plan, Tp, Cp = plan_for(8192, 2048, 3)          # the cell: eight blocks of 1,024 rows, eight lane tiles a grid step, no padding
    assert (plan.rows, plan.tiles, Tp, Cp, plan.biased, plan.head) == (1024, 8, 8192, 2048, False, None)
    for bcx, taps in (((1, 40, 200), (3, 100)), ((1, 40, 384), (3, 100)), ((1, 40, 384), (9, 128))):
        with pytest.raises(ValueError, match="gated_short_conv shapes"):
            gated_short_conv(jnp.ones(bcx), jnp.ones(taps))


def test_the_accepted_kernels_trace_to_what_they_traced_to():
    """``short_conv``'s two kernels know nothing of the gated form: their
    traced jaxprs at cell 6's width are the digest ``tests/test_short_conv.py``
    has held since PR 43 (the whole-size lowered text stands in
    ``tests/test_chip_compile.py``)."""
    from tests.test_short_conv import _PARENT_TRACED, test_without_a_norm_the_kernels_trace_to_what_they_traced_to as held

    held(next(shape for shape in _PARENT_TRACED if shape[1] == 4352))


# --- the mixer and the model -----------------------------------------------------


def test_the_convolution_mixer_is_the_references(world):
    p = world["params"]["params"]["layers_0"]["conv"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 40, 32)), jnp.float32)
    got = ShortConvMixer(CFG).apply({"params": p}, x)
    knob = lfm2_moe_ref.knobs(file_config())
    want = jnp.stack([lfm2_moe_ref.conv_mixer(row, p, file_config(), PROD, knob) for row in x])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_the_weight_maker_makes_the_tree_the_model_reads_and_the_layers_follow_the_published_indices(world):
    params = world["params"]
    shapes = jax.eval_shape(Lfm2Moe(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    assert CFG.kinds == ("conv", "full_attention", "conv") == weights_lfm2_lm.layer_kinds(file_config())
    assert CFG.sparse == (False, True, True) == tuple(s for _, s in weights_lfm2_lm.layer_plan(file_config()))
    whole = Lfm2MoeConfig.tiny()
    assert whole.kinds == ("conv", "full_attention", "conv", "conv", "conv") and whole.sparse == (False, True, True, True, True)
    tree = params["params"]
    assert "gate_proj" in tree["layers_0"]["feed_forward"] and "router" in tree["layers_1"]["feed_forward"]
    assert "q_layernorm" in tree["layers_1"]["self_attn"] and tree["layers_2"]["conv"]["conv_taps"].shape == (3, 32)
    assert "lm_head" not in tree and "shared_experts" not in tree["layers_1"]["feed_forward"]
    assert initial_model_state(CFG)["moe_sizes"].shape == (2, 8) and initial_model_state(whole)["moe_sizes"].shape == (4, 8)
    published = Lfm2MoeConfig()
    assert [i for i, k in enumerate(published.kinds) if k == "full_attention"] == list(range(2, 40, 4))
    assert published.sparse.count(False) == 2 and published.head_size == 64 and published.held == 64


def test_logits_match_the_plain_reference(world):
    logits, sizes = jax.jit(Lfm2Moe(CFG).apply)(world["params"], world["tokens"])
    want = jax.jit(lambda p, row: lfm2_moe_ref.logits_fn(p, row, file_config()))
    for got, row in zip(logits, world["tokens"]):           # the reference takes a row at a time: one program, twice
        np.testing.assert_allclose(np.asarray(got), np.asarray(want(world["params"], row)), atol=5e-6)
    assert sizes.shape == (2, 8) and np.asarray(sizes).sum(axis=1).tolist() == [2 * 80] * 2     # every expert held: top-2 of 80 tokens


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(world, loss):
    """Tolerance: float32 both sides, products at full precision; what is
    left is the order of summation (the kernels' walks, the flash kernel's
    online softmax, the grouped products, the fused loss): 5e-4 of a leaf's
    largest entry."""
    (value, state), grads = world["program"][loss]
    want, want_grads = world["reference"]
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert set(state) == {"moe_sizes"}
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6 + 5e-4 * scale, err_msg=jax.tree_util.keystr(path)
        )
    bias = grads["params"]["layers_1"]["feed_forward"]["expert_bias"]
    assert not np.asarray(bias).any()                     # a buffer no gradient reaches


def test_bfloat16_in_the_references_place_fails_the_comparison_the_program_passes(world):
    """The reference with every product's operands rounded to bfloat16 is not
    the reference: its first gradient is off by thousands of times what the
    program's is (2e-7 against 1.6e-3 here), leaf by leaf (``chipbench/correct.worst_leaf_gap``)."""
    want = np.asarray(leaf_norms(world["reference"][1]))
    rounded = jax.jit(lambda p, t: lfm2_moe_ref.loss_and_grads(p, t, file_config(), "bfloat16"))(
        world["params"], world["tokens"]
    )
    program = correct.worst_leaf_gap(np.asarray(leaf_norms(world["program"]["dense"][1])), want)
    control = correct.worst_leaf_gap(np.asarray(leaf_norms(rounded[1])), want)
    assert program < 1e-5 < 1e-3 < control, (program, control)


@pytest.mark.parametrize("fault", [f for f in lfm2_moe_ref.FAULTS if f])
def test_each_fault_of_the_reference_is_another_function(world, fault):
    """The ten controls ``correct`` has to fail are the reference with one
    piece changed, the same compiled program given other flags: each moves
    the norm of some leaf's first gradient by 2% or more of itself, where the
    program is off by 1e-6.  ``rope_before_norm`` is among them because the
    seeded per-head norm scales are not one (``weights_lfm2_lm.HEAD_NORM``):
    at one, a rotation before the norm is the rotation after it.  An untied
    head that starts equal to the embedding moves no logit; its gradient tells
    it apart."""
    cfg = file_config()
    want = np.asarray(leaf_norms(world["reference"][1]), np.float64)
    _, grads = world["faulted"](world["params"], world["tokens"], lfm2_moe_ref.knobs(cfg, fault))
    moved = np.abs(np.asarray(leaf_norms(grads), np.float64) - want) / np.maximum(want, 1e-30)
    sound = np.abs(np.asarray(leaf_norms(world["program"]["dense"][1]), np.float64) - want) / np.maximum(want, 1e-30)
    assert moved[want > 0].max() > 2e-2 > 1e-4 > sound[want > 0].max()
    with pytest.raises(ValueError, match="fault"):
        lfm2_moe_ref.knobs(cfg, "no_such_fault")


def test_three_adamw_steps_follow_the_plain_reference(world):
    """The program's own optimizer chain (optax, clipped AdamW) on the
    model's stateful loss against the reference's own AdamW, three steps on
    three batches: each loss, and every leaf's change."""
    import optax

    # batches of the fixture's shape: the loss and its gradient are the program the fixture compiled
    params, rows = world["params"], np.random.default_rng(7).integers(0, CFG.vocab_size, (3, 2, 40)).astype(np.int32)
    make = lambda: sharp(weights_lfm2_lm.make_params(5, file_config()))  # noqa: E731
    want = lfm2_moe_ref.train_steps(make(), rows, file_config(), OPT, make)
    tx = optax.chain(
        optax.clip_by_global_norm(OPT["clip_norm"]),
        optax.adamw(OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"], weight_decay=OPT["weight_decay"]),
    )

    @jax.jit
    def apply(p, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state

    p, opt_state, losses = params, tx.init(params), []
    for batch in rows:
        (loss, _), grads = world["grad_fn"](p, initial_model_state(CFG), jnp.asarray(batch))
        p, opt_state = apply(p, opt_state, grads)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, np.asarray(want["losses"]), rtol=2e-6)
    moved = np.asarray(leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, params)))
    np.testing.assert_allclose(moved, np.asarray(want["update_norms"]), rtol=2e-3, atol=1e-9)


# --- the share -------------------------------------------------------------------


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(world):
    """64 experts over four chips, scaled down: 8 experts, 2 a share at
    offsets 0, 2, 4, 6.  The router is every share's alike (its 8 outputs, its
    2 experts a token); a share adds its own experts' part and nothing stands
    in for the others.  There is no shared expert, so nothing is counted once:
    the four parts add up to the layer that holds all eight, and to the uncut
    reference's."""
    full = world["params"]["params"]["layers_1"]["feed_forward"]
    x = jnp.asarray(np.random.default_rng(11).normal(size=(1, 80, 32)), jnp.float32)
    whole, sizes = SparseExperts(CFG).apply({"params": full}, x)
    parts, counted = [], []
    for offset in (0, 2, 4, 6):
        cfg = dataclasses.replace(CFG, experts_held=2, expert_offset=offset)
        mine = {**full, **{k: full[k][offset:offset + 2] for k in ("experts_w1", "experts_w3", "experts_w2")}}
        part, given = SparseExperts(cfg).apply({"params": mine}, x)
        share = jnp.stack([
            lfm2_moe_ref.sparse_ffn(row, mine, file_config(cfg), PROD, lfm2_moe_ref.knobs(file_config())) for row in x
        ])
        np.testing.assert_allclose(np.asarray(part), np.asarray(share), atol=2e-6)      # the reference is given the same share
        parts.append(part)
        counted.append(np.asarray(given))
    uncut = jnp.stack([lfm2_moe_ref.sparse_ffn(row, full, file_config(), PROD, lfm2_moe_ref.knobs(file_config())) for row in x])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=2e-6)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=2e-6)
    assert np.concatenate(counted).tolist() == np.asarray(sizes).tolist() and int(np.sum(sizes)) == 2 * 80


def test_the_expert_layers_rows_at_the_cells_numbers_by_hand():
    """8,192 tokens, top-4, 16 of 64 held: a token picks four different
    experts, so at most four of its assignments land here: the bound is
    32,768 rows; on balance a quarter of the 32,768 assignments do, 8,192 (one
    a token), and the short rows are twice that, 16,384, in whole row tiles;
    each held expert then sees 512 rows where its deployment's load at 8,192
    tokens a chip would be 2,048."""
    assert moe.assignment_bound(8192, 4, 16) == 32768
    assert moe.short_rows(8192, 4, 16, 64) == 16384 == 2 * (8192 * 4 * 16 // 64)
    assert moe.short_rows(8192, 4, 16, None) == 32768 and moe.short_rows(8192, 4, 64, 64) == 32768
    assert 8192 * 4 * 16 // 64 // 16 == 512 and 4 * 8192 * 4 // 64 == 2048
    assert 16384 % moe.ROW_TILE == 0


# --- the configuration -------------------------------------------------------------


def test_the_configuration_reads_the_catalogs_row_and_refuses_what_is_not_implemented():
    from chipbench.runners.train_lfm2_lm import model_config

    config = json.loads((ROOT / "chipbench/configs/lfm2-24b-a2b-ep4.json").read_text())
    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if rows.is_file():
        row = next(json.loads(l) for l in rows.read_text().splitlines() if '"name": "LFM2-24B-A2B"' in l)
        differs = {k for k, v in row["config"].items() if config.get(k) != v}
        assert differs == {"num_hidden_layers", "num_dense_layers", "vocab_size"} and config["source"] == row["source_url"]
        assert set(config["reduced"]) == differs | {"num_experts_held"}
    cfg = model_config(config)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size) == (2048, 32, 8, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.num_experts, cfg.num_experts_per_tok) == (11776, 1536, 64, 4)
    assert (cfg.conv_L_cache, cfg.rope_theta, cfg.norm_eps, cfg.routed_scaling_factor) == (3, 1e6, 1e-5, 1)
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.held_layers, cfg.held, cfg.vocab_size) == (40, 2, (1, 2, 3, 4, 5), 16, 16384)
    assert cfg.kinds == ("conv", "full_attention", "conv", "conv", "conv") and cfg.sparse == (False, True, True, True, True)
    assert (cfg.remat, str(cfg.dtype)) == (config["assumed"]["program"]["remat"], "bfloat16")
    with pytest.raises(SystemExit, match="held"):
        model_config({**config, "num_dense_layers": 2})
    for refused in ({"conv_bias": True}, {"tie_word_embeddings": False}, {"rope_type": "yarn"}):
        with pytest.raises(ValueError, match="published lfm2_moe settings"):
            Lfm2MoeConfig.tiny(**refused)
    with pytest.raises(ValueError, match="rope_type default"):
        Lfm2MoeConfig.from_config({**config, "rope_parameters": {"rope_theta": 1e6, "rope_type": "linear"}})
    for words, match in (
        ({"remat": "some"}, "remat"), ({"layers_held": (5, 1)}, "layers_held"),
        ({"layers_held": (1, 8)}, "layers_held"), ({"experts_held": 4, "expert_offset": 6}, "experts"),
        ({"layer_types": ("conv", "mamba") * 4}, "layer_types"), ({"num_key_value_heads": 3}, "heads"),
    ):
        with pytest.raises(ValueError, match=match):
            Lfm2MoeConfig.tiny(**words)


# --- the workload ------------------------------------------------------------------


def test_the_workload_trains_through_the_ddp_trainer():
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.workloads import train_lfm2_moe

    args = train_lfm2_moe.build_parser().parse_args(
        ["--epochs", "2", "--world", "2", "--hidden", "32", "--dense-width", "64", "--expert-width", "16", "--seq", "32",
         "--batch", "2", "--corpus-tokens", "2048", "--layers-held", "1,2", "--experts-held", "4", "--expert-offset", "2"]
    )
    report = {}
    first, last = train_lfm2_moe.run(args, report)
    assert last < first and isinstance(report["trainer"], DDPTrainer)
    sizes = np.asarray(report["state"].model_state["moe_sizes"])
    assert sizes.shape == (1, 4) and sizes.sum() > 0
