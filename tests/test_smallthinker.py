"""SmallThinker-21BA3B-Instruct's block (``adapcc_tpu/models/smallthinker.py``)
at a small size on the CPU, the flash kernels in the Pallas interpreter.

The whole model against the plain reference on seeded weights (logits, loss,
first gradient by leaf, three AdamW steps); the attention layer at a group of
seven query heads to a K/V head against the dense form, with the window and
without; bfloat16 in the reference's place fails; each fault of the reference
is another function and moves the loss; the routing reads nothing the
attention computes; the router's gradient against finite differences on one
logit; the four shares of the experts add up to the uncut layer; the expert
layer's rows at the cell's numbers by hand; the configuration reads the
catalog's row and refuses what is not implemented; the workload trains through
``DDPTrainer.step``.

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
from adapcc_tpu.models.smallthinker import (
    Attention, Block, HeldExperts, SmallThinker, SmallThinkerConfig, initial_model_state, stateful_loss,
)
from adapcc_tpu.utils.observability import default_registry
from chipbench import correct, weights_smallthinker_lm
from chipbench.reference import smallthinker_ref
from chipbench.reference.gpt2_ref import leaf_norms

# each kind of layer once: a global layer without positions, a windowed one with them (tiny() itself is the whole period)
CFG = SmallThinkerConfig.tiny(layers_held=(0, 1))
PROD = smallthinker_ref._product("float32")
OPT = {"clip_norm": 1.0, "learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
ROOT = Path(__file__).resolve().parents[1]
FAULTS = tuple(f for f in smallthinker_ref.FAULTS if f)


def file_config(cfg: SmallThinkerConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's file states it (``config.json``
    keys, the published depth beside the layers held)."""
    keys = (
        "vocab_size", "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "moe_ffn_hidden_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts", "sliding_window_size", "rope_theta", "rms_norm_eps",
        "expert_offset",
    )
    out = {k: getattr(cfg, k) for k in keys}
    out.update(
        rope_layout=list(cfg.rope_layout), sliding_window_layout=list(cfg.sliding_window_layout),
        layers_held=list(cfg.held_layers), num_experts_held=cfg.held, num_hidden_layers=len(cfg.held_layers),
        published={"num_hidden_layers": cfg.num_hidden_layers},
    )
    out.update(over)
    return out


def sharp(params):
    """The seeded weights with every matrix of a layer eight times as large:
    at 32 channels an embedding row of 0.2 times a normal(0, 0.02) router is
    0.02 where at the published 2,560 it is 0.2, and a layer's products of 32
    terms are a ninth of those of 2,560, so the router's logits, the scores
    and the experts' part of the stream would all but vanish and no piece of
    the mathematics could be told from another.  Norms and the head stay; the
    embedding, drawn at 0.2, is taken at 0.16, the scale at which every
    tolerance of this file was set."""

    def scaled(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if "embedding" in names:
            return 0.8 * leaf
        return leaf if "scale" in names or "lm_head" in names else 8.0 * leaf

    return jax.tree_util.tree_map_with_path(scaled, params)


@pytest.fixture(scope="module")
def world():
    """Weights by the seed, tokens, and both sides' loss and gradients on them."""
    params = sharp(weights_smallthinker_lm.make_params(5, file_config()))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)
    faulted = jax.jit(lambda p, t, knob: smallthinker_ref.loss_and_grads(p, t, file_config(), "float32", knob))
    reference = faulted(params, tokens, smallthinker_ref.knobs(file_config()))
    grad_fn = {
        loss: jax.jit(jax.value_and_grad(stateful_loss(SmallThinker(CFG), loss, block=64), has_aux=True))
        for loss in ("dense", "chunked")
    }
    program = {loss: fn(params, initial_model_state(CFG), tokens) for loss, fn in grad_fn.items()}
    return {
        "params": params, "tokens": tokens, "reference": reference, "program": program, "faulted": faulted,
        "grad_fn": grad_fn["dense"],
    }


# --- attention at a group of seven ------------------------------------------------


def dense_attention(q, k, v, window):
    """The oracle: scores written out, the mask written out, head ``j`` on K/V head ``j // group``."""
    T, G = q.shape[1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v, precision="highest")


@pytest.mark.parametrize("windowed", [True, False], ids=["window", "global"])
def test_the_attention_layer_is_the_dense_form_at_a_group_of_seven(windowed):
    """The flash kernels (interpreted) inside the model's layer against the
    dense form on the same projections, value and the layer's five gradients:
    seven query heads read the one K/V head, rotated where the layer is
    windowed (14 on 2 heads at the kernels' own entry is
    ``tests/test_flash_window.py``'s).  Tolerance: float32 both sides; the
    online softmax sums in another order: 2e-5 of the largest entry."""
    from adapcc_tpu.models.trinity import rotary

    H, Hkv = CFG.num_attention_heads, CFG.num_key_value_heads
    layer = Attention(CFG, rotated=windowed, windowed=windowed)
    x = jnp.asarray(np.random.default_rng(H).normal(size=(2, 40, 32)), jnp.float32)
    p = jax.tree_util.tree_map(lambda w: 8.0 * w, layer.init(jax.random.PRNGKey(1), x))

    def plain(p, x):
        k = p["params"]
        q, kk, v = (
            (x @ k[name]["kernel"]).reshape(2, 40, heads_, 8)
            for name, heads_ in (("q_proj", H), ("k_proj", Hkv), ("v_proj", Hkv))
        )
        if windowed:
            q, kk = rotary(q, CFG.rope_theta), rotary(kk, CFG.rope_theta)
        return dense_attention(q, kk, v, CFG.sliding_window_size if windowed else None).reshape(2, 40, H * 8) @ k["o_proj"]["kernel"]

    dy = jnp.asarray(np.random.default_rng(2).normal(size=(2, 40, 32)), jnp.float32)
    got, pull = jax.vjp(layer.apply, p, x)
    want, pull_plain = jax.vjp(plain, p, x)
    for a, b in zip(jax.tree_util.tree_leaves((got, pull(dy))), jax.tree_util.tree_leaves((want, pull_plain(dy)))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.abs(b).max()))


# --- the model ---------------------------------------------------------------------


def test_the_weight_maker_makes_the_tree_the_model_reads_and_the_layers_follow_the_published_indices(world):
    params = world["params"]
    shapes = jax.eval_shape(SmallThinker(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    assert CFG.plan == ((False, False), (True, True)) == weights_smallthinker_lm.layer_plan(file_config())
    assert CFG.kinds == ("global", "window+rope") and SmallThinkerConfig.tiny().kinds == ("global",) + ("window+rope",) * 3
    tree = params["params"]
    assert tree["layers_0"]["router"]["kernel"].shape == (32, 8) and tree["lm_head"].shape == (256, 32)
    assert set(tree["layers_1"]["self_attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}          # no q/k norm, no gate
    assert set(tree["layers_1"]["block_sparse_moe"]) == {"experts_w1", "experts_w2", "experts_w3"}   # no shared expert, no bias
    assert initial_model_state(CFG)["moe_sizes"].shape == (2, 8) and initial_model_state(SmallThinkerConfig.tiny())["moe_sizes"].shape == (4, 8)
    published = SmallThinkerConfig()
    assert [i for i, (r, w) in enumerate(published.plan) if not r and not w] == list(range(0, 52, 4))
    assert all(r == w for r, w in published.plan) and published.held == 64 and published.num_experts == 64


def test_logits_match_the_plain_reference(world):
    logits, sizes = jax.jit(SmallThinker(CFG).apply)(world["params"], world["tokens"])
    want = jax.jit(lambda p, row: smallthinker_ref.logits_fn(p, row, file_config()))
    for got, row in zip(logits, world["tokens"]):           # the reference takes a row at a time: one program, twice
        np.testing.assert_allclose(np.asarray(got), np.asarray(want(world["params"], row)), atol=5e-6)
    assert sizes.shape == (2, 8) and np.asarray(sizes).sum(axis=1).tolist() == [3 * 80] * 2     # every expert held: top-3 of 80 tokens


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(world, loss):
    """Tolerance: float32 both sides, products at full precision; what is
    left is the order of summation (the flash kernel's online softmax, the
    grouped products, the fused loss): 5e-4 of a leaf's largest entry."""
    (value, state), grads = world["program"][loss]
    want, want_grads = world["reference"]
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert set(state) == {"moe_sizes"}
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6 + 5e-4 * scale, err_msg=jax.tree_util.keystr(path)
        )
    router = np.asarray(grads["params"]["layers_1"]["router"]["kernel"])
    assert np.abs(router).max() > 0                       # the combine's dw through the softmax over the chosen


def test_bfloat16_in_the_references_place_fails_the_comparison_the_program_passes(world):
    """The reference with every product's operands rounded to bfloat16 is not
    the reference: its first gradient is off by a thousand times what the
    program's is, leaf by leaf (``chipbench/correct.worst_leaf_gap``)."""
    want = np.asarray(leaf_norms(world["reference"][1]))
    rounded = jax.jit(lambda p, t: smallthinker_ref.loss_and_grads(p, t, file_config(), "bfloat16"))(
        world["params"], world["tokens"]
    )
    program = correct.worst_leaf_gap(np.asarray(leaf_norms(world["program"]["dense"][1])), want)
    control = correct.worst_leaf_gap(np.asarray(leaf_norms(rounded[1])), want)
    assert program < 1e-5 < 1e-3 < control, (program, control)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_reference_is_another_function_and_moves_the_loss(world, fault):
    """The controls ``correct`` has to fail are the reference with one piece
    changed, the same compiled program given other flags: each moves the loss
    (by 2e-5 of itself or more, where the program is off by 1e-6 at most) and
    the norm of some leaf's first gradient by 2% or more of itself, where the
    program is off by 1e-4 at most."""
    cfg = file_config()
    loss, want = float(world["reference"][0]), np.asarray(leaf_norms(world["reference"][1]), np.float64)
    faulted, grads = world["faulted"](world["params"], world["tokens"], smallthinker_ref.knobs(cfg, fault))
    moved = np.abs(np.asarray(leaf_norms(grads), np.float64) - want) / np.maximum(want, 1e-30)
    sound = np.abs(np.asarray(leaf_norms(world["program"]["dense"][1]), np.float64) - want) / np.maximum(want, 1e-30)
    assert abs(float(faulted) - loss) > 2e-5 * loss > 20 * abs(float(world["program"]["dense"][0][0]) - loss)
    assert moved[want > 0].max() > 2e-2 > 1e-4 > sound[want > 0].max()
    with pytest.raises(ValueError, match="fault"):
        smallthinker_ref.knobs(cfg, "no_such_fault")


def test_three_adamw_steps_follow_the_plain_reference(world):
    """The program's own optimizer chain (optax, clipped AdamW) on the
    model's stateful loss against the reference's own AdamW, three steps on
    three batches: each loss, and every leaf's change."""
    import optax

    # batches of the fixture's shape: the loss and its gradient are the program the fixture compiled
    params, rows = world["params"], np.random.default_rng(7).integers(0, CFG.vocab_size, (3, 2, 40)).astype(np.int32)
    make = lambda: sharp(weights_smallthinker_lm.make_params(5, file_config()))  # noqa: E731
    want = smallthinker_ref.train_steps(make(), rows, file_config(), OPT, make)
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


# --- the early router ----------------------------------------------------------------


def test_the_routing_reads_the_layers_input_and_nothing_the_attention_computes(world):
    """A layer's choice of experts is made from ``h`` as the layer is handed
    it: other attention weights leave every held expert's assignments as they
    were (and move the layer's output); a call traces the early router once a
    layer (the counter the benchmark's ``correct`` reads)."""
    p = world["params"]["params"]["layers_1"]
    h = 0.5 * jnp.asarray(np.random.default_rng(4).normal(size=(2, 40, 32)), jnp.float32)
    block = Block(CFG, rotated=True, windowed=True)
    apply = jax.jit(lambda p, h: block.apply({"params": p}, h))
    out, sizes = apply(p, h)
    other = {**p, "self_attn": jax.tree_util.tree_map(lambda w: -3.0 * w, p["self_attn"])}
    out_other, sizes_other = apply(other, h)
    assert np.asarray(sizes).tolist() == np.asarray(sizes_other).tolist() and int(np.sum(sizes)) == 3 * 80
    assert float(jnp.abs(out - out_other).max()) > 1e-3
    # the reference's routing from the same input chooses the same experts
    ids, _ = smallthinker_ref.route(h[0], p["router"]["kernel"], file_config(), PROD, smallthinker_ref.knobs(file_config()))
    ids_second, _ = smallthinker_ref.route(h[1], p["router"]["kernel"], file_config(), PROD, smallthinker_ref.knobs(file_config()))
    chosen = np.concatenate([np.asarray(ids).ravel(), np.asarray(ids_second).ravel()])
    assert np.bincount(chosen, minlength=8).tolist() == np.asarray(sizes).tolist()
    metrics = default_registry()
    before = metrics.snapshot()["counters"].get("smallthinker.early_route_calls", 0)
    jax.eval_shape(SmallThinker(CFG).apply, world["params"], world["tokens"])
    assert metrics.snapshot()["counters"]["smallthinker.early_route_calls"] == before + 2


def test_the_routers_gradient_is_the_finite_difference_on_one_logit(world):
    """``w = softmax(top_k(logits))``, then the held experts: the gradient of
    a scalar of the layer's output by one chosen logit (the combine's ``dw``
    through the softmax over the chosen) against a central difference too
    small to flip a choice.  Tolerance: float32, a step of 1e-2 on a logit of
    the order of one: 2% of the derivative."""
    p = world["params"]["params"]["layers_1"]["block_sparse_moe"]
    rng = np.random.default_rng(9)
    y = jnp.asarray(rng.normal(size=(1, 24, 32)), jnp.float32)
    logits = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(1, 24, 32)), jnp.float32)

    @jax.jit
    def scalar(logits):
        top, ids = jax.lax.top_k(logits, 3)
        out, _ = HeldExperts(CFG).apply({"params": p}, y, ids, jax.nn.softmax(top, axis=-1))
        return jnp.vdot(out, c)

    grad = np.asarray(jax.grad(scalar)(logits))
    order = np.argsort(-np.asarray(logits), axis=-1)
    gaps = np.take_along_axis(np.asarray(logits), order[:, 2:3], 1) - np.take_along_axis(np.asarray(logits), order[:, 3:4], 1)
    token = int(np.argmax(gaps))                           # the token whose third and fourth logits lie farthest apart
    for rank in (0, 2, 5):                                 # two chosen logits, and one that was not chosen
        expert, step = int(order[token, rank]), 1e-2
        bump = jnp.zeros_like(logits).at[token, expert].set(step)
        numeric = float(scalar(logits + bump) - scalar(logits - bump)) / (2 * step)
        if rank < 3:
            assert abs(grad[token, expert]) > 1e-5
            assert grad[token, expert] == pytest.approx(numeric, rel=2e-2)
        else:
            assert grad[token, expert] == 0.0 == numeric


# --- the share -------------------------------------------------------------------


def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(world):
    """64 experts over four chips, scaled down: 8 experts, 2 a share at
    offsets 0, 2, 4, 6.  The routing is every share's alike (the router's 8
    outputs, its 3 experts a token, made before the attention); a share adds
    its own experts' part and nothing stands in for the others.  There is no
    shared expert, so nothing is counted once: the four parts add up to the
    layer that holds all eight, and to the uncut reference's."""
    layer = world["params"]["params"]["layers_1"]
    full, knob = layer["block_sparse_moe"], smallthinker_ref.knobs(file_config())
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(80, 32)), jnp.float32)                  # what the router read
    y = jnp.asarray(rng.normal(size=(1, 80, 32)), jnp.float32)               # what stands in front of the experts
    ids, weights = smallthinker_ref.route(h, layer["router"]["kernel"], file_config(), PROD, knob)
    assert np.asarray(jnp.sum(weights, axis=-1)) == pytest.approx(1.0, abs=1e-6)     # the softmax over the chosen three
    whole, sizes = HeldExperts(CFG).apply({"params": full}, y, ids, weights)
    parts, counted = [], []
    for offset in (0, 2, 4, 6):
        cfg = dataclasses.replace(CFG, experts_held=2, expert_offset=offset)
        mine = {k: full[k][offset:offset + 2] for k in full}
        part, given = HeldExperts(cfg).apply({"params": mine}, y, ids, weights)
        share = smallthinker_ref.held_experts(y[0], ids, weights, mine, file_config(cfg), PROD, knob)[None]
        np.testing.assert_allclose(np.asarray(part), np.asarray(share), atol=2e-6)      # the reference is given the same share
        parts.append(part)
        counted.append(np.asarray(given))
    uncut = smallthinker_ref.held_experts(y[0], ids, weights, full, file_config(), PROD, knob)[None]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=2e-6)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=2e-6)
    assert np.concatenate(counted).tolist() == np.asarray(sizes).tolist() and int(np.sum(sizes)) == 3 * 80


def test_the_expert_layers_rows_at_the_cells_numbers_by_hand():
    """8,192 tokens, top-6, 16 of 64 held: a token picks six different
    experts, so at most six of its assignments land here: the bound is 49,152
    rows; on balance a quarter of the 49,152 assignments do, 12,288 (one and a
    half a token), and the short rows are twice that, 24,576, in whole row
    tiles; each held expert then sees 768 rows where its deployment's load at
    8,192 tokens a chip would be 3,072."""
    assert moe.assignment_bound(8192, 6, 16) == 49152
    assert moe.short_rows(8192, 6, 16, 64) == 24576 == 2 * (8192 * 6 * 16 // 64)
    assert moe.short_rows(8192, 6, 16, None) == 49152 and moe.short_rows(8192, 6, 64, 64) == 49152
    assert 8192 * 6 * 16 // 64 // 16 == 768 and 4 * 8192 * 6 // 64 == 3072
    assert 24576 % moe.ROW_TILE == 0


# --- the configuration -------------------------------------------------------------


def test_the_configuration_reads_the_catalogs_row_and_refuses_what_is_not_implemented():
    from chipbench.runners.train_smallthinker_lm import model_config

    config = json.loads((ROOT / "chipbench/configs/smallthinker-21b-a3b-ep4.json").read_text())
    rows = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if rows.is_file():
        row = next(json.loads(l) for l in rows.read_text().splitlines() if '"name": "SmallThinker-21BA3B-Instruct"' in l)
        differs = {k for k, v in row["config"].items() if config.get(k, "absent") != v}
        assert differs == {"num_hidden_layers", "vocab_size"} and config["source"] == row["source_url"]
        assert set(config["reduced"]) == differs | {"num_experts_held"}
    cfg = model_config(config)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (2560, 28, 4, 128)
    assert (cfg.moe_ffn_hidden_size, cfg.num_experts, cfg.moe_num_active_primary_experts) == (768, 64, 6)
    assert (cfg.sliding_window_size, cfg.rope_theta, cfg.rms_norm_eps) == (4096, 1.5e6, 1e-6)
    assert (cfg.num_hidden_layers, cfg.held_layers, cfg.held, cfg.expert_offset, cfg.vocab_size) == (52, (0, 1, 2, 3), 16, 0, 37984)
    assert cfg.kinds == ("global", "window+rope", "window+rope", "window+rope")
    assert (cfg.remat, str(cfg.dtype)) == (config["assumed"]["program"]["remat"], "bfloat16")
    with pytest.raises(SystemExit, match="held"):
        model_config({**config, "num_hidden_layers": 5})
    for refused in ({"moe_primary_router_apply_softmax": False}, {"tie_word_embeddings": True}, {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(ValueError, match="published smallthinker settings"):
            SmallThinkerConfig.tiny(**refused)
    for words, match in (
        ({"remat": "some"}, "remat"), ({"layers_held": (3, 1)}, "layers_held"), ({"layers_held": (1, 8)}, "layers_held"),
        ({"experts_held": 4, "expert_offset": 6}, "experts"), ({"rope_layout": (0, 1, 2, 1, 0, 1, 1, 1)}, "rope_layout"),
        ({"sliding_window_layout": (0, 1)}, "sliding_window_layout"), ({"num_key_value_heads": 2}, "heads"),
    ):
        with pytest.raises(ValueError, match=match):
            SmallThinkerConfig.tiny(**words)


# --- the workload ------------------------------------------------------------------


def test_the_workload_trains_through_the_ddp_trainer():
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.workloads import train_smallthinker

    args = train_smallthinker.build_parser().parse_args(
        ["--epochs", "2", "--world", "2", "--hidden", "32", "--expert-width", "16", "--seq", "32", "--batch", "2",
         "--corpus-tokens", "2048", "--layers-held", "0,1", "--experts-held", "4", "--expert-offset", "2"]
    )
    report = {}
    first, last = train_smallthinker.run(args, report)
    assert last < first and isinstance(report["trainer"], DDPTrainer)
    sizes = np.asarray(report["state"].model_state["moe_sizes"])
    assert sizes.shape == (2, 4) and sizes.sum() > 0
