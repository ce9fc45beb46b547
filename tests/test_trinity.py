"""Trinity-Mini's block (``adapcc_tpu/models/trinity.py``) at a small size on
the CPU: both kinds of layer, 8 experts top-2 beside a shared one, window 16
at T = 64, 4 query heads on 2 KV heads.

The program against the plain reference (``chipbench/reference/trinity_ref.py``)
on seeded weights: logits, loss and every gradient leaf; the eight shares'
routed parts plus the shared expert counted once add up to the uncut
reference's layer output; the expert layer drops nothing; the workload trains
through ``DDPTrainer.step`` and hands the routing counts out beside the loss.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.moe import (
    MoEConfig, MoEMLP, assignment_bound, held_assignments, record_routing, routed_experts, short_rows,
)
from adapcc_tpu.models.trinity import Trinity, TrinityConfig, initial_model_state, stateful_loss
from adapcc_tpu.utils.observability import MetricsRegistry, default_registry
from chipbench import weights_moe_lm
from chipbench.reference import trinity_ref

CFG = TrinityConfig.tiny()


def file_config(cfg: TrinityConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's files state it (``config.json`` keys)."""
    out = {
        f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if f.name not in ("dtype", "attention", "flash_block", "remat", "layer_types", "experts_held")
    }
    out.update(layer_types_here=list(cfg.kinds), num_experts_held=cfg.held)
    out.update(over)
    return out


@pytest.fixture(scope="module")
def params():
    return weights_moe_lm.make_params(5, file_config())


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 64)), jnp.int32)


@pytest.fixture(scope="module")
def reference(params, tokens):
    """The plain reference's ``(loss, grads)`` on the module's weights and
    tokens, once a module: both forms of the model's loss are held to the
    same numbers."""
    return jax.jit(lambda p, t: trinity_ref.loss_and_grads(p, t, file_config()))(params, tokens)


@pytest.fixture(scope="module")
def reference_logits(params, tokens):
    """The plain reference's logits, once a module: both attentions are held to them."""
    return jax.jit(lambda p, t: jnp.stack([trinity_ref.logits_fn(p, row, file_config()) for row in t]))(params, tokens)


def test_the_weight_maker_makes_the_tree_the_model_reads(params):
    shapes = jax.eval_shape(Trinity(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    mlp = params["params"]["layers_1"]["mlp"]
    assert not np.any(np.asarray(mlp["expert_bias"])) and mlp["router"].shape == (32, 8)
    assert CFG.kinds == ("sliding_attention", "sliding_attention", "full_attention")


@pytest.mark.parametrize("attention", ["xla", "flash"])
def test_logits_match_the_plain_reference(params, tokens, reference_logits, attention):
    model = Trinity(dataclasses.replace(CFG, attention=attention, flash_block=16))
    logits, sizes = jax.jit(model.apply)(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(reference_logits), atol=2e-6)
    assert sizes.shape == (2, 8) and sizes.dtype == jnp.int32
    assert sizes.sum(axis=1).tolist() == [2 * 64 * 2] * 2       # every assignment, all experts held


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(params, tokens, reference, loss):
    model = Trinity(CFG)
    (value, state), grads = jax.jit(jax.value_and_grad(stateful_loss(model, loss, block=64), has_aux=True))(
        params, initial_model_state(CFG), tokens
    )
    want, want_grads = reference
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert state["moe_sizes"].shape == (2, 8)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6 + 2e-4 * scale, err_msg=jax.tree_util.keystr(path)
        )
    # no gradient reaches the bias the top-k is taken with
    assert not np.any(np.asarray(grads["params"]["layers_1"]["mlp"]["expert_bias"]))


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(params):
    """What each of eight chips computes for its one expert, plus what they
    all compute alike (the shared expert) counted once, is the uncut
    reference's expert FFN."""
    p = params["params"]["layers_1"]["mlp"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(96, 32)), jnp.float32)
    prod = trinity_ref._product("float32")
    cfg = file_config()
    whole = trinity_ref.sparse_ffn(x, p, cfg, prod)
    shared = trinity_ref.gated_mlp(
        x, p["shared_experts"]["gate_proj"]["kernel"], p["shared_experts"]["up_proj"]["kernel"],
        p["shared_experts"]["down_proj"]["kernel"], prod,
    )
    ids, weights = trinity_ref.route(x, p, cfg, prod)
    total, given = shared, []
    for share in range(8):
        stacked = {k: p[f"experts_{k}"][share:share + 1] for k in ("w1", "w3", "w2")}
        part, sizes = routed_experts(x, ids, weights, stacked, offset=share, act=jax.nn.silu, dtype=jnp.float32)
        total, given = total + part, given + [int(sizes[0])]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-6)
    assert sum(given) == 96 * 2
    # and a chip's share through the model: experts 2-5 of 8, routed over all 8
    held = dataclasses.replace(CFG, experts_held=4, expert_offset=2)
    cut = jax.tree_util.tree_map(lambda a: a, params)
    for name in ("layers_1", "layers_2"):
        for k in ("experts_w1", "experts_w3", "experts_w2"):
            cut["params"][name]["mlp"][k] = params["params"][name]["mlp"][k][2:6]
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, 64)), jnp.int32)
    logits, _ = jax.jit(Trinity(held).apply)(cut, toks)
    want = jax.jit(lambda p, row: trinity_ref.logits_fn(p, row, file_config(held, expert_offset=2)))(cut, toks[0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize(
    "held,offset,tokens,num_experts", [(8, 0, 32, None), (3, 2, 32, None), (1, 7, 32, None), (2, 2, 256, 16)],
    ids=["all-held", "three-held", "one-held", "past-the-short-rows"],
)
def test_the_expert_layer_drops_nothing_however_uneven_the_routing(held, offset, tokens, num_experts):
    """Every token sends both its choices to the same two experts: the rows
    fill to the bound and every assignment of a held expert is computed, also
    where the layer has short rows (128 here) and the bound's path must run."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(tokens, 8)), jnp.float32)
    ids = jnp.tile(jnp.asarray([[2, 3]], jnp.int32), (tokens, 1))
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(tokens, 2)), jnp.float32)
    stacked = {
        "w1": jnp.asarray(rng.normal(size=(held, 8, 4)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(held, 4, 8)), jnp.float32),
    }
    y, sizes = routed_experts(
        x, ids, w, stacked, offset=offset, num_experts=num_experts, act=jax.nn.gelu, dtype=jnp.float32
    )
    want = jnp.zeros_like(x)
    for j, e in enumerate((2, 3)):
        if offset <= e < offset + held:
            want = want + w[:, j:j + 1] * (jax.nn.gelu(x @ stacked["w1"][e - offset]) @ stacked["w2"][e - offset])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    here = sum(offset <= e < offset + held for e in (2, 3))
    bound = assignment_bound(tokens, 2, held)
    assert int(sizes.sum()) == tokens * here <= bound
    sent = held_assignments(ids, offset, held)
    assert sent.order.shape == (bound,) and int(sent.here.sum()) == tokens * here
    short = short_rows(tokens, 2, held, num_experts)
    # told how many experts there are, the layer has short rows and these assignments overflow them
    assert short == bound if num_experts is None else short == 128 < int(sizes.sum())
    g = default_registry().snapshot()["gauges"]
    assert (g["moe.assignment_bound"], g["moe.short_rows"]) == (bound, short)


# the expert layer with short rows: 256 tokens top-2 over 16 experts, experts
# 4 and 5 held: a balanced share of 64 assignments, 128 short rows, bound 512
_SHORT = dict(tokens=256, top_k=2, num_experts=16, held=2, offset=4)


def _routing_that_fills(fill: int) -> jnp.ndarray:
    """``ids [256, 2]`` that send exactly ``fill`` assignments to experts 4
    and 5 (first choices to 4, then second choices to 5), the rest to 0 and 1."""
    n = _SHORT["tokens"]
    flat = np.tile(np.asarray([0, 1], np.int32), n)
    first = min(fill, n)
    flat[0:2 * first:2] = 4
    flat[1:2 * (fill - first):2] = 5
    return jnp.asarray(flat.reshape(n, 2))


def _balanced_routing() -> jnp.ndarray:
    rng = np.random.default_rng(11)
    return jnp.asarray(np.stack([rng.permutation(16)[:2] for _ in range(_SHORT["tokens"])]), jnp.int32)


@pytest.mark.parametrize(
    "ids,fits",
    [(_balanced_routing, True), (lambda: _routing_that_fills(128), True),
     (lambda: _routing_that_fills(129), False), (lambda: _routing_that_fills(512), False)],
    ids=["balanced", "exactly-the-short-rows", "one-more", "the-bound-filled"],
)
def test_short_rows_give_what_the_one_path_gives(ids, fits):
    """The layer told how many experts there are (short rows when the
    assignments fit them, the bound when not) against the layer of one path:
    outputs, counts and the gradient of every input."""
    ids = ids()
    n, k, held = _SHORT["tokens"], _SHORT["top_k"], _SHORT["held"]
    short = short_rows(n, k, held, _SHORT["num_experts"])
    assert short == 128 < assignment_bound(n, k, held) == 512
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    stacked = {
        name: jnp.asarray(rng.normal(size=(held, *shape)), jnp.float32)
        for name, shape in (("w1", (8, 4)), ("w3", (8, 4)), ("w2", (4, 8)))
    }
    mix = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)

    def layer(num_experts):
        def f(x, w, stacked):
            y, sizes = routed_experts(
                x, ids, w, stacked, offset=_SHORT["offset"], num_experts=num_experts, act=jax.nn.silu,
                dtype=jnp.float32,
            )
            return jnp.sum(y * mix), (y, sizes)

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(x, w, stacked)

    (_, (y, sizes)), grads = layer(_SHORT["num_experts"])
    (_, (want_y, want_sizes)), want = layer(None)
    assert (int(sizes.sum()) <= short) is fits
    assert sizes.tolist() == want_sizes.tolist()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-6)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5, err_msg=jax.tree_util.keystr(path)
        )


def _conditionals(fn, *args) -> int:
    return jax.jit(fn).lower(*args).as_text().count("stablehlo.case")


@pytest.mark.parametrize("who,want", [("MoEMLP", 0), ("all-held", 0), ("a-share-held", 4)])
def test_only_a_layer_that_holds_a_share_chooses_between_two_paths(who, want, params, tokens):
    """Where the short rows would reach the bound (every expert held) the
    lowered program has the one path and no ``conditional``; a share of the
    experts has one forward and one backward in each of its two expert layers."""
    if who == "MoEMLP":
        layer = MoEMLP(MoEConfig.tiny())
        x = jnp.zeros((2, 16, 32), jnp.float32)
        p = layer.init(jax.random.PRNGKey(0), x)
        count = _conditionals(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)[0])), p, x)
    else:
        cfg = CFG if who == "all-held" else dataclasses.replace(CFG, experts_held=1, expert_offset=3)
        model = Trinity(cfg)
        wide = jnp.tile(tokens, (4, 1))   # 512 tokens: a share of 128 rows, short rows 256, bound 512
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0), wide)
        count = _conditionals(jax.grad(lambda p, t: jnp.sum(model.apply(p, t)[0])), p, wide)
    assert count == want


def test_routing_samples_and_the_dropped_counter():
    metrics = MetricsRegistry()
    record_routing(np.array([[10, 30, 0, 0], [5, 5, 5, 5]]), metrics=metrics)
    snap = metrics.snapshot()
    assert snap["samples"]["moe.assignments_here"]["count"] == 2
    assert snap["samples"]["moe.assignments_here"]["mean"] == 30.0
    assert snap["samples"]["moe.load_max_over_mean"]["max"] == 3.0
    assert snap["counters"]["moe.dropped"] == 0.0
    assert "moe.rows_fit" not in snap["samples"]      # no layer said how short its rows are


@pytest.mark.parametrize("short,fit", [(40, 1.0), (32, 0.5), (16, 0.0)], ids=["both-fit", "one-fits", "neither"])
def test_routing_samples_whether_the_assignments_fit_the_short_rows(short, fit):
    """``moe.rows_fit``: 1.0 for a layer-step whose assignments fit the short
    rows the traced layer left in its gauge, else 0.0."""
    metrics = MetricsRegistry()
    metrics.gauge("moe.short_rows", short)
    record_routing(np.array([[10, 30, 0, 0], [5, 5, 5, 5]]), metrics=metrics)
    rows_fit = metrics.snapshot()["samples"]["moe.rows_fit"]
    assert (rows_fit["count"], rows_fit["mean"], rows_fit["max"]) == (2, fit, float(fit > 0))


def test_the_benchmarks_reader_gives_the_share_of_layer_steps_on_the_short_rows():
    from chipbench import run

    reader = run.load_reader("moe_short_rows_share", run.BENCH / "metrics")
    default_registry().gauge("moe.short_rows", 32)         # the process-wide registry, as a traced layer leaves it
    record_routing(np.array([[10, 30, 0, 0], [5, 5, 5, 5]]))
    fit = default_registry().snapshot()["samples"]["moe.rows_fit"]
    assert fit["count"] >= 2 and 0.0 < fit["mean"] < 1.0
    assert reader.read({}) == pytest.approx(100.0 * fit["mean"])


def test_the_config_refuses_what_it_does_not_implement():
    with pytest.raises(ValueError, match="sigmoid"):
        TrinityConfig.tiny(score_func="softmax")
    with pytest.raises(ValueError, match="layer_types"):
        TrinityConfig.tiny(num_hidden_layers=4)
    with pytest.raises(ValueError, match="experts"):
        TrinityConfig.tiny(experts_held=4, expert_offset=6)
    assert TrinityConfig(num_hidden_layers=8).kinds[3::4] == ("full_attention",) * 2


def test_the_workload_trains_through_ddptrainer_and_hands_the_counts_out(capsys):
    from adapcc_tpu.workloads.train_trinity import build_parser, run

    report = {}
    first, last = run(build_parser().parse_args(
        ["--epochs", "3", "--world", "2", "--experts-held", "4", "--expert-offset", "2", "--remat", "dots"]
    ), report)
    assert last < first - 0.5, (first, last)
    out = capsys.readouterr().out
    assert "experts 2..6 of 8 held" in out and "assignments here" in out
    sizes = np.asarray(report["state"].model_state["moe_sizes"])
    assert sizes.shape == (3, 4) and sizes.sum() > 0
    assert report["trainer"].donate_state is True
