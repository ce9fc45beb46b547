"""Granite 4.0-H's block (``adapcc_tpu/models/granite_hybrid.py``) and its
state-space scan (``adapcc_tpu/ops/ssd.py``) at a small size on the CPU, the
kernels in the Pallas interpreter.

The chunked scan against the recurrence a step at a time
(``chipbench/reference/granite_hybrid_ref.ssm_recurrence``), forward and all
six gradients, at a row inside one chunk and rows over several, with ``T`` no
whole number of chunks; the state carried across a packed join; the mixer and
the whole model against the plain reference on seeded weights (loss, first
gradient by leaf, three AdamW steps); each of the four scalings changes the
result; ``layer_types`` places the mixers; the tied head's gradient reaches
the embedding twice; the workload trains through ``DDPTrainer.step``.

One module-scoped fixture holds the weights, the tokens, the reference's loss
and gradients and the program's: every comparison reads them (PERF.md section
7 item 27: a result computed once a module).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.granite_hybrid import (
    GraniteHybrid, GraniteHybridConfig, Mamba2Mixer, initial_model_state, stateful_loss,
)
from adapcc_tpu.ops.ssd import chunk_decay_floor, chunk_plan, ssd
from adapcc_tpu.utils.observability import default_registry
from chipbench import weights_ssm_lm
from chipbench.reference import granite_hybrid_ref

CFG = GraniteHybridConfig.tiny()
PROD = granite_hybrid_ref._product("float32")
OPT = {"clip_norm": 1.0, "learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


def file_config(cfg: GraniteHybridConfig = CFG, **over) -> dict:
    """The configuration as the benchmark's file states it (``config.json`` keys)."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name not in ("dtype", "remat")}
    out["layer_types"] = list(cfg.layer_types)
    out.update(over)
    return out


@pytest.fixture(scope="module")
def world():
    """Weights by the seed, tokens, and both sides' loss and gradients on them."""
    params = weights_ssm_lm.make_params(5, file_config())
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)
    reference = jax.jit(lambda p, t: granite_hybrid_ref.loss_and_grads(p, t, file_config()))(params, tokens)
    program = {
        loss: jax.value_and_grad(stateful_loss(GraniteHybrid(CFG), loss, block=64), has_aux=True)(
            params, initial_model_state(), tokens
        ) for loss in ("dense", "chunked")
    }
    return {"params": params, "tokens": tokens, "reference": reference, "program": program}


# --- the kernel --------------------------------------------------------------


def scan_inputs(T, seed, rate, B=1, H=4, P=8, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (B, T, H * P))
    lo, hi = {"near-one": (1e-5, 1e-3), "near-zero": (1.0, 8.0), "every-rate": (1e-4, 4.0)}[rate]
    dt = jnp.exp(jax.random.uniform(ks[1], (B, T, H), minval=math.log(lo), maxval=math.log(hi)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=math.log(4.0)))
    Bm, Cm = jax.random.normal(ks[3], (B, T, N)), jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (H,))
    return (x, dt, A, Bm, Cm, D), jax.random.normal(ks[6], (B, T, H * P))


def recurrence(x, dt, A, B, C, D):
    H = dt.shape[-1]
    rows = [
        granite_hybrid_ref.ssm_recurrence(x[b].reshape(x.shape[1], H, -1), dt[b], A, B[b], C[b], D, PROD).reshape(x.shape[1:])
        for b in range(x.shape[0])
    ]
    return jnp.stack(rows)


_LENGTHS = {"under-a-chunk": 40, "a-chunk-and-a-part": 200, "three-chunks-less-a-part": 300}
# (T, rate, B, H, the one head whose output is weighed or None for all): a row of four heads (two lane tiles of a pair)
# at every length and rate; then two rows of two, three (one head a tile) and six heads, so that a head or a row picked
# by the wrong index reads another's numbers; then the last head's output alone, so that what dt, A and D get back for a
# head other than the first is held to that head's column and the other columns to zero
SCANS = {
    **{f"{length}-{rate}": (T, rate, 1, 4, None)
       for length, T in _LENGTHS.items() for rate in ("near-one", "near-zero", "every-rate")},
    "two-rows-of-two-heads": (200, "every-rate", 2, 2, None),
    "two-rows-of-three-heads": (140, "every-rate", 2, 3, None),
    "two-rows-of-six-heads": (140, "every-rate", 2, 6, None),
    "the-last-of-four-heads-alone": (140, "near-one", 2, 4, 3),
}


@pytest.mark.parametrize("case", list(SCANS))
def test_the_chunked_scan_is_the_recurrence_forward_and_in_all_six_gradients(case):
    """Tolerance: both sides are float32 with full-precision products, and
    differ in the order of summation; the chunked form's exponent ``G_t - G_s``
    is a difference of two sums as large as a chunk's whole log-decay, so it
    carries the rounding of that sum: hundreds at ``every-rate`` (an ulp of
    3e-5, 2e-4 of the largest value compared with the sums' own error), up to
    4,000 at ``near-zero``, where a step forgets all but e^-1 to e^-32 (an ulp
    of 5e-4: 1e-3).  The model's own rates sum to 200 at most in a chunk."""
    T, rate, B, H, only = SCANS[case]
    rel = 1e-3 if rate == "near-zero" else 2e-4
    args, mix = scan_inputs(T, T, rate, B=B, H=H)
    if only is not None:
        mix = mix * jnp.repeat(jnp.arange(H) == only, mix.shape[-1] // H)
    got, want = ssd(*args), recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=rel * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: jnp.sum(ssd(*a) * mix), argnums=tuple(range(6)))(*args)
    wants = jax.grad(lambda *a: jnp.sum(recurrence(*a) * mix), argnums=tuple(range(6)))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), grads, wants):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5 + rel * float(jnp.abs(b).max()), err_msg=f"d{name}"
        )
    if only is not None:
        for name, g in zip(("dt", "A", "D"), (grads[1], grads[2], grads[5])):
            g = np.asarray(g)
            assert np.abs(g[..., only]).max() > 1e-3 and not g[..., :only].any(), name


def test_the_chunk_follows_the_shape_and_the_scan_leaves_its_gauges():
    assert chunk_plan(8192) == (128, 4, 8192)         # the cell: 64 chunks, four to a grid step
    assert chunk_plan(300) == (128, 1, 384) and chunk_plan(200) == (128, 2, 256) and chunk_plan(40) == (40, 1, 40)
    args, _ = scan_inputs(200, 0, "every-rate")
    ssd(*args)
    gauges = default_registry().snapshot()["gauges"]
    assert (gauges["ssd.chunk"], gauges["ssd.tiles"]) == (128, 4 * 2)
    assert gauges["ssd.padded_rows"] == 256 - 200        # the one copy left: the pad along T, where T is no whole chunks
    ssd(*scan_inputs(40, 0, "every-rate")[0])
    assert default_registry().snapshot()["gauges"]["ssd.padded_rows"] == 0
    with pytest.raises(ValueError, match="ssd shapes"):
        ssd(args[0], args[1], args[2], args[3][..., :4], args[4], args[5])


def test_a_head_that_fills_no_lane_tile_is_refused_through_mosaic_and_taken_by_the_interpreter():
    """The kernels find a head as ``P`` lanes of a 128-lane tile: through
    Mosaic ``P`` divides 128 (or is whole tiles), the heads fill whole tiles
    and the state's channels are whole tiles; the scan says so before any
    kernel is built.  The interpreter takes any shape."""
    args, _ = scan_inputs(40, 1, "every-rate", H=3, P=24, N=128)
    with pytest.raises(ValueError, match="lanes of a 128-lane tile"):
        ssd(*args, interpret=False)
    narrow, _ = scan_inputs(40, 1, "every-rate", H=2, P=64, N=16)
    with pytest.raises(ValueError, match="multiple of 128"):
        ssd(*narrow, interpret=False)
    want = recurrence(*args)
    np.testing.assert_allclose(np.asarray(ssd(*args, interpret=True)), np.asarray(want), atol=2e-4 * float(jnp.abs(want).max()))


def test_a_batch_of_rows_scans_each_from_a_zero_state_and_a_packed_join_resets_nothing():
    """Two rows of a batch know nothing of each other; two walks packed end
    to end into one row are one sequence: the second walk's outputs start
    from the state the first left (the recurrence says the same), and differ
    from the walk scanned alone."""
    args, _ = scan_inputs(140, 3, "near-one", B=2)
    both = ssd(*args)
    x, dt, A, Bm, Cm, D = args
    for b in range(2):
        alone = ssd(x[b:b + 1], dt[b:b + 1], A, Bm[b:b + 1], Cm[b:b + 1], D)
        np.testing.assert_allclose(np.asarray(both[b:b + 1]), np.asarray(alone), atol=1e-5)
    packed = tuple(jnp.concatenate([a[0], a[1]])[None] for a in (x, dt, Bm, Cm))
    joined = ssd(packed[0], packed[1], A, packed[2], packed[3], D)
    want = recurrence(packed[0], packed[1], A, packed[2], packed[3], D)
    np.testing.assert_allclose(np.asarray(joined), np.asarray(want), atol=2e-4 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(np.asarray(joined[0, :140]), np.asarray(both[0]), atol=1e-5)
    assert float(jnp.abs(joined[0, 140:] - both[1]).max()) > 0.05     # the first walk's state is still there


def test_the_decay_floor_is_the_smallest_decay_a_chunk_lays_on_its_state():
    (_, dt, A, *_), _ = scan_inputs(300, 2, "near-one")
    a = np.pad(np.asarray(dt * A, np.float64), ((0, 0), (0, 84), (0, 0))).reshape(1, 3, 128, 4)
    assert float(chunk_decay_floor(dt, A)) == pytest.approx(np.exp(a.sum(axis=2).min()), rel=1e-5)
    assert float(jax.grad(lambda d: chunk_decay_floor(d, A))(dt).max()) == 0.0       # a sample, not a term of the loss


# --- the mixer and the model -------------------------------------------------


@pytest.mark.parametrize("T", [40, 200])
def test_the_mamba_mixer_is_the_step_at_a_time_form(world, T):
    """One projection split three ways, the biased convolution over x, B and C
    together, the scan, the gate before the norm over all channels; a row
    inside one chunk of the kernel and a row over two."""
    p = world["params"]["params"]["layers_0"]["mixer"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T, 32)), jnp.float32)
    got, floor = Mamba2Mixer(CFG).apply({"params": p}, x)
    knob = granite_hybrid_ref.knobs(file_config())
    want = jax.jit(lambda p, x: jnp.stack([granite_hybrid_ref.mamba_mixer(row, p, file_config(), PROD, knob) for row in x]))(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    assert 0.0 < float(floor) < 1.0


def test_the_weight_maker_makes_the_tree_the_model_reads_and_layer_types_places_the_mixers(world):
    params = world["params"]
    shapes = jax.eval_shape(GraniteHybrid(CFG).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    for want, got in zip(jax.tree_util.tree_leaves(shapes), jax.tree_util.tree_leaves(params)):
        assert want.shape == got.shape and got.dtype == jnp.float32
    assert CFG.kinds == ("mamba", "mamba", "attention", "mamba") == weights_ssm_lm.layer_kinds(file_config())
    assert "q_proj" in params["params"]["layers_2"]["mixer"] and "in_proj" in params["params"]["layers_3"]["mixer"]
    assert "lm_head" not in params["params"]                       # the embedding is the head
    moved = dataclasses.replace(CFG, layer_types=("attention", "mamba", "mamba", "mamba"))
    placed = jax.eval_shape(GraniteHybrid(moved).init, jax.random.PRNGKey(0), jnp.zeros((1, 40), jnp.int32))
    assert "q_proj" in placed["params"]["layers_0"]["mixer"] and "A_log" in placed["params"]["layers_2"]["mixer"]
    published = GraniteHybridConfig()
    assert [i for i, k in enumerate(published.kinds) if k == "attention"] == [5, 15, 25, 35]
    scan = params["params"]["layers_0"]["mixer"]
    rate = np.exp(np.asarray(scan["A_log"])) * np.asarray(jax.nn.softplus(scan["dt_bias"]))
    assert 0.15 < np.exp(-rate).min() and np.exp(-rate).max() < 0.9995     # a step forgets neither all nor nothing


def test_logits_match_the_plain_reference(world):
    logits, floor = GraniteHybrid(CFG).apply(world["params"], world["tokens"])
    want = jax.jit(
        lambda p, t: jnp.stack([granite_hybrid_ref.logits_fn(p, row, file_config()) for row in t])
    )(world["params"], world["tokens"])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=5e-6)
    assert floor.shape == () and 0.0 < float(floor) < 1.0


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(world, loss):
    """Tolerance: float32 both sides, products at full precision; what is
    left is the order of summation (the chunked scan, the flash kernel's
    online softmax, the fused loss): 5e-4 of a leaf's largest entry."""
    (value, state), grads = world["program"][loss]
    want, want_grads = world["reference"]
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert set(state) == {"ssd_decay_floor"} and 0.0 < float(state["ssd_decay_floor"]) < 1.0
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-6 + 5e-4 * scale, err_msg=jax.tree_util.keystr(path)
        )


def test_bfloat16_in_the_references_place_fails_the_comparison_the_program_passes(world):
    """The reference with every product's operands rounded to bfloat16 is not
    the reference: its first gradient is off by a hundred times what the
    program's is, leaf by leaf (``chipbench/correct.worst_leaf_gap``)."""
    from chipbench import correct
    from chipbench.reference.gpt2_ref import leaf_norms

    want = np.asarray(leaf_norms(world["reference"][1]))
    rounded = jax.jit(lambda p, t: granite_hybrid_ref.loss_and_grads(p, t, file_config(), "bfloat16"))(
        world["params"], world["tokens"]
    )
    program = correct.worst_leaf_gap(np.asarray(leaf_norms(world["program"]["dense"][1])), want)
    control = correct.worst_leaf_gap(np.asarray(leaf_norms(rounded[1])), want)
    assert program < 2e-4 < 2e-3 < control, (program, control)


def test_three_adamw_steps_follow_the_plain_reference(world):
    """The program's own optimizer chain (optax, clipped AdamW) on the
    model's stateful loss against the reference's own AdamW, three steps on
    three batches: each loss, and every leaf's change."""
    import optax

    params, rows = world["params"], np.random.default_rng(7).integers(0, CFG.vocab_size, (3, 2, 40)).astype(np.int32)
    make = lambda: weights_ssm_lm.make_params(5, file_config())  # noqa: E731
    want = granite_hybrid_ref.train_steps(make(), rows, file_config(), OPT, make)
    tx = optax.chain(
        optax.clip_by_global_norm(OPT["clip_norm"]),
        optax.adamw(OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"], weight_decay=OPT["weight_decay"]),
    )
    loss_fn = stateful_loss(GraniteHybrid(CFG))

    @jax.jit
    def step(p, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, initial_model_state(), batch)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    p, opt_state, losses = params, tx.init(params), []
    for batch in rows:
        p, opt_state, loss = step(p, opt_state, jnp.asarray(batch))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, np.asarray(want["losses"]), rtol=2e-6)
    moved = np.asarray(granite_hybrid_ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, params)))
    np.testing.assert_allclose(moved, np.asarray(want["update_norms"]), rtol=2e-3)


SCALINGS = {
    "embedding_multiplier": 6.0, "residual_multiplier": 0.5, "attention_multiplier": 0.4, "logits_scaling": 4.0,
}


def sharp(params):
    """The weights with the attention layer's four projections thirty times
    as large: at 32 channels the seeded scores are all but zero and a softmax
    of them is flat whatever scales them (at the published 2,048 they are
    not), and what the layer adds to the stream is small beside the rest."""
    mixer = params["params"]["layers_2"]["mixer"]
    mixer = {k: {"kernel": 30.0 * v["kernel"]} for k, v in mixer.items()}
    return {"params": {**params["params"], "layers_2": {**params["params"]["layers_2"], "mixer": mixer}}}


@pytest.mark.parametrize("key", list(SCALINGS))
def test_each_of_the_four_scalings_changes_the_result_as_it_changes_the_references(world, key):
    cfg = dataclasses.replace(CFG, **{key: SCALINGS[key]})
    params = sharp(world["params"])
    logits, _ = GraniteHybrid(cfg).apply(params, world["tokens"][:1])
    base, _ = GraniteHybrid(CFG).apply(params, world["tokens"][:1])
    want = jax.jit(lambda p, row: granite_hybrid_ref.logits_fn(p, row, file_config(cfg)))(params, world["tokens"][0])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want), atol=5e-6)
    assert float(jnp.abs(logits - base).max()) > 1e-3


@pytest.mark.parametrize("fault", [f for f in granite_hybrid_ref.FAULTS if f])
def test_each_fault_of_the_reference_is_another_function(world, fault):
    """The three controls ``correct`` has to fail are the reference with one
    number changed: each moves the logits."""
    cfg = file_config()
    row, params = world["tokens"][0], sharp(world["params"])
    sound = granite_hybrid_ref.logits_fn(params, row, cfg)
    broken = granite_hybrid_ref.logits_fn(params, row, cfg, knob=granite_hybrid_ref.knobs(cfg, fault))
    assert float(jnp.abs(sound - broken).max()) > 1e-3
    with pytest.raises(ValueError, match="fault"):
        granite_hybrid_ref.knobs(cfg, "no_such_fault")


def test_the_tied_heads_gradient_reaches_the_embedding_twice(world):
    """The embedding is looked up and is the head: its gradient is the sum of
    the two uses'.  With the lookup's path cut the head's part is left, with
    the head's path cut the lookup's, and the two add up to the whole."""
    params, tokens = world["params"], world["tokens"]
    model, table = GraniteHybrid(CFG), world["params"]["params"]["embed_tokens"]["embedding"]

    def loss(lookup, head):
        from adapcc_tpu.models.gpt2 import lm_loss

        def with_table(e):
            return {"params": {**params["params"], "embed_tokens": {"embedding": e}}}

        hidden, _ = model.apply(with_table(lookup), tokens, return_hidden=True)
        return lm_loss(jnp.einsum("btd,vd->btv", hidden, head), tokens)

    by_lookup, by_head = jax.grad(loss, argnums=(0, 1))(table, table)
    whole = world["program"]["dense"][1]["params"]["embed_tokens"]["embedding"]
    assert float(jnp.abs(by_lookup).max()) > 1e-4 and float(jnp.abs(by_head).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(by_lookup + by_head), np.asarray(whole), atol=1e-6)
    unseen = np.setdiff1d(np.arange(CFG.vocab_size), np.asarray(tokens))
    assert not np.asarray(by_lookup)[unseen].any() and np.asarray(by_head)[unseen].any()


def test_the_config_reads_config_json_and_refuses_what_it_does_not_implement():
    import json
    from pathlib import Path

    body = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/granite-h-micro-vp8.json").read_text())
    cfg = GraniteHybridConfig.from_config(body)
    assert cfg.kinds == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4 and len(cfg.layer_types) == 40
    assert (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state, cfg.head_dim) == (2048, 4096, 128, 64)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier, cfg.logits_scaling) == (12, 0.22, 0.015625, 8)
    with pytest.raises(ValueError, match="no experts"):
        GraniteHybridConfig.tiny(num_local_experts=4)
    with pytest.raises(ValueError, match="no experts"):
        GraniteHybridConfig.tiny(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="layer_types"):
        GraniteHybridConfig.tiny(num_hidden_layers=5)
    with pytest.raises(ValueError, match="remat"):
        GraniteHybridConfig.tiny(remat="some")


def test_the_workload_trains_through_ddptrainer_and_hands_the_decay_floor_out(capsys):
    from adapcc_tpu.workloads.train_granite_hybrid import build_parser, run

    report = {}
    first, last = run(build_parser().parse_args(["--epochs", "3", "--world", "2"]), report)
    assert last < first - 0.5, (first, last)
    out = capsys.readouterr().out
    assert "granite_hybrid:" in out and "'attention'" in out and "smallest chunk decay" in out
    floor = float(report["state"].model_state["ssd_decay_floor"])
    assert 0.0 <= floor < 1.0
    assert report["trainer"].donate_state is True
