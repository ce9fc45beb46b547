"""Phi-4-mini-flash-reasoning's SambaY decoder
(``adapcc_tpu/models/phi4_flash.py``) and its selective scan
(``adapcc_tpu/ops/selective_scan.py``) at a small size on the CPU, the
kernels in the Pallas interpreter.

The scan kernel against the recurrence a step at a time
(``chipbench/reference/phi4_flash_ref.selective_recurrence``), forward and in
all six gradients, and through the state a block of rows hands the next, at
``T`` that is and is not a whole number of blocks, at channel counts that are
and are not whole lane blocks, at the seeded extremes of ``dt A``; a batch of
rows each from a zero state; the whole model against the plain reference on
seeded weights (logits, loss, first gradient by leaf, three AdamW steps);
each fault of the reference is another function; the memory's and the shared
K/V's gradients are sums over their readers; ``lambda_0`` follows the
published index; the workload trains through ``DDPTrainer.step``.

One module-scoped fixture holds the weights, the tokens, the reference's loss
and gradients and the program's: every comparison reads them (PERF.md section
7 item 27: a result computed once a module).
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.phi4_flash import (
    Block, Phi4Flash, Phi4FlashConfig, band_waste, kind_of, lambda_init, stateful_loss,
)
from adapcc_tpu.ops.selective_scan import plan_for, selective_scan
from adapcc_tpu.utils.observability import default_registry
from chipbench import weights_sambay_lm
from chipbench.reference import phi4_flash_ref

CFG = Phi4FlashConfig.tiny()
PROD = phi4_flash_ref._product("float32")
OPT = {"clip_norm": 1.0, "learning_rate": 1e-3, "weight_decay": 0.01, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
REAL = Path(__file__).resolve().parents[1] / "chipbench/configs/phi4-mini-flash-vp8.json"


def file_config(cfg: Phi4FlashConfig = CFG) -> dict:
    """The configuration as the benchmark's file states it."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_attention_heads, "num_key_value_heads": cfg.num_key_value_heads,
        "sliding_window": cfg.sliding_window, "layer_norm_eps": cfg.layer_norm_eps, "layers_held": list(cfg.held),
        "num_hidden_layers": len(cfg.held), "published": {"num_hidden_layers": cfg.num_hidden_layers},
        "assumed": {"mamba": {
            "d_state": cfg.mamba_d_state, "d_conv": cfg.mamba_d_conv, "expand": cfg.mamba_expand, "dt_rank": cfg.dt_rank,
        }},
    }


def seeded(seed: int = 5):
    """The seed's weights with every vector (biases, norms, ``D``, ``lambda``)
    moved off its 0 or 1, so that each takes part in what is compared."""
    params = weights_sambay_lm.make_params(seed, file_config())
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else x for x, k in zip(leaves, keys)]
    )


def there(params):
    return weights_sambay_lm.published_order(params, file_config())


@pytest.fixture(scope="module")
def world():
    """Weights by the seed, tokens, and both sides' loss and gradients on them."""
    params = seeded()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 40)), jnp.int32)
    # one compiled reference for the sound numbers and for every fault: a fault is a few numbers it is given
    by_knob = jax.jit(lambda p, t, knob: phi4_flash_ref.loss_and_grads(p, t, file_config(), knob=knob))
    reference = by_knob(there(params), tokens, phi4_flash_ref.knobs(file_config()))
    program = {
        loss: jax.jit(jax.value_and_grad(stateful_loss(Phi4Flash(CFG), loss, block=64), has_aux=True))(
            params, (), tokens
        ) for loss in ("dense", "chunked")
    }
    return {"params": params, "tokens": tokens, "reference": reference, "program": program, "by_knob": by_knob}


# --- the kernel --------------------------------------------------------------


def scan_inputs(T, seed, rate, B=1, C=128, N=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (B, T, C)).astype(dtype)
    lo, hi = {"near-one": (1e-5, 1e-3), "near-zero": (1.0, 8.0), "seeded": (1e-3, 0.1)}[rate]
    dt = jnp.exp(jax.random.uniform(ks[1], (B, T, C), minval=math.log(lo), maxval=math.log(hi)))
    # the seeded A: -(1 .. N) along the state axis, so dt A runs from -0.001 to -1.6 a step at the seeded extremes
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (C, N)) * jnp.exp(0.1 * jax.random.normal(ks[2], (C, 1)))
    Bm, Cm = jax.random.normal(ks[3], (B, T, N)).astype(dtype), jax.random.normal(ks[4], (B, T, N)).astype(dtype)
    D = jax.random.normal(ks[5], (C,))
    return (x, dt, A, Bm, Cm, D), jax.random.normal(ks[6], (B, T, C))


def recurrence(x, dt, A, B, C, D):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    return jnp.stack([
        phi4_flash_ref.selective_recurrence(f32(x[b]), dt[b], A, f32(B[b]), f32(C[b]), D, PROD) for b in range(x.shape[0])
    ])


# (T, rate, rows of the batch, channels, states, what of y is weighed): float32 rows come in blocks of 256 at most, in
# whole sublane tiles of 8; channels in lane tiles of 128, four at most to a grid step
SCANS = {
    "under-a-block-one-lane-tile": (40, "seeded", 1, 128, 16, "all"),
    "under-a-block-near-one": (40, "near-one", 1, 128, 16, "all"),
    "under-a-block-near-zero": (44, "near-zero", 1, 128, 16, "all"),
    "two-blocks-whole": (512, "seeded", 1, 128, 16, "all"),
    "two-blocks-less-a-part-channels-no-lane-tile": (300, "seeded", 1, 200, 16, "all"),
    "five-lane-tiles-one-to-a-grid-step": (40, "seeded", 1, 640, 4, "all"),
    "six-lane-tiles-three-to-a-grid-step-two-rows": (40, "seeded", 2, 768, 4, "all"),
    # only the second block's output is weighed: what the first block's inputs get back came through the state alone
    "through-the-state-a-block-hands-on": (300, "near-one", 1, 128, 16, "last-block"),
}


@pytest.mark.parametrize("case", list(SCANS))
def test_the_scan_kernel_is_the_recurrence_forward_and_in_every_gradient(case):
    """Tolerance: both sides are float32 and walk the same steps in the same
    order; the kernel's sums over states and channels run in another order
    and its ``B`` and ``C`` pass through a one-hot product at full precision:
    2e-5 of the largest value compared (``dA`` sums 300 steps of products:
    1e-4)."""
    T, rate, B, C, N, weighed = SCANS[case]
    args, mix = scan_inputs(T, T + C, rate, B=B, C=C, N=N)
    if weighed == "last-block":
        mix = mix * (jnp.arange(T) >= 256)[None, :, None]
    got, want = selective_scan(*args), recurrence(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: jnp.sum(selective_scan(*a) * mix), argnums=tuple(range(6)))(*args)
    wants = jax.grad(lambda *a: jnp.sum(recurrence(*a) * mix), argnums=tuple(range(6)))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), grads, wants):
        rel = 1e-4 if name == "A" else 2e-5
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6 + rel * float(jnp.abs(b).max()), err_msg=f"d{name}"
        )
    if weighed == "last-block":
        assert float(jnp.abs(grads[0][:, :256]).max()) > 1e-3       # and it is not nothing


def test_bfloat16_rows_come_in_tiles_of_sixteen_and_the_state_stays_float32():
    """``x``, ``B``, ``C`` in bfloat16 as the model hands them: the kernel's
    own arithmetic is float32, so against the recurrence on the same rounded
    inputs what is left is ``y``'s own rounding to bfloat16 (2^-9 of it)."""
    args, mix = scan_inputs(40, 3, "seeded", dtype=jnp.bfloat16)
    assert plan_for(40, 128, 16, jnp.bfloat16)[0].rows == 48 and plan_for(40, 128, 16, jnp.float32)[0].rows == 40
    got, want = selective_scan(*args), recurrence(*args)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=2.0 ** -8 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: jnp.sum(selective_scan(*a).astype(jnp.float32) * mix), argnums=(0, 1, 3))(*args)
    wants = jax.grad(lambda *a: jnp.sum(recurrence(*a) * mix), argnums=(0, 1, 3))(*args)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32, jnp.bfloat16]
    for a, b in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2.0 ** -7 * float(jnp.abs(b.astype(jnp.float32)).max()))


def test_the_blocks_follow_the_shape_and_the_scan_leaves_its_gauges():
    plan, T, C = plan_for(8192, 5120, 16, jnp.bfloat16)          # the cell's: 32 blocks of rows, ten of channels
    assert (plan.rows, plan.group, plan.width, plan.tiles, T, C) == (256, 16, 512, 4, 8192, 5120)
    assert plan_for(300, 200, 16, jnp.float32)[1:] == (512, 256) and plan_for(300, 200, 16, jnp.float32)[0].width == 256
    assert plan_for(40, 640, 4, jnp.float32)[0].width == 128      # five lane tiles: one to a grid step
    args, _ = scan_inputs(300, 1, "seeded", B=2, C=200)
    selective_scan(*args)
    gauges = default_registry().snapshot()["gauges"]
    assert (gauges["sscan.chunk"], gauges["sscan.tiles"], gauges["sscan.padded_rows"], gauges["sscan.lane_block"]) == (256, 4, 212, 256)
    with pytest.raises(ValueError, match="selective_scan shapes"):
        selective_scan(args[0], args[1], args[2].T, *args[3:])


def test_a_batch_of_rows_scans_each_from_a_zero_state_and_a_packed_join_resets_nothing():
    args, _ = scan_inputs(300, 11, "near-one", B=2)
    x, dt, A, Bm, Cm, D = args
    both = selective_scan(*args)
    alone = selective_scan(x[1:], dt[1:], A, Bm[1:], Cm[1:], D)
    np.testing.assert_allclose(np.asarray(both[1:]), np.asarray(alone), atol=1e-6)
    # the second half of a row is not the same rows scanned from zero: the state runs across
    tail = selective_scan(x[:1, 150:], dt[:1, 150:], A, Bm[:1, 150:], Cm[:1, 150:], D)
    assert float(jnp.abs(both[:1, 150:] - tail).max()) > 1e-2


# --- the model ---------------------------------------------------------------


def test_the_weight_maker_makes_the_tree_the_model_reads_and_the_published_index_places_the_kinds(world):
    shapes = jax.eval_shape(Phi4Flash(CFG).init, jax.random.PRNGKey(0), world["tokens"])
    assert jax.tree_util.tree_map(lambda x: x.shape, shapes) == jax.tree_util.tree_map(lambda x: x.shape, world["params"])
    assert CFG.kinds == ("M", "S", "M*", "F", "G", "X", "G", "X") == weights_sambay_lm.layer_kinds(file_config())
    published = [kind_of(i, 32) for i in range(32)]
    assert {k: published.count(k) for k in set(published)} == {"M": 8, "S": 8, "M*": 1, "F": 1, "G": 7, "X": 7}
    assert (published[16], published[17], published[18], published[19], published[15]) == ("M*", "F", "G", "X", "S")
    assert [kind_of(i, 32) for i in range(32)] == [weights_sambay_lm.kind_of(i, 32) for i in range(32)]
    mixer = world["params"]["params"]["layers_0"]["mixer"]
    assert mixer["A_log"].shape == (64, 4) and mixer["x_proj"]["kernel"].shape == (64, 2 + 8) and mixer["dt_proj"].shape == (2, 64)
    assert set(world["params"]["params"]["layers_4"]["mixer"]) == {"in_proj", "out_proj"}                      # G
    assert "qkv_proj" not in world["params"]["params"]["layers_5"]["mixer"]                                    # X


def test_lambda_0_follows_the_published_index_under_layers_held(world):
    """With the four learned vectors at zero ``lambda`` is ``lambda_0`` of the
    layer's published index, whatever place it has among the layers run."""
    def flat(params):
        layers = {}
        for name, layer in params["params"].items():
            mixer = layer.get("mixer", {}) if isinstance(layer, dict) else {}
            layers[name] = {**layer, "mixer": {k: jnp.zeros_like(v) if k.startswith("lambda_") else v for k, v in mixer.items()}} if mixer else layer
        return {"params": layers}

    _, sown = Phi4Flash(CFG).apply(flat(world["params"]), world["tokens"][:1, :16], mutable=["intermediates"])
    lambdas = {int(name[7:]): float(layer["mixer"]["lambda"][0]) for name, layer in sown["intermediates"].items()}
    want = {place: 0.8 - 0.6 * math.exp(-0.3 * index) for place, index in ((1, 1), (3, 7), (5, 9), (7, 11))}
    assert lambdas == pytest.approx(want, rel=1e-6)
    real = Phi4FlashConfig.from_config(json.loads(REAL.read_text()), num_hidden_layers=32)
    assert [i for i, kind in zip(real.held, real.kinds) if kind in "SFX"] == [1, 17, 19]
    assert lambda_init(0) == pytest.approx(0.2) and phi4_flash_ref.lambda_init(17) == lambda_init(17)


def test_logits_match_the_plain_reference(world):
    """Tolerance: float32 both sides; the order of summation is what differs
    (the flash kernels' online softmax, the scan's sums): 5e-6 where the
    logits reach 0.5."""
    logits = Phi4Flash(CFG).apply(world["params"], world["tokens"])
    reference = jax.jit(lambda p, row: phi4_flash_ref.logits_fn(p, row, file_config()))
    want = jnp.stack([reference(there(world["params"]), row) for row in world["tokens"]])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=5e-6)
    assert float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("loss", ["dense", "chunked"])
def test_loss_and_every_gradient_leaf_match_the_plain_reference(world, loss):
    """Tolerance: float32 both sides, products at full precision; what is
    left is the order of summation (the scan kernel, the flash kernels'
    online softmax, the fused loss): 5e-4 of a leaf's largest entry.  The
    attention projections' leaves are compared through the permutation."""
    (value, state), grads = world["program"][loss]
    want, want_grads = world["reference"]
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    assert state == ()                                         # nothing carried from step to step
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(there(grads)), jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, jax.tree_util.keystr(path)
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
    rounded = jax.jit(lambda p, t: phi4_flash_ref.loss_and_grads(p, t, file_config(), "bfloat16"))(
        there(world["params"]), world["tokens"]
    )
    program = correct.worst_leaf_gap(np.asarray(leaf_norms(world["program"]["dense"][1])), want)
    control = correct.worst_leaf_gap(np.asarray(leaf_norms(rounded[1])), want)
    assert program < 2e-4 < 2e-3 < control, (program, control)


def test_three_adamw_steps_follow_the_plain_reference(world):
    """The program's own optimizer chain (optax, clipped AdamW) on the
    model's stateful loss against the reference's own AdamW, three steps on
    three batches: each loss, and every leaf's change (a norm is the same in
    either column order; each key's bias a leaf of its own, as the reference
    counts them)."""
    import optax

    params, rows = world["params"], np.random.default_rng(7).integers(0, CFG.vocab_size, (3, 2, 40)).astype(np.int32)
    make = lambda: there(seeded())  # noqa: E731
    want = phi4_flash_ref.train_steps(make(), rows, file_config(), OPT, make)
    tx = optax.chain(
        optax.clip_by_global_norm(OPT["clip_norm"]),
        optax.adamw(OPT["learning_rate"], b1=OPT["b1"], b2=OPT["b2"], eps=OPT["eps"], weight_decay=OPT["weight_decay"]),
    )
    loss_fn = stateful_loss(Phi4Flash(CFG))

    @jax.jit
    def step(p, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, (), batch)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    p, opt_state, losses = params, tx.init(params), []
    for batch in rows:
        p, opt_state, loss = step(p, opt_state, jnp.asarray(batch))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, np.asarray(want["losses"]), rtol=2e-6)
    moved = weights_sambay_lm.key_bias_apart(jax.tree_util.tree_map(jnp.subtract, p, params), file_config())
    # a key's bias takes no gradient: AdamW steps it by rounding's sign, on either side (train_sambay_lm.compare)
    held = [not jax.tree_util.keystr(path).endswith("['qkv_proj']['bias'][1]") for path, _ in jax.tree_util.tree_leaves_with_path(moved)]
    assert held.count(False) == 2
    np.testing.assert_allclose(
        np.asarray(phi4_flash_ref.leaf_norms(moved))[held], np.asarray(want["update_norms"])[held], rtol=2e-3
    )


@pytest.mark.parametrize("fault", [f for f in phi4_flash_ref.FAULTS if f])
def test_each_fault_of_the_reference_is_another_function(world, fault):
    """The controls ``correct`` has to fail are the reference with a few
    numbers changed: each moves the first gradient, leaf by leaf, by a
    hundred times what separates the program from the reference (``kv_own``
    leaves the forward as it is and moves the gradient alone)."""
    from chipbench import correct
    from chipbench.reference.gpt2_ref import leaf_norms

    cfg = file_config()
    loss, grads = world["by_knob"](there(world["params"]), world["tokens"], phi4_flash_ref.knobs(cfg, fault))
    want_loss, want = world["reference"]
    assert correct.worst_leaf_gap(np.asarray(leaf_norms(grads)), np.asarray(leaf_norms(want))) > 2e-2
    assert (float(loss) == float(want_loss)) == (fault == "kv_own")
    with pytest.raises(ValueError, match="fault"):
        phi4_flash_ref.knobs(cfg, "no_such_fault")


def test_the_memorys_and_the_shared_kvs_gradients_are_sums_over_their_readers(world):
    """The tiny model's last four layers are ``G, X, G, X``.  Handed one
    memory and one K/V, what the two get back is the sum of what each reader
    gets back when it is handed a copy of its own; no reader's part is zero,
    and the parts differ, so a sum of one would not pass for it."""
    params, T = world["params"]["params"], 24
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    h = jax.random.normal(ks[0], (1, T, CFG.hidden_size))
    m = jax.random.normal(ks[1], (1, T, CFG.d_inner))
    kv = (
        jax.random.normal(ks[2], (1, T, 1, CFG.head_dim)), jax.random.normal(ks[3], (1, T, 1, CFG.head_dim)),
        jax.random.normal(ks[4], (1, T, 1, 2 * CFG.head_dim)),
    )
    mix = jax.random.normal(ks[5], h.shape)
    assert CFG.kinds[4:] == ("G", "X", "G", "X")

    def tail(h, handed):
        """Layers 4-7, layer ``i`` handed ``handed[i - 4]``."""
        for i, carried in zip(range(4, 8), handed):
            h, _ = Block(CFG, CFG.kinds[i], CFG.held[i]).apply({"params": params[f"layers_{i}"]}, h, carried)
        return jnp.sum(h * mix)

    shared = jax.jit(jax.grad(lambda m, kv: tail(h, [{"m": m, "kv": kv}] * 4), argnums=(0, 1)))(m, kv)
    own = jax.jit(jax.grad(
        lambda m1, kv1, m2, kv2: tail(h, [{"m": m1}, {"kv": kv1}, {"m": m2}, {"kv": kv2}]), argnums=(0, 1, 2, 3)
    ))(m, kv, m, kv)
    np.testing.assert_allclose(np.asarray(shared[0]), np.asarray(own[0] + own[2]), atol=1e-6)
    for whole, a, b in zip(shared[1], own[1], own[3]):
        np.testing.assert_allclose(np.asarray(whole), np.asarray(a + b), atol=1e-6)
        assert min(float(jnp.abs(a).max()), float(jnp.abs(b).max())) > 1e-5 and float(jnp.abs(a - b).max()) > 1e-5
    assert min(float(jnp.abs(own[0]).max()), float(jnp.abs(own[2]).max())) > 1e-5
    # and in the whole model the makers' own leaves carry it: the reference's gradient of M*'s and F's projections,
    # which test_loss_and_every_gradient_leaf holds the program to, is not what a reader less leaves (kv_own)
    assert band_waste(8192, 512, jnp.bfloat16, 64) == pytest.approx(2.0, rel=1e-3)


def test_the_config_reads_config_json_and_refuses_what_it_does_not_implement():
    body = json.loads(REAL.read_text())
    cfg = Phi4FlashConfig.from_config(body, num_hidden_layers=body["published"]["num_hidden_layers"])
    assert cfg.kinds == ("M", "S", "M*", "F", "G", "X") and cfg.held == (0, 1, 16, 17, 18, 19)
    assert (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.head_dim, cfg.sliding_window) == (2560, 5120, 16, 160, 64, 512)
    assert Phi4FlashConfig().kinds.count("G") == 7 and len(Phi4FlashConfig().held) == 32
    for bad in ({"mb_per_layer": 1}, {"tie_word_embeddings": False}, {"mlp_bias": True}, {"hidden_act": "gelu"}):
        with pytest.raises(ValueError, match="phi4flash"):
            Phi4FlashConfig.tiny(**bad)
    with pytest.raises(ValueError, match="reads what no"):
        Phi4FlashConfig.tiny(layers_held=(0, 1, 8, 9))              # G and X with no M* and no F before them
    with pytest.raises(ValueError, match="layers_held"):
        Phi4FlashConfig.tiny(layers_held=(1, 0))
    with pytest.raises(ValueError, match="remat"):
        Phi4FlashConfig.tiny(remat="some")
    with pytest.raises(ValueError, match="pairs"):
        Phi4FlashConfig.tiny(num_attention_heads=2, num_key_value_heads=1)


def test_a_recomputed_block_hands_on_what_it_carries(world):
    """``nn.remat(Block)`` still wraps one layer though it takes and returns
    the memory and the K/V beside the stream: the same loss and gradients."""
    cfg = Phi4FlashConfig.tiny(remat="dots")
    (value, _), grads = jax.jit(jax.value_and_grad(stateful_loss(Phi4Flash(cfg)), has_aux=True))(
        world["params"], (), world["tokens"]
    )
    (want, _), want_grads = world["program"]["dense"]
    assert float(value) == pytest.approx(float(want), rel=1e-6)
    for got, ref in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6 + 1e-4 * float(jnp.abs(ref).max()))


def test_the_workload_trains_through_ddptrainer(capsys):
    from adapcc_tpu.workloads.train_phi4_flash import build_parser, run

    report = {}
    args = ["--epochs", "2", "--world", "2", "--corpus-tokens", "4096", "--lr", "1e-2"]     # eight steps an epoch
    first, last = run(build_parser().parse_args(args), report)
    assert last < first - 0.2, (first, last)
    out = capsys.readouterr().out
    assert "phi4_flash:" in out and "(4, 'M*')" in out and "(7, 'X')" in out
    assert report["state"].model_state == ()
    assert report["trainer"].donate_state is True
