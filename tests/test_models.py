"""Model zoo sanity: shapes, finiteness, gradient flow, MoE routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from adapcc_tpu.models.moe import MoEConfig, MoEMLP
from adapcc_tpu.models.vgg import VGG, VGG11_CFG
from adapcc_tpu.models.vit import ViT, ViTConfig


def test_gpt2_forward_and_loss():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    tokens = jnp.ones((2, cfg.max_seq), dtype=jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    logits = jax.jit(model.apply)(params, tokens)
    assert logits.shape == (2, cfg.max_seq, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    loss = lm_loss(logits, tokens)
    assert np.isfinite(float(loss))


def _textbook_loss(logits, tokens):
    """The next-token loss as the textbook writes it, in float32: what
    ``lm_loss`` has to equal whatever form it is compiled from."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0])


_LOSS_CASES = {
    # id: (rows, T, vocabulary, logits rounded to bfloat16, a very large logit)
    "fp32": (3, 16, 256, False, False),
    "bf16-rounded": (3, 16, 256, True, False),
    "vocab-not-a-multiple-of-128": (2, 16, 257, True, False),
    "T-2": (4, 2, 130, False, False),
    "T-not-a-multiple-of-8": (2, 13, 384, True, False),
    "very-large-logit": (3, 8, 130, False, True),
}


@pytest.mark.parametrize("case", list(_LOSS_CASES))
def test_lm_loss_is_the_textbook_loss_in_value_and_gradient(case):
    rows, T, vocab, rounded, large = _LOSS_CASES[case]
    rng = np.random.default_rng(len(case))
    logits = rng.normal(scale=4.0, size=(rows, T, vocab)).astype(np.float32)
    tokens = rng.integers(0, vocab, (rows, T)).astype(np.int32)
    if large:
        # exp() of it overflows float32: on the target in one row, off it in another
        logits[0, 2, tokens[0, 3]] = 3.0e4
        logits[1, 2, (tokens[1, 3] + 1) % vocab] = 3.0e4
    logits = jnp.asarray(logits)
    if rounded:  # what the bf16 head hands over, cast to float32
        logits = logits.astype(jnp.bfloat16).astype(jnp.float32)
    tokens = jnp.asarray(tokens)
    got, got_grad = jax.jit(jax.value_and_grad(lm_loss))(logits, tokens)
    want, want_grad = jax.jit(jax.value_and_grad(_textbook_loss))(logits, tokens)
    assert got.dtype == jnp.float32 and np.isfinite(float(got))
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert got_grad.shape == logits.shape and np.isfinite(np.asarray(got_grad)).all()
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad), rtol=2e-5, atol=1e-9)
    assert not np.any(np.asarray(got_grad[:, -1]))  # the last position predicts nothing


def test_trinity_dense_loss_is_the_textbook_loss_of_its_logits():
    from adapcc_tpu.models.trinity import Trinity, TrinityConfig, initial_model_state, stateful_loss

    cfg = TrinityConfig.tiny()
    model = Trinity(cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 24)), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    loss_fn = stateful_loss(model, "dense")
    (got, _), got_grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, initial_model_state(cfg), tokens)
    want, want_grad = jax.jit(jax.value_and_grad(lambda p: _textbook_loss(model.apply(p, tokens)[0], tokens)))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grad), jax.tree_util.tree_leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-7)


#: the language models' entry points (``workloads/train_<name>.py`` over ``models/<name>.py``): the configuration's and the model's class
_LM_ENTRIES = {
    "trinity": ("TrinityConfig", "Trinity"),
    "kimi_linear": ("KimiLinearConfig", "KimiLinear"),
    "joyai_flash": ("JoyAIFlashConfig", "JoyAIFlash"),
    "granite_hybrid": ("GraniteHybridConfig", "GraniteHybrid"),
    "phi4_flash": ("Phi4FlashConfig", "Phi4Flash"),
}
#: the twelve flags of a job, the same in every entry (``train_lm.job_parser``), with the defaults they had before it
_JOB_FLAGS = dict(
    vocab=256, hidden=64, dense_width=128, seq=64, batch=8, corpus_tokens=16384, epochs=2, lr=3e-3, world=None,
    loss="dense", remat="none", dtype="float32",
)


@pytest.mark.parametrize("what", ["flags", "trainer"])
@pytest.mark.parametrize("entry", sorted(_LM_ENTRIES))
def test_every_language_models_entry_keeps_the_jobs_flags_and_builds_the_one_trainer(entry, what, mesh2):
    """The five entries share ``workloads/train_lm.py``: each parser carries
    the job's twelve flags at the defaults it always had, and each
    ``build_trainer(cfg, tx, mesh)`` (the signature the benchmark's runners
    call) gives ``(DDPTrainer, model)``, the trainer with a stateful loss and
    a donated state on a ring of the mesh's size.  Nothing is compiled."""
    import importlib

    import optax

    from adapcc_tpu.ddp import DDPTrainer

    module = importlib.import_module(f"adapcc_tpu.workloads.train_{entry}")
    if what == "flags":
        parser = module.build_parser()
        args = parser.parse_args([])
        assert {name: getattr(args, name) for name in _JOB_FLAGS} == _JOB_FLAGS
        choices = {a.dest: a.choices for a in parser._actions if a.choices}
        assert (choices["loss"], choices["remat"], choices["dtype"]) == (
            ("dense", "chunked"), ("none", "dots", "full"), ("float32", "bfloat16")
        )
        return
    models = importlib.import_module(f"adapcc_tpu.models.{entry}")
    config, model = (getattr(models, name) for name in _LM_ENTRIES[entry])
    cfg, tx = config.tiny(), optax.sgd(0.1)
    trainer, built = module.build_trainer(cfg, tx, mesh2)
    assert isinstance(trainer, DDPTrainer) and isinstance(built, model) and built.cfg is cfg
    assert trainer.stateful_loss is True and trainer.donate_state is True
    strategy = trainer.hook.strategy
    assert (strategy.synthesis, strategy.world_size) == ("ring", int(mesh2.devices.size))
    assert module.build_trainer(cfg, tx, mesh2, loss="chunked", donate_state=False)[0].donate_state is False
    with pytest.raises(ValueError, match="not in \\('dense', 'chunked'\\)"):
        module.build_trainer(cfg, tx, mesh2, loss="fused")


def test_pipeline_last_stage_loss_is_the_textbook_loss_of_its_logits():
    from adapcc_tpu.pipe.partition import composed_loss, partition_gpt2, split_params

    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (3, 13), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    part = partition_gpt2(cfg, 2)
    # every stage in order; the last applies the head and lm_loss
    got, got_grad = jax.jit(
        jax.value_and_grad(lambda p: composed_loss(cfg, part, split_params(p, part), tokens))
    )(params)
    want, want_grad = jax.jit(jax.value_and_grad(lambda p: _textbook_loss(model.apply(p, tokens), tokens)))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grad), jax.tree_util.tree_leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-7)


@pytest.mark.slow
def test_gpt2_gradients_nonzero():
    cfg = GPT2Config.tiny()
    model = GPT2(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    # shorter than max_seq exercises position-embedding slicing
    params = model.init(jax.random.PRNGKey(0), tokens)
    g = jax.grad(lambda p: lm_loss(model.apply(p, tokens), tokens))(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(n > 0 for n in norms) > len(norms) * 0.8


def test_gpt2_remat_variant_matches():
    cfg = GPT2Config.tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, cfg.vocab_size)
    params = jax.jit(GPT2(cfg).init)(jax.random.PRNGKey(0), tokens)
    import dataclasses

    cfg_r = dataclasses.replace(cfg, remat=True)
    out_a = jax.jit(GPT2(cfg).apply)(params, tokens)
    out_b = jax.jit(GPT2(cfg_r).apply)(params, tokens)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), rtol=1e-5)


@pytest.mark.slow
def test_vgg_forward():
    model = VGG(cfg=VGG11_CFG, num_classes=10, classifier_width=64)
    x = jnp.ones((2, 32, 32, 3))
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 10)
    assert np.isfinite(np.asarray(out)).all()


def test_vit_forward():
    cfg = ViTConfig.tiny()
    model = ViT(cfg)
    x = jnp.ones((2, cfg.image_size, cfg.image_size, 3))
    params = jax.jit(model.init)(jax.random.PRNGKey(0), x)
    out = jax.jit(model.apply)(params, x)
    assert out.shape == (2, cfg.num_classes)


def test_moe_forward_and_aux_loss():
    cfg = MoEConfig.tiny()
    model = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, cfg.d_model))
    params = jax.jit(model.init)(jax.random.PRNGKey(1), x)
    y, aux = jax.jit(model.apply)(params, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    # balanced-ish routing on random inputs: aux loss near 1 (perfect balance
    # gives exactly 1.0 for the switch formulation)
    assert 0.5 < float(aux) < cfg.num_experts


def test_moe_tokens_actually_routed():
    cfg = MoEConfig.tiny()
    model = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model))
    params = jax.jit(model.init)(jax.random.PRNGKey(4), x)
    y, _ = jax.jit(model.apply)(params, x)
    # output differs from input (experts transformed it) and is token-dependent
    assert not np.allclose(np.asarray(y), np.asarray(x))
    assert np.asarray(y).std(axis=1).mean() > 0


def test_moe_gradients_flow_to_experts():
    cfg = MoEConfig.tiny()
    model = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, cfg.d_model))
    params = jax.jit(model.init)(jax.random.PRNGKey(6), x)

    def loss(p):
        y, aux = model.apply(p, x)
        return jnp.mean(y**2) + 0.01 * aux

    g = jax.jit(jax.grad(loss))(params)
    w1g = g["params"]["w1"]
    assert float(jnp.linalg.norm(w1g)) > 0


@pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
def test_gpt2_remat_policies_match(policy):
    """Policy-based remat changes the memory/FLOP trade, not the function:
    forward and gradients equal the non-remat model."""
    import dataclasses

    cfg = GPT2Config.tiny()
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0, cfg.vocab_size)
    params = jax.jit(GPT2(cfg).init)(jax.random.PRNGKey(0), tokens)
    cfg_r = dataclasses.replace(cfg, remat=True, remat_policy=policy)
    out_a = jax.jit(GPT2(cfg).apply)(params, tokens)
    out_b = jax.jit(GPT2(cfg_r).apply)(params, tokens)
    # bf16 activations: what a dots policy *recomputes* in backward/refused
    # fusions may re-round differently from the saved value, so equality
    # holds only to bf16 resolution (~2^-8), not fp32 eps
    tol = 1e-2 if cfg.dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b), atol=tol)
    ga = jax.jit(jax.grad(lambda p: lm_loss(GPT2(cfg).apply(p, tokens), tokens)))(params)
    gb = jax.jit(jax.grad(lambda p: lm_loss(GPT2(cfg_r).apply(p, tokens), tokens)))(params)
    # gradients compare RELATIVELY (bf16 re-rounding scales with magnitude;
    # a flat atol=1e-2 would pass 100%-wrong small gradients), with an
    # absolute floor of one bf16 ulp-at-1 (2^-8) for near-zero leaves
    rtol, atol = (2e-2, 4e-3) if cfg.dtype == jnp.bfloat16 else (1e-6, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)


def test_gpt2_remat_policy_validated():
    import dataclasses

    cfg = dataclasses.replace(GPT2Config.tiny(), remat=True, remat_policy="bogus")
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="remat_policy"):
        GPT2(cfg).init(jax.random.PRNGKey(0), tokens)


@pytest.mark.slow
def test_moe_router_z_loss():
    """z-loss adds coef·mean(logsumexp²) to the aux term and is disabled at
    coef 0; the EP shard path reports the same global value."""
    import dataclasses

    from adapcc_tpu.models.moe import MoEConfig, MoEMLP

    cfg0 = dataclasses.replace(MoEConfig.tiny(), router_z_coef=0.0)
    cfg1 = dataclasses.replace(MoEConfig.tiny(), router_z_coef=0.1)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 32)), jnp.float32)
    params = MoEMLP(cfg0).init(jax.random.PRNGKey(0), x)
    y0, aux0 = MoEMLP(cfg0).apply(params, x)
    y1, aux1 = MoEMLP(cfg1).apply(params, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1))  # output unchanged
    assert float(aux1) > float(aux0)  # logsumexp² penalty is positive

    # EP shard path matches the single-device aux (same global mean);
    # top_k=1 keeps the EP program's unrolled dispatch small — the parity
    # claim (z-loss pmean across shards) is top_k-independent
    from jax.sharding import Mesh

    from adapcc_tpu.parallel import expert_parallel_moe

    cfg_ep = dataclasses.replace(cfg1, top_k=1)
    _, aux_ref = MoEMLP(cfg_ep).apply(params, x)
    mesh = Mesh(np.array(jax.devices()[:4]), ("experts",))
    _, aux_ep = expert_parallel_moe(
        params, x.reshape(-1, cfg_ep.d_model), cfg_ep, mesh
    )
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


# -- ResNet (reference main_elastic.py --arch resnet18/50) ---------------------


def test_resnet_forward_group_and_batch_norm():
    # two-stage tiny net: same block/norm/shortcut code paths as ResNet18
    # at a fraction of the CPU compile cost (the full-width archs are
    # covered shape-only below)
    from adapcc_tpu.models.resnet import BasicBlock, ResNet

    x = jnp.ones((2, 16, 16, 3), jnp.float32)
    gn = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, num_classes=10,
                width=8, small_inputs=True, dtype=jnp.float32)
    v = jax.jit(gn.init)(jax.random.PRNGKey(0), x)
    # GroupNorm variant is stateless: params only
    assert set(v.keys()) == {"params"}
    out = jax.jit(gn.apply)(v, x)
    assert out.shape == (2, 10) and out.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(out)))

    bn = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, num_classes=10,
                width=8, small_inputs=True, dtype=jnp.float32, norm="batch")
    vb = jax.jit(lambda key, x: bn.init(key, x, train=True))(jax.random.PRNGKey(0), x)
    assert "batch_stats" in vb
    out_t, upd = jax.jit(lambda v, x: bn.apply(v, x, train=True, mutable=["batch_stats"]))(vb, x)
    assert out_t.shape == (2, 10)
    # train-mode batch statistics actually update the running stats
    before = jax.tree_util.tree_leaves(vb["batch_stats"])
    after = jax.tree_util.tree_leaves(upd["batch_stats"])
    assert any(
        float(np.abs(np.asarray(a) - np.asarray(b)).max()) > 0
        for a, b in zip(after, before)
    )
    out_e = jax.jit(lambda v, x: bn.apply(v, x, train=False))(
        {"params": vb["params"], "batch_stats": upd["batch_stats"]}, x
    )
    assert out_e.shape == (2, 10)


def test_resnet50_bottleneck_forward():
    from adapcc_tpu.models.resnet import Bottleneck, ResNet

    x = jnp.ones((1, 16, 16, 3), jnp.float32)
    m = ResNet(stage_sizes=(1, 1), block_cls=Bottleneck, num_classes=7,
               width=8, small_inputs=True, dtype=jnp.float32)
    v = jax.jit(m.init)(jax.random.PRNGKey(0), x)
    assert jax.jit(m.apply)(v, x).shape == (1, 7)


def test_resnet_param_counts_match_torchvision():
    """Exact structural parity with the reference's torchvision archs
    (main_elastic.py:75 resnet18 default): the BN variants at full width
    reproduce torchvision's published parameter counts to the digit.
    eval_shape only — nothing is materialized."""
    from adapcc_tpu.models.resnet import ResNet18, ResNet50

    for ctor, want in ((ResNet18, 11_689_512), (ResNet50, 25_557_032)):
        mdl = ctor(num_classes=1000, norm="batch")
        shapes = jax.eval_shape(
            lambda k, m=mdl: m.init(k, jnp.ones((1, 224, 224, 3))),
            jax.random.PRNGKey(0),
        )
        n = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(shapes["params"])
        )
        assert n == want


def test_resnet_imagenet_stem_downsamples():
    from adapcc_tpu.models.resnet import BasicBlock, ResNet

    m = ResNet(stage_sizes=(1,), block_cls=BasicBlock, num_classes=5,
               width=8, dtype=jnp.float32)
    x = jnp.ones((1, 64, 64, 3), jnp.float32)
    v = jax.jit(m.init)(jax.random.PRNGKey(0), x)
    assert jax.jit(m.apply)(v, x).shape == (1, 5)


def test_resnet_non_power_of_two_width():
    # C=48 has no 32-group split; the auto norm must pick the largest
    # divisor <= 32 (24) instead of dying inside flax (ADVICE r4)
    from adapcc_tpu.models.resnet import BasicBlock, ResNet

    x = jnp.ones((1, 16, 16, 3), jnp.float32)
    m = ResNet(stage_sizes=(1, 1), block_cls=BasicBlock, num_classes=5,
               width=48, small_inputs=True, dtype=jnp.float32)
    v = jax.jit(m.init)(jax.random.PRNGKey(0), x)
    assert jax.jit(m.apply)(v, x).shape == (1, 5)
