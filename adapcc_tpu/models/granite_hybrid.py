"""Granite 4.0-H's block (``model_type`` ``granitemoehybrid`` with no
experts): Mamba-2 state-space layers nine to one with grouped-query attention
that carries no positions, a dense gated MLP in every layer, scaled residuals,
a scaled embedding and scaled logits over a tied head.

Written from the published ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json)
and the layer equations docs/GRANITE_HYBRID.md states; the fields of
:class:`GraniteHybridConfig` are that file's keys.  The two mixers share no
code with each other: the Mamba-2 mixer is a recurrence over a ``[64, 128]``
state a head (:mod:`adapcc_tpu.ops.ssd`), the attention layer a causal softmax
at head size 64 with four query heads to a K/V head
(:mod:`adapcc_tpu.ops.flash_attention`, its scale the configuration's
``attention_multiplier``, not ``1 / sqrt(d)``).  Norm, gated MLP, the remat
table and the taps' and the decay's initialisation are
:mod:`adapcc_tpu.models.lm`'s, the short convolution with its bias and silu
one kernel (:mod:`adapcc_tpu.ops.short_conv`).

Four scalings no other model here has: ``h = embedding_multiplier E[ids]``;
both branches of a layer enter the stream times ``residual_multiplier``; the
attention scores times ``attention_multiplier``; ``logits = norm(h) E^T /
logits_scaling`` through the embedding itself (``tie_word_embeddings``), whose
gradient is therefore a sum of two.

The model returns ``(logits, decay_floor)``: the second is the smallest decay
any chunk of any Mamba-2 layer's scan laid on the state it was handed
(:func:`adapcc_tpu.ops.ssd.chunk_decay_floor`), which :func:`stateful_loss`
hands out beside the loss.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from adapcc_tpu.models.lm import (
    REMAT, GatedMLP, RMSNorm, a_log_init, dense, dt_bias_init, next_token_loss, taps_init,
)
from adapcc_tpu.utils.observability import default_registry

_PUBLISHED_LAYERS = tuple("attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192   # the MLP of every layer (no experts beside it)
    num_hidden_layers: int = 40
    #: the mixer of each layer, as published; the first ``num_hidden_layers`` are run
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256            # the published kernel's chunk; ``ops/ssd.chunk_plan`` chooses this one's
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if (
            self.num_local_experts or self.mamba_n_groups != 1 or self.position_embedding_type != "nope"
            or self.hidden_act != "silu" or not self.tie_word_embeddings or not self.mamba_conv_bias
            or self.mamba_proj_bias or self.attention_bias
            or self.mamba_expand * self.hidden_size != self.mamba_n_heads * self.mamba_d_head
        ):
            raise ValueError(
                "only the published granitemoehybrid settings are implemented: no experts, one group of B and C, "
                "no positions, silu, a tied head, a biased convolution and no other bias, "
                "mamba_expand * hidden_size = mamba_n_heads * mamba_d_head"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        if self.num_attention_heads % self.num_key_value_heads or self.hidden_size % self.num_attention_heads:
            raise ValueError(f"heads {self.num_attention_heads} over {self.num_key_value_heads} of {self.hidden_size}")
        self.kinds   # every layer run has a known mixer

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"mamba"`` or ``"attention"`` for each layer run here: the first
        ``num_hidden_layers`` of ``layer_types``."""
        kinds = tuple(self.layer_types[:self.num_hidden_layers])
        if len(kinds) != self.num_hidden_layers or set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {self.layer_types} for {self.num_hidden_layers} layers")
        return kinds

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "GraniteHybridConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(GraniteHybridConfig.__dataclass_fields__)
        fields = {k: v for k, v in config.items() if k in names}
        fields["layer_types"] = tuple(fields["layer_types"])
        fields.update(program)
        return GraniteHybridConfig(**fields)

    @staticmethod
    def tiny(**over) -> "GraniteHybridConfig":
        """Test-sized: two Mamba-2 layers, an attention layer and a Mamba-2
        one; both mixers' kernels run (in the interpreter off the chip)."""
        base = dict(
            vocab_size=256, hidden_size=32, shared_intermediate_size=64, num_hidden_layers=4,
            layer_types=("mamba", "mamba", "attention", "mamba"), num_attention_heads=4, num_key_value_heads=2,
            attention_multiplier=0.2, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, dtype=jnp.float32,
        )
        base.update(over)
        return GraniteHybridConfig(**base)


class Mamba2Mixer(nn.Module):
    """One projection split three ways (gate, convolved channels, step
    sizes), a biased causal convolution over ``x``, ``B`` and ``C`` together,
    the state-space scan, and a norm over all ``d_inner`` channels of the
    output gated *before* it is normed.  Returns ``(out, decay_floor)``."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u):
        from adapcc_tpu.ops.short_conv import short_conv
        from adapcc_tpu.ops.ssd import chunk_decay_floor, ssd

        cfg = self.cfg
        H, N, d_in = cfg.mamba_n_heads, cfg.mamba_d_state, cfg.d_inner
        conv = d_in + 2 * N
        z, xBC, dt = jnp.split(dense(d_in + conv + H, cfg, "in_proj")(u), [d_in, d_in + conv], axis=-1)
        with jax.named_scope("ssd_conv"):
            taps = self.param("conv_taps", taps_init, (cfg.mamba_d_conv, conv))
            bias = self.param("conv_bias", nn.initializers.zeros, (conv,))
            xBC = short_conv(xBC, taps, bias)       # bias and silu in the kernel
        x, B, C = jnp.split(xBC, [d_in, d_in + N], axis=-1)
        with jax.named_scope("ssd_gate"):
            A = -jnp.exp(self.param("A_log", a_log_init, (H,)).astype(jnp.float32))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param("dt_bias", dt_bias_init, (H,)))
            floor = chunk_decay_floor(dt, A)
        with jax.named_scope("ssd_scan"):
            y = ssd(x, dt, A, B, C, self.param("D", nn.initializers.ones, (H,)))
        y = RMSNorm(cfg.rms_norm_eps, name="norm")(y * nn.silu(z))
        return dense(cfg.hidden_size, cfg, "out_proj")(y), floor


class AttentionMixer(nn.Module):
    """Causal grouped-query attention without positions, the scores scaled by
    ``attention_multiplier``."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, u):
        from adapcc_tpu.ops import flash_attention

        cfg = self.cfg
        B, T, _ = u.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = dense(H * D, cfg, "q_proj")(u).reshape(B, T, H, D)
        k = dense(Hkv * D, cfg, "k_proj")(u).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, cfg, "v_proj")(u).reshape(B, T, Hkv, D)
        with jax.named_scope("gqa_attn"):
            o = flash_attention(q, k, v, causal=True, scale=float(cfg.attention_multiplier))
        return dense(cfg.hidden_size, cfg, "o_proj")(o.reshape(B, T, H * D))


class Block(nn.Module):
    """One layer, two norms, both branches scaled: ``h += r mixer(norm(h))``,
    then ``h += r mlp(norm(h))``.  Returns ``(h, decay_floor or None)``."""

    cfg: GraniteHybridConfig
    kind: str

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        r = jnp.asarray(cfg.residual_multiplier, cfg.dtype)
        u = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(h)
        if self.kind == "mamba":
            m, floor = Mamba2Mixer(cfg, name="mixer")(u)
        else:
            m, floor = AttentionMixer(cfg, name="mixer")(u), None
        h = h + r * m
        m = GatedMLP(cfg, cfg.shared_intermediate_size, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        )
        return h + r * m, floor


class GraniteHybrid(nn.Module):
    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits [B, T, vocab] float32, decay_floor
        float32 scalar)``; with ``return_hidden`` the final norm's output over
        ``logits_scaling`` stands in for the logits (its product with the
        embedding is the logits).  No positions anywhere: the state-space
        layers carry the order."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )
        h = embed(tokens) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        floors = []
        for i, kind in enumerate(cfg.kinds):
            h, floor = block(cfg, kind, name=f"layers_{i}")(h)
            if floor is not None:
                floors.append(floor)
        floor = jnp.min(jnp.stack(floors)) if floors else jnp.ones((), jnp.float32)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(h) * jnp.asarray(1.0 / cfg.logits_scaling, cfg.dtype)
        if return_hidden:
            return h, floor
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), embed.embedding.astype(cfg.dtype))
        return logits.astype(jnp.float32), floor


def stateful_loss(model: GraniteHybrid, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: the mean next-token cross-entropy over
    the vocabulary held, through ``gpt2.lm_loss`` (or ``ops/chunked_ce.py``
    with ``loss="chunked"``, the head product fused into the loss) with the
    embedding as the head; the state the step returns is ``{"ssd_decay_floor"}``."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)

    def loss_fn(params, model_state, batch):
        out, floor = model.apply(params, batch, return_hidden=hidden)
        return value(out, params["params"]["embed_tokens"]["embedding"], batch), {"ssd_decay_floor": floor}

    return loss_fn


def initial_model_state():
    """The ``model_state`` a trainer's first state carries: no scan yet."""
    return {"ssd_decay_floor": jnp.ones((), jnp.float32)}


def record_step(model_state, metrics=None) -> None:
    """The per-step sample ``ssd.decay_floor`` from what a compiled step
    returned beside its loss (read after the steps, so that no step waits for
    the host)."""
    (metrics or default_registry()).sample("ssd.decay_floor", float(model_state["ssd_decay_floor"]))
