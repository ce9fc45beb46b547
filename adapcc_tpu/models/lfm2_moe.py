"""LFM2-24B-A2B's block (``model_type`` ``lfm2_moe``): a double-gated short
convolution as the mixer of three layers in four, grouped-query attention with
per-head q/k norms and rotary positions on the fourth, a dense gated MLP in
the leading layers and sigmoid-routed sparse experts with no shared one after
them, over a tied head.

Written from the published ``config.json``
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) and the
layer equations docs/LFM2_MOE.md states; the fields of :class:`Lfm2MoeConfig`
are that file's keys (``rope_parameters``' two entries flat).  The
convolution *is* the mixer here, not the front of a scan: ``y = C * conv3(B * x)`` with ``[B, C, x]`` the thirds of one
projection, no bias and no activation, one Pallas kernel each way
(:func:`adapcc_tpu.ops.short_conv.gated_short_conv`, which reads the thirds
where the projection wrote them).  The attention layer is Trinity's form (the
norms, then :func:`adapcc_tpu.models.trinity.rotary`, then
:mod:`adapcc_tpu.ops.flash_attention`) without its gate and with positions on
every such layer.  Norm, gated MLP, the remat table, the taps' initialisation
and the loss's fork are :mod:`adapcc_tpu.models.lm`'s, the expert layer
:func:`adapcc_tpu.models.moe.routed_experts`.

**The share.**  ``layers_held`` names the published layers run here (a
layer's mixer follows ``layer_types[l]``, its feed-forward ``l <
num_dense_layers``); ``experts_held`` and ``expert_offset`` which of the
``num_experts`` routed experts live on this chip.  The router keeps its
published width and its experts per token and routes over all; the layer adds
its own experts' part.  What absent experts would have added is left out:
nothing stands in for the chips that hold them.

The model returns, beside the logits, the assignments each held expert was
given in each expert layer (``[expert layers, experts_held]`` int32), which
:func:`stateful_loss` hands out through ``TrainState.model_state``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from adapcc_tpu.models.lm import REMAT, GatedMLP, RMSNorm, dense, next_token_loss, taps_init
from adapcc_tpu.models.moe import routed_experts
from adapcc_tpu.models.trinity import rotary

_PUBLISHED_LAYERS = tuple("full_attention" if i % 4 == 2 else "conv" for i in range(40))
#: under the sum of a token's chosen scores (``norm_topk_prob``), as the published code has it
TOPK_NORM_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776         # the leading dense layers' MLP
    moe_intermediate_size: int = 1536      # every expert's
    #: the published depth: ``layer_types`` has one entry for each
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None         # None: hidden_size / num_attention_heads
    conv_L_cache: int = 3                  # the convolution's taps
    conv_bias: bool = False
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    # ``rope_parameters``' two entries, flat (a configuration is hashable)
    rope_theta: float = 1000000.0
    rope_type: str = "default"
    tie_word_embeddings: bool = True
    #: the published indices of the layers run here, ascending; None: all
    layers_held: Optional[Tuple[int, ...]] = None
    #: routed experts held here, ``expert_offset … expert_offset + experts_held``
    #: of ``num_experts``; None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if self.conv_bias or not self.tie_word_embeddings or self.rope_type != "default":
            raise ValueError(
                "only the published lfm2_moe settings are implemented: no bias in the convolution, a tied head, "
                "rope_type default"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"heads {self.num_attention_heads} over {self.num_key_value_heads}")
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types} for {self.num_hidden_layers} layers")
        held = self.held_layers
        if list(held) != sorted(set(held)) or not held or held[0] < 0 or held[-1] >= self.num_hidden_layers:
            raise ValueError(f"layers_held {held} of {self.num_hidden_layers} published layers")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(f"experts {self.expert_offset}+{self.held} of {self.num_experts}")

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_hidden_layers)) if self.layers_held is None else tuple(self.layers_held)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The mixer of each layer run here."""
        return tuple(self.layer_types[i] for i in self.held_layers)

    @property
    def sparse(self) -> Tuple[bool, ...]:
        """Whether each layer run here feeds forward through the experts."""
        return tuple(i >= self.num_dense_layers for i in self.held_layers)

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else int(self.experts_held)

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_attention_heads if self.head_dim is None else int(self.head_dim)

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "Lfm2MoeConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(Lfm2MoeConfig.__dataclass_fields__)
        fields = {k: v for k, v in {**config, **config.get("rope_parameters", {})}.items() if k in names}
        fields.update(program)
        fields["layer_types"] = tuple(fields["layer_types"])
        if fields.get("layers_held") is not None:
            fields["layers_held"] = tuple(fields["layers_held"])
        return Lfm2MoeConfig(**fields)

    @staticmethod
    def tiny(**over) -> "Lfm2MoeConfig":
        """Test-sized: eight published layers of which five are run (a dense
        convolution layer, then a period with experts: attention and three
        convolutions), 8 experts top-2, 4 query heads on 2 K/V heads of 8."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=8,
            num_dense_layers=2, layer_types=tuple("full_attention" if i % 4 == 2 else "conv" for i in range(8)),
            layers_held=(1, 2, 3, 4, 5), num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, dtype=jnp.float32,
        )
        base.update(over)
        return Lfm2MoeConfig(**base)


class ShortConvMixer(nn.Module):
    """``(C * conv(B * x)) W_out``, ``[B, C, x] = u W_in``: two gates that
    depend on the data around a causal depthwise convolution of ``conv_L_cache``
    taps, no bias and no activation anywhere."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u):
        from adapcc_tpu.ops.short_conv import gated_short_conv

        cfg = self.cfg
        d = cfg.hidden_size
        with jax.named_scope("gconv_proj"):
            bcx = dense(3 * d, cfg, "in_proj")(u)
        taps = self.param("conv_taps", taps_init, (cfg.conv_L_cache, d))
        with jax.named_scope("gconv"):
            y = gated_short_conv(bcx, taps)
        with jax.named_scope("gconv_proj"):
            return dense(d, cfg, "out_proj")(y)


class Attention(nn.Module):
    """Causal grouped-query attention: q and k normed per head, then rotated
    over the whole head, scores over ``sqrt(head)``."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u):
        from adapcc_tpu.ops import flash_attention

        cfg = self.cfg
        B, T, _ = u.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_size
        q = dense(H * D, cfg, "q_proj")(u).reshape(B, T, H, D)
        k = dense(Hkv * D, cfg, "k_proj")(u).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, cfg, "v_proj")(u).reshape(B, T, Hkv, D)
        q = rotary(RMSNorm(cfg.norm_eps, name="q_layernorm")(q), cfg.rope_theta)
        k = rotary(RMSNorm(cfg.norm_eps, name="k_layernorm")(k), cfg.rope_theta)
        with jax.named_scope("lfm2_attn"):
            o = flash_attention(q, k, v, causal=True)
        return dense(cfg.hidden_size, cfg, "out_proj")(o.reshape(B, T, H * D))


class SparseExperts(nn.Module):
    """The held routed experts' part of a sigmoid top-k layer with no shared
    expert; also returns the assignments each held expert was given."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, d = x.shape
        E, k, held, width = cfg.num_experts, cfg.num_experts_per_tok, cfg.held, cfg.moe_intermediate_size
        tokens = x.reshape(B * T, d)
        with jax.named_scope("moe_route"):
            router = self.param("router", nn.initializers.normal(0.02), (d, E))
            scores = jax.nn.sigmoid(
                jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
            )
            ranked = scores
            if cfg.use_expert_bias:
                # a vector no gradient reaches; its update is the training recipe's
                ranked = scores + jax.lax.stop_gradient(self.param("expert_bias", nn.initializers.zeros, (E,)))
            _, ids = jax.lax.top_k(ranked, k)
            weights = jnp.take_along_axis(scores, ids, axis=-1)
            if cfg.norm_topk_prob:
                weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + TOPK_NORM_EPS)
            weights = weights * cfg.routed_scaling_factor
        init = nn.initializers.normal(0.02)
        stacked = {
            "w1": self.param("experts_w1", init, (held, d, width)),
            "w3": self.param("experts_w3", init, (held, d, width)),
            "w2": self.param("experts_w2", init, (held, width, d)),
        }
        with jax.named_scope("moe_experts"):
            routed, sizes = routed_experts(
                tokens, ids, weights, stacked, offset=cfg.expert_offset, num_experts=E, act=nn.silu, dtype=cfg.dtype,
            )
        return routed.reshape(B, T, d).astype(x.dtype), sizes


class Block(nn.Module):
    """One layer, two norms: ``h += mixer(norm(h))``, then ``h +=
    ffn(norm(h))``.  Returns ``(h, sizes or None)``."""

    cfg: Lfm2MoeConfig
    kind: str
    sparse: bool

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        u = RMSNorm(cfg.norm_eps, name="operator_norm")(h)
        if self.kind == "conv":
            h = h + ShortConvMixer(cfg, name="conv")(u)
        else:
            h = h + Attention(cfg, name="self_attn")(u)
        x = RMSNorm(cfg.norm_eps, name="ffn_norm")(h)
        if self.sparse:
            m, sizes = SparseExperts(cfg, name="feed_forward")(x)
        else:
            m, sizes = GatedMLP(cfg, cfg.intermediate_size, name="feed_forward")(x), None
        return h + m, sizes


class Lfm2Moe(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits [B, T, vocab] float32, sizes [expert
        layers, experts_held] int32)``; with ``return_hidden`` the final
        norm's output stands in for the logits (its product with the
        embedding is the logits)."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )
        h = embed(tokens)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        sizes = []
        for i, (kind, sparse) in enumerate(zip(cfg.kinds, cfg.sparse)):
            h, given = block(cfg, kind, sparse, name=f"layers_{i}")(h)
            if given is not None:
                sizes.append(given)
        h = RMSNorm(cfg.norm_eps, name="embedding_norm")(h)
        sizes = jnp.stack(sizes) if sizes else jnp.zeros((0, cfg.held), jnp.int32)
        if return_hidden:
            return h, sizes
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), embed.embedding.astype(cfg.dtype))
        return logits.astype(jnp.float32), sizes


def stateful_loss(model: Lfm2Moe, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: the mean next-token cross-entropy over
    the vocabulary held, with the embedding as the head (``loss`` "dense":
    float32 logits of the whole batch; "chunked": ``ops/chunked_ce.py``), and
    ``{"moe_sizes": [expert layers, held]}`` as the state the step returns."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)

    def loss_fn(params, model_state, batch):
        out, sizes = model.apply(params, batch, return_hidden=hidden)
        return value(out, params["params"]["embed_tokens"]["embedding"], batch), {"moe_sizes": sizes}

    return loss_fn


def initial_model_state(cfg: Lfm2MoeConfig):
    """The ``model_state`` a trainer's first state carries: no assignments yet."""
    return {"moe_sizes": jnp.zeros((sum(cfg.sparse), cfg.held), jnp.int32)}
