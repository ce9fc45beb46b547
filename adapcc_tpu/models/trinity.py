"""Trinity-Mini's block (``model_type`` ``afmoe``): windowed and full
grouped-query attention with a gated output, and sigmoid-routed sparse
experts beside a shared expert.

Written from the published ``config.json``
(https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json) and the
layer equations of the modeling code as docs/TRINITY.md states them; the
fields of :class:`TrinityConfig` are the ``config.json`` keys.  Shaped for
the TPU like :mod:`adapcc_tpu.models.gpt2`: bfloat16 products over float32
parameters, static shapes, the repo's flash kernels (``window=`` on a
``sliding_attention`` layer, four KV heads under 32 query heads), and the one
expert layer of :mod:`adapcc_tpu.models.moe`.

**The share.**  ``experts_held`` and ``expert_offset`` tell a layer which of
the ``num_experts`` routed experts live on this chip (all of them by
default).  The router keeps its published width and its experts per token
and routes over all; the layer adds the shared expert and its own experts'
part.  What absent experts would have added is left out: nothing stands in
for the chips that hold them.

The model returns, beside the logits, the assignments each held expert was
given in each expert layer (``[expert layers, experts_held]`` int32): a
training step built by :func:`stateful_loss` hands them out through
``TrainState.model_state``, beside its loss.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models.lm import REMAT, GatedMLP, RMSNorm, dense, next_token_loss
from adapcc_tpu.models.moe import routed_experts


@dataclass(frozen=True)
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144          # the leading dense layers' FFN
    moe_intermediate_size: int = 1024      # every expert's, the shared one's too
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: one of ``sliding_attention`` / ``full_attention`` for each layer; None:
    #: every ``global_attn_every_n_layers``-th layer full, the others sliding
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    #: routed experts held here, ``expert_offset … expert_offset + experts_held``
    #: of ``num_experts``; None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    #: "flash": ops/flash_attention.py; "xla": scores materialized, mask written out
    attention: str = "flash"
    flash_block: Optional[int] = None
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if self.score_func != "sigmoid" or self.hidden_act != "silu" or self.tie_word_embeddings:
            raise ValueError("only the published afmoe settings are implemented: sigmoid scores, silu, untied head")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert, as published")
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        if len(self.kinds) != self.num_hidden_layers:
            raise ValueError(f"{len(self.kinds)} layer_types for {self.num_hidden_layers} layers")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(f"experts {self.expert_offset}+{self.held} of {self.num_experts}")

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else int(self.experts_held)

    @property
    def kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        every = self.global_attn_every_n_layers
        return tuple(
            "full_attention" if (i + 1) % every == 0 else "sliding_attention"
            for i in range(self.num_hidden_layers)
        )

    @staticmethod
    def tiny(**over) -> "TrinityConfig":
        """Test-sized: both kinds of layer, 8 experts top-2 beside a shared
        one, window 16, 4 query heads on 2 KV heads."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
            num_hidden_layers=3, num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, sliding_window=16,
            layer_types=("sliding_attention", "sliding_attention", "full_attention"),
            num_experts=8, num_experts_per_tok=2, route_scale=1.5,
            dtype=jnp.float32, attention="xla",
        )
        base.update(over)
        return TrinityConfig(**base)


def rotary(x, theta: float):
    """Rotary positions over the whole head of ``x [B, T, H, D]`` (the two
    halves of the head form the rotated pairs), angles in float32."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    angle = jnp.asarray(np.arange(T)[:, None] * inv_freq[None, :], jnp.float32)   # [T, D/2]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    first, second = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-second, first], axis=-1) * sin).astype(x.dtype)


class GatedAttention(nn.Module):
    cfg: TrinityConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = dense(H * D, cfg, "q_proj")(x).reshape(B, T, H, D)
        k = dense(Hkv * D, cfg, "k_proj")(x).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, cfg, "v_proj")(x).reshape(B, T, Hkv, D)
        gate = dense(H * D, cfg, "gate_proj")(x)
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
        sliding = self.kind == "sliding_attention"
        if sliding:   # a full layer carries no positions
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        window = cfg.sliding_window if sliding and cfg.sliding_window < T else None
        with jax.named_scope("attn_window" if sliding else "attn_full"):
            if cfg.attention == "flash":
                from adapcc_tpu.ops import flash_attention

                out = flash_attention(
                    q, k, v, causal=True, window=window,
                    block_q=cfg.flash_block, block_k=cfg.flash_block,
                )
            elif cfg.attention == "xla":
                out = _dense_attention(q, k, v, window)
            else:
                raise ValueError(f"unknown attention {cfg.attention!r} (flash|xla)")
        out = out.reshape(B, T, H * D) * jax.nn.sigmoid(gate)
        return dense(cfg.hidden_size, cfg, "o_proj")(out)


def _dense_attention(q, k, v, window):
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / np.sqrt(D)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


class SparseExperts(nn.Module):
    """The shared expert plus the held routed experts' part; also returns
    the assignments each held expert was given."""

    cfg: TrinityConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, d = x.shape
        E, k, held, width = cfg.num_experts, cfg.num_experts_per_tok, cfg.held, cfg.moe_intermediate_size
        tokens = x.reshape(B * T, d)
        with jax.named_scope("moe_route"):
            router = self.param("router", nn.initializers.normal(0.02), (d, E))
            # a vector no gradient reaches; its update is the training recipe's
            bias = jax.lax.stop_gradient(self.param("expert_bias", nn.initializers.zeros, (E,)))
            scores = jax.nn.sigmoid(
                jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
            )
            _, ids = jax.lax.top_k(scores + bias, k)
            chosen = jnp.take_along_axis(scores, ids, axis=-1)
            if cfg.route_norm:
                chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
            weights = chosen * cfg.route_scale
        with jax.named_scope("moe_shared"):
            shared = GatedMLP(cfg, width, name="shared_experts")(x)
        init = nn.initializers.normal(0.02)
        stacked = {
            "w1": self.param("experts_w1", init, (held, d, width)),
            "w3": self.param("experts_w3", init, (held, d, width)),
            "w2": self.param("experts_w2", init, (held, width, d)),
        }
        routed, sizes = routed_experts(
            tokens, ids, weights, stacked, offset=cfg.expert_offset, num_experts=E, act=nn.silu,
            dtype=cfg.dtype,
        )
        return shared + routed.reshape(B, T, d).astype(x.dtype), sizes


class Block(nn.Module):
    """One layer with its four norms: ``h += norm(attn(norm(h)))``, then
    ``h += norm(ffn(norm(h)))``."""

    cfg: TrinityConfig
    kind: str
    sparse: bool

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, name=name)  # noqa: E731
        a = GatedAttention(cfg, self.kind, name="self_attn")(norm("input_layernorm")(h))
        h = h + norm("post_attention_layernorm")(a)
        x = norm("pre_mlp_layernorm")(h)
        if self.sparse:
            m, sizes = SparseExperts(cfg, name="mlp")(x)
        else:
            m, sizes = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(x), None
        return h + norm("post_mlp_layernorm")(m), sizes


class Trinity(nn.Module):
    cfg: TrinityConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits [B, T, vocab] float32, sizes [expert
        layers, experts_held] int32)``; with ``return_hidden`` the final
        norm's output stands in for the logits (the chunked loss takes the
        head itself)."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )
        h = embed(tokens)
        if cfg.mup_enabled:
            h = h * jnp.asarray(np.sqrt(cfg.hidden_size), h.dtype)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        sizes = []
        for i, kind in enumerate(cfg.kinds):
            h, given = block(cfg, kind, i >= cfg.num_dense_layers, name=f"layers_{i}")(h)
            if given is not None:
                sizes.append(given)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        sizes = jnp.stack(sizes) if sizes else jnp.zeros((0, cfg.held), jnp.int32)
        # untied head, stored [vocab, hidden] (the layout the chunked loss reads)
        head = self.param("lm_head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size))
        if return_hidden:
            return h, sizes
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), head.astype(cfg.dtype))
        return logits.astype(jnp.float32), sizes


def stateful_loss(model: Trinity, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: the mean next-token cross-entropy over
    the vocabulary held, and ``{"moe_sizes": [expert layers, held]}`` as the
    state the step returns beside it.  ``loss`` is "dense" (float32 logits of
    the whole batch) or "chunked" (``ops/chunked_ce.py``: the head product
    fused into the loss, ``block`` rows of the vocabulary at a time)."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)

    def loss_fn(params, model_state, batch):
        out, sizes = model.apply(params, batch, return_hidden=hidden)
        return value(out, params["params"]["lm_head"], batch), {"moe_sizes": sizes}

    return loss_fn


def initial_model_state(cfg: TrinityConfig):
    """The ``model_state`` a trainer's first state carries: no assignments yet."""
    layers = cfg.num_hidden_layers - cfg.num_dense_layers
    return {"moe_sizes": jnp.zeros((max(layers, 0), cfg.held), jnp.int32)}
