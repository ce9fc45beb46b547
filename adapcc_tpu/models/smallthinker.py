"""SmallThinker-21BA3B-Instruct's block: a router that reads the layer's
input *before* attention, a softmax over the chosen top-6 of 64 ReGLU experts
with no shared one, window-4,096 rotated and global position-free
grouped-query attention at 28 query heads on 4 K/V heads, an untied head.

Written from the published ``config.json``
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json)
and the layer equations docs/SMALLTHINKER.md states; the fields of
:class:`SmallThinkerConfig` are that file's keys.  What sets the block apart
from the others here is the order: ``ids`` and ``weights`` of a layer's
experts are computed from the un-normed stream ``h`` the layer is handed
(:class:`EarlyRouter`, under the scope ``moe_route``), the attention follows,
and the expert layer behind it (:class:`HeldExperts`) is *given* the choice;
nothing the attention computes reaches the routing.  Attention is Trinity's
form (:func:`adapcc_tpu.models.trinity.rotary`,
:mod:`adapcc_tpu.ops.flash_attention` with ``window=`` on a layer whose
``sliding_window_layout`` entry is 1) without its q/k norms and its gate, a
group of seven query heads to a K/V head.  Norm, projection, the remat table
and the loss's fork are :mod:`adapcc_tpu.models.lm`'s, the expert layer
:func:`adapcc_tpu.models.moe.routed_experts` with ``act=relu``.

**The share.**  ``layers_held`` names the published layers run here (a
layer's positions follow ``rope_layout[l]``, its mask
``sliding_window_layout[l]``); ``experts_held`` and ``expert_offset`` which of
the ``moe_num_primary_experts`` routed experts live on this chip.  The router
keeps its published width and its experts per token and routes over all; the
layer adds its own experts' part.  What absent experts would have added is
left out: nothing stands in for the chips that hold them.

The model returns, beside the logits, the assignments each held expert was
given in each layer (``[layers, experts_held]`` int32), which
:func:`stateful_loss` hands out through ``TrainState.model_state``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from adapcc_tpu.models.lm import REMAT, RMSNorm, dense, next_token_loss
from adapcc_tpu.models.moe import routed_experts
from adapcc_tpu.models.trinity import rotary
from adapcc_tpu.utils.observability import default_registry

#: published layers 0, 4, 8, … are global without positions, the rest windowed with them
_PUBLISHED_LAYOUT = tuple(int(i % 4 != 0) for i in range(52))


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    #: the published depth: both layouts have one entry for each
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768                 # every expert's width
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True                    # changes nothing under the softmax: the six already sum to 1
    rope_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    sliding_window_layout: Tuple[int, ...] = _PUBLISHED_LAYOUT
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rope_scaling: Optional[Any] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    #: the published indices of the layers run here, ascending; None: all
    layers_held: Optional[Tuple[int, ...]] = None
    #: routed experts held here, ``expert_offset … expert_offset + experts_held``
    #: of ``moe_num_primary_experts``; None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if not self.moe_primary_router_apply_softmax or self.tie_word_embeddings or self.rope_scaling is not None:
            raise ValueError(
                "only the published smallthinker settings are implemented: a softmax over the chosen logits, "
                "an untied head, no rope_scaling"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"heads {self.num_attention_heads} over {self.num_key_value_heads}")
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, name)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(f"{name} {layout} for {self.num_hidden_layers} layers")
        held = self.held_layers
        if list(held) != sorted(set(held)) or not held or held[0] < 0 or held[-1] >= self.num_hidden_layers:
            raise ValueError(f"layers_held {held} of {self.num_hidden_layers} published layers")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(f"experts {self.expert_offset}+{self.held} of {self.num_experts}")

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.num_hidden_layers)) if self.layers_held is None else tuple(self.layers_held)

    @property
    def plan(self) -> Tuple[Tuple[bool, bool], ...]:
        """``(rotated, windowed)`` of each layer run here."""
        return tuple((bool(self.rope_layout[i]), bool(self.sliding_window_layout[i])) for i in self.held_layers)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer run here by name: ``window`` or ``global``, ``+rope`` where rotated."""
        return tuple(("window" if windowed else "global") + ("+rope" if rotated else "") for rotated, windowed in self.plan)

    @property
    def num_experts(self) -> int:
        return self.moe_num_primary_experts

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else int(self.experts_held)

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "SmallThinkerConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(SmallThinkerConfig.__dataclass_fields__)
        fields = {k: v for k, v in config.items() if k in names}
        fields.update(program)
        for name in ("rope_layout", "sliding_window_layout", "layers_held"):
            if fields.get(name) is not None:
                fields[name] = tuple(int(i) for i in fields[name])
        return SmallThinkerConfig(**fields)

    @staticmethod
    def tiny(**over) -> "SmallThinkerConfig":
        """Test-sized: eight published layers of which one period is run (a
        global layer without positions, then three windowed ones with them), 7
        query heads on 1 K/V head of 8 (the group of 7 kept), 8 experts top-3,
        window 16."""
        layout = tuple(int(i % 4 != 0) for i in range(8))
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=8, num_attention_heads=7, num_key_value_heads=1, head_dim=8,
            moe_ffn_hidden_size=16, moe_num_primary_experts=8, moe_num_active_primary_experts=3, rope_layout=layout,
            sliding_window_layout=layout, sliding_window_size=16, layers_held=(0, 1, 2, 3), dtype=jnp.float32,
        )
        base.update(over)
        return SmallThinkerConfig(**base)


class EarlyRouter(nn.Module):
    """``(ids [N, k], weights [N, k])`` from the layer's input as it comes,
    before any norm: the top ``k`` of the float32 logits, then a softmax over
    the chosen ``k``.  No bias vector, no scale, no auxiliary term."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        default_registry().incr("smallthinker.early_route_calls")
        router = self.param("kernel", nn.initializers.normal(0.02), (cfg.hidden_size, cfg.num_experts))
        tokens = h.reshape(-1, cfg.hidden_size)
        logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision="highest")
        top, ids = jax.lax.top_k(logits, cfg.moe_num_active_primary_experts)
        return ids, jax.nn.softmax(top, axis=-1)


class Attention(nn.Module):
    """Causal grouped-query attention with no q/k norm and no gate: rotated
    over the whole head where ``rotated``, a band of ``sliding_window_size``
    keys where ``windowed``, scores over ``sqrt(head)``."""

    cfg: SmallThinkerConfig
    rotated: bool
    windowed: bool

    @nn.compact
    def __call__(self, x):
        from adapcc_tpu.ops import flash_attention

        cfg = self.cfg
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = dense(H * D, cfg, "q_proj")(x).reshape(B, T, H, D)
        k = dense(Hkv * D, cfg, "k_proj")(x).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, cfg, "v_proj")(x).reshape(B, T, Hkv, D)
        if self.rotated:
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        window = cfg.sliding_window_size if self.windowed and cfg.sliding_window_size < T else None
        with jax.named_scope("attn_window" if self.windowed else "attn_full"):
            out = flash_attention(q, k, v, causal=True, window=window)
        return dense(cfg.hidden_size, cfg, "o_proj")(out.reshape(B, T, H * D))


class HeldExperts(nn.Module):
    """The held ReGLU experts' part of a layer whose routing was made before
    the attention: ``sum_j w_j (relu(y W_gate) ∘ y W_up) W_down`` over the
    token's chosen experts that live here.  Also returns the assignments each
    held expert was given."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, y, ids, weights):
        cfg = self.cfg
        B, T, d = y.shape
        held, width = cfg.held, cfg.moe_ffn_hidden_size
        init = nn.initializers.normal(0.02)
        stacked = {
            "w1": self.param("experts_w1", init, (held, d, width)),      # gate
            "w3": self.param("experts_w3", init, (held, d, width)),      # up
            "w2": self.param("experts_w2", init, (held, width, d)),      # down
        }
        with jax.named_scope("moe_experts"):
            routed, sizes = routed_experts(
                y.reshape(B * T, d), ids, weights, stacked, offset=cfg.expert_offset, num_experts=cfg.num_experts,
                act=nn.relu, dtype=cfg.dtype,
            )
        return routed.reshape(B, T, d).astype(y.dtype), sizes


class Block(nn.Module):
    """One layer: the routing from ``h`` as it comes, ``h += attn(norm(h))``,
    then ``h += experts(norm(h))`` by that routing.  Returns ``(h, sizes)``."""

    cfg: SmallThinkerConfig
    rotated: bool
    windowed: bool

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        with jax.named_scope("moe_route"):
            ids, weights = EarlyRouter(cfg, name="router")(h)
        x = RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(h)
        h = h + Attention(cfg, self.rotated, self.windowed, name="self_attn")(x)
        y = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        m, sizes = HeldExperts(cfg, name="block_sparse_moe")(y, ids, weights)
        return h + m, sizes


class SmallThinker(nn.Module):
    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits [B, T, vocab] float32, sizes [layers,
        experts_held] int32)``; with ``return_hidden`` the final norm's output
        stands in for the logits (the chunked loss takes the head itself)."""
        cfg = self.cfg
        h = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )(tokens)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        sizes = []
        for i, (rotated, windowed) in enumerate(cfg.plan):
            h, given = block(cfg, rotated, windowed, name=f"layers_{i}")(h)
            sizes.append(given)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        sizes = jnp.stack(sizes)
        # untied head, stored [vocab, hidden] (the layout the chunked loss reads)
        head = self.param("lm_head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size))
        if return_hidden:
            return h, sizes
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), head.astype(cfg.dtype))
        return logits.astype(jnp.float32), sizes


def stateful_loss(model: SmallThinker, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: the mean next-token cross-entropy over
    the vocabulary held (``loss`` "dense": float32 logits of the whole batch;
    "chunked": ``ops/chunked_ce.py``), and ``{"moe_sizes": [layers, held]}`` as
    the state the step returns."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)

    def loss_fn(params, model_state, batch):
        out, sizes = model.apply(params, batch, return_hidden=hidden)
        return value(out, params["params"]["lm_head"], batch), {"moe_sizes": sizes}

    return loss_fn


def initial_model_state(cfg: SmallThinkerConfig):
    """The ``model_state`` a trainer's first state carries: no assignments yet."""
    return {"moe_sizes": jnp.zeros((len(cfg.held_layers), cfg.held), jnp.int32)}
