"""JoyAI-LLM-Flash's block (``model_type`` ``joyai_llm_flash``): rotated
latent attention with a query rank on every layer, sigmoid-routed sparse
experts beside a shared expert after a leading dense layer, and a
multi-token-prediction (MTP) module over the trunk.

Written from the published ``config.json``
(https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json)
and the layer equations docs/JOYAI_FLASH.md states; the fields of
:class:`JoyAIFlashConfig` are that file's keys.  The layer and its latent
mixer are :mod:`adapcc_tpu.models.kimi_linear`'s (``Block``, ``MLAMixer``:
here with ``q_lora_rank`` and the rotation of ``rope_interleave``); norm,
gated MLP and the remat table are :mod:`adapcc_tpu.models.lm`'s, the expert
layer with its share :mod:`adapcc_tpu.models.trinity`'s (``noaux_tc`` in one
group is Trinity's router to the letter: sigmoid scores, a bias for the choice
only, top-k, renormalised, scaled).

**Two loss terms over shared weights.**  The MTP module (depth 1) merges the
trunk's final-norm output at position ``i`` with the embedding of token
``i + 1``, runs one more latent + expert block and reads it through the
trunk's own head: it predicts token ``i + 2``.  The embedding and the head
are each used twice in a step, so their gradients are sums.  The module runs
on all ``T`` positions with the token stream shifted by one and a filler in
the last place (the block is causal: places ``0 .. T-3``, the ones that
enter the loss, never see it), so that the flash kernels keep whole tiles.

The model returns ``(logits, mtp_logits, sizes)``, ``sizes [expert layers +
1, experts_held]`` with the module's expert layer last;
:func:`stateful_loss` gives ``L = L_main + mtp_loss_weight * L_mtp`` and
hands both terms out beside the routing counts.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from adapcc_tpu.models.kimi_linear import Block
from adapcc_tpu.models.lm import REMAT, RMSNorm, dense, next_token_loss
from adapcc_tpu.utils.observability import default_registry


@dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the leading dense layers' FFN
    moe_intermediate_size: int = 768       # every expert's, the shared one's too
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[Dict[str, Any]] = None
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    #: routed experts held here, ``expert_offset … expert_offset + experts_held``
    #: of ``n_routed_experts``; None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    #: ``L = L_main + mtp_loss_weight * L_mtp``: the training recipe's, not ``config.json``'s
    mtp_loss_weight: float = 0.3
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if (
            self.scoring_func != "sigmoid" or self.topk_method != "noaux_tc" or self.hidden_act != "silu"
            or self.n_shared_experts != 1 or self.n_group != 1 or self.topk_group != 1
            or self.tie_word_embeddings or self.attention_bias or self.rope_scaling is not None
            or not self.rope_interleave or self.num_nextn_predict_layers != 1
        ):
            raise ValueError(
                "only the published joyai_llm_flash settings are implemented: sigmoid scores (noaux_tc) in one "
                "group, silu, one shared expert, an untied head, no bias, interleaved rotation without scaling, "
                "one multi-token-prediction module"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(f"experts {self.expert_offset}+{self.held} of {self.num_experts}")

    # what the shared modules (trinity's expert layer, kimi_linear's mixer) read, under their key names
    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else int(self.experts_held)

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def route_norm(self) -> bool:
        return self.norm_topk_prob

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def mla_use_nope(self) -> bool:
        return False        # the position channels are rotated

    @property
    def kinds(self):
        """Every trunk layer's mixer (``train_lm.train`` prints them)."""
        return ("mla",) * self.num_hidden_layers

    @property
    def expert_layers(self) -> int:
        """Expert layers a step runs: the trunk's and the MTP module's."""
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0) + self.num_nextn_predict_layers

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "JoyAIFlashConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(JoyAIFlashConfig.__dataclass_fields__)
        return JoyAIFlashConfig(**{**{k: v for k, v in config.items() if k in names}, **program})

    @staticmethod
    def tiny(**over) -> "JoyAIFlashConfig":
        """Test-sized: a dense layer and two expert layers under the MTP
        module, 8 experts top-2 beside a shared one; the flash kernels run
        (in the interpreter off the chip)."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
            num_hidden_layers=3, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
            n_routed_experts=8, num_experts_per_tok=2, routed_scaling_factor=1.5, dtype=jnp.float32,
        )
        base.update(over)
        return JoyAIFlashConfig(**base)


def _block(cfg: JoyAIFlashConfig):
    policy = REMAT[cfg.remat]
    return Block if policy is False else nn.remat(Block, policy=policy)


class MTPModule(nn.Module):
    """The checkpoint's layer after the last: ``z = [enorm(Emb(t_{i+1})) |
    hnorm(hbar_i)] W_eh``, one latent + expert block over ``z``, and the norm
    in front of the shared head.  The embedding and the head are the
    trunk's own parameters, handed in and read by the caller."""

    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, trunk, next_embedded):
        cfg = self.cfg
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, name=name)  # noqa: E731
        with jax.named_scope("mtp_merge"):
            merged = jnp.concatenate([norm("enorm")(next_embedded), norm("hnorm")(trunk)], axis=-1)
            z = dense(cfg.hidden_size, cfg, "eh_proj")(merged)
        with jax.named_scope("mtp_block"):
            u, sizes = _block(cfg)(cfg, "mla", True, name="block")(z)
        return norm("shared_head_norm")(u), sizes


class JoyAIFlash(nn.Module):
    cfg: JoyAIFlashConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits, mtp_logits, sizes)``: the trunk's
        and the MTP module's ``[B, T, vocab]`` float32 (place ``i`` of the
        first predicts token ``i + 1``, of the second token ``i + 2``; the
        module's last place holds a filler's) and ``sizes [expert layers + 1,
        experts_held]`` int32; with ``return_hidden`` the two norms' outputs
        in front of the head stand in for the logits."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )
        default_registry().gauge("mtp.depth", cfg.num_nextn_predict_layers)
        h = embed(tokens)
        block = _block(cfg)
        sizes = []
        for i in range(cfg.num_hidden_layers):
            h, given = block(cfg, "mla", i >= cfg.first_k_dense_replace, name=f"layers_{i}")(h)
            if given is not None:
                sizes.append(given)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        # token i + 1 beside place i; the last place's filler (the row's first token) is seen by no place that counts
        u, given = MTPModule(cfg, name="mtp")(h, embed(jnp.roll(tokens, -1, axis=1)))
        sizes = jnp.stack(sizes + [given])
        # untied head, stored [vocab, hidden] (the layout the chunked loss reads), read twice
        head = self.param("lm_head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size))
        if return_hidden:
            return h, u, sizes

        def read(x, scope):
            with jax.named_scope(scope):
                return jnp.einsum("btd,vd->btv", x.astype(cfg.dtype), head.astype(cfg.dtype)).astype(jnp.float32)

        return read(h, "lm_head"), read(u, "mtp_head"), sizes


def stateful_loss(model: JoyAIFlash, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: ``L_main + mtp_loss_weight * L_mtp``,
    the trunk's mean next-token cross-entropy over places ``0 .. T-2`` and
    the module's over places ``0 .. T-3`` against the token two on, both
    through ``gpt2.lm_loss`` (or ``ops/chunked_ce.py`` with ``loss="chunked"``)
    over the vocabulary held; the state the step returns is ``{"moe_sizes",
    "loss_main", "loss_mtp"}``."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)
    weight = model.cfg.mtp_loss_weight

    def loss_fn(params, model_state, batch):
        out, mtp_out, sizes = model.apply(params, batch, return_hidden=hidden)
        head = params["params"]["lm_head"]
        main = value(out, head, batch)
        # the module's place i answers for token i + 2: its places but the last against the tokens from the second on
        with jax.named_scope("mtp_head"):
            mtp = value(mtp_out[:, :-1], head, batch[:, 1:], scope=None)
        return main + weight * mtp, {"moe_sizes": sizes, "loss_main": main, "loss_mtp": mtp}

    return loss_fn


def initial_model_state(cfg: JoyAIFlashConfig):
    """The ``model_state`` a trainer's first state carries: no assignments
    and no loss yet."""
    zero = jnp.zeros((), jnp.float32)
    return {"moe_sizes": jnp.zeros((cfg.expert_layers, cfg.held), jnp.int32), "loss_main": zero, "loss_mtp": zero}


def record_step(model_state, metrics=None) -> None:
    """Per-step samples from what a compiled step returned beside its loss
    (read after the steps, so that no step waits for the host): the routing
    counts as ``moe.record_routing`` records them, and the loss's two terms
    as ``lm.loss_main`` and ``lm.loss_mtp``."""
    from adapcc_tpu.models.moe import record_routing

    metrics = metrics or default_registry()
    record_routing(model_state["moe_sizes"], metrics=metrics)
    metrics.sample("lm.loss_main", float(model_state["loss_main"]))
    metrics.sample("lm.loss_mtp", float(model_state["loss_mtp"]))
