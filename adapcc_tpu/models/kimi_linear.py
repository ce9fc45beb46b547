"""Kimi-Linear's block (``model_type`` ``kimi_linear``): Kimi Delta Attention
(KDA) layers three to one with latent attention that carries no positions,
sigmoid-routed sparse experts beside a shared expert after a leading dense
layer.  The latent mixer and the layer are also JoyAI-LLM-Flash's
(:mod:`adapcc_tpu.models.joyai_flash`), which gives the mixer a query rank
and rotates its position channels.

Written from the published ``config.json``
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
and the layer equations docs/KIMI_LINEAR.md states; the fields of
:class:`KimiLinearConfig` are that file's keys (its ``linear_attn_config``
group flattened).  The two mixers share no code with each other: KDA is a
recurrence over a ``[128, 128]`` state a head (:mod:`adapcc_tpu.ops.kda`), the
latent layer a causal softmax whose scores run over 192 channels and whose
values over 128 (:mod:`adapcc_tpu.ops.flash_attention`).  Norm, gated MLP,
the remat table and the scans' initialisers are :mod:`adapcc_tpu.models.lm`'s;
the expert layer with its share and the training loss are
:mod:`adapcc_tpu.models.trinity`'s: the router is Trinity's to the letter
(sigmoid scores, a bias for the choice only, top-k of one group,
renormalised, scaled), so a chip holds ``experts_held`` of the
``num_experts`` from ``expert_offset`` on, as there.

The model returns ``(logits, sizes)`` as ``Trinity`` does, so Trinity's
``stateful_loss`` and ``initial_model_state`` serve it and are this module's
names too.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models.lm import REMAT, GatedMLP, RMSNorm, a_log_init, dense, dt_bias_init, taps_init
from adapcc_tpu.models.trinity import SparseExperts, initial_model_state, stateful_loss  # noqa: F401

#: ``l2norm(x) = x * rsqrt(sum(x^2) + L2_EPS)`` over a head
L2_EPS = 1e-6


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the leading dense layers' FFN
    moe_intermediate_size: int = 1024      # every expert's, the shared one's too
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    #: ``linear_attn_config``: the layers of each kind, numbered from 1 as published
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    linear_attn_num_heads: int = 32        # ``linear_attn_config.num_heads``
    linear_attn_head_dim: int = 128        # ``linear_attn_config.head_dim``; the low-rank gates' inner size too
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32          # the latent layers'
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None      # None: one query projection, as published
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64             # carried, and rotated only where ``mla_use_nope`` is false
    v_head_dim: int = 128
    mla_use_nope: bool = True              # as published: no positions in the latent layers
    rope_theta: float = 10000.0            # read only where ``mla_use_nope`` is false
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_router_activation_func: str = "sigmoid"
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    #: routed experts held here, ``expert_offset … expert_offset + experts_held``
    #: of ``num_experts``; None holds them all
    experts_held: Optional[int] = None
    expert_offset: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if (
            self.moe_router_activation_func != "sigmoid" or self.hidden_act != "silu" or self.tie_word_embeddings
            or self.num_shared_experts != 1 or self.num_expert_group != 1 or self.topk_group != 1
        ):
            raise ValueError(
                "only the published kimi_linear settings are implemented: sigmoid scores in one group, "
                "silu, one shared expert, an untied head"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        self.kinds   # every layer is of one kind
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(f"experts {self.expert_offset}+{self.held} of {self.num_experts}")

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"kda"`` or ``"mla"`` for each layer run here: the first
        ``num_hidden_layers`` of the published lists."""
        kinds = []
        for i in range(1, self.num_hidden_layers + 1):
            if (i in self.kda_layers) == (i in self.full_attn_layers):
                raise ValueError(f"layer {i} must be in exactly one of kda_layers and full_attn_layers")
            kinds.append("kda" if i in self.kda_layers else "mla")
        return tuple(kinds)

    # what models/trinity.py's shared modules read, under its key names
    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else int(self.experts_held)

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def route_norm(self) -> bool:
        return self.moe_renormalize

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "KimiLinearConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(KimiLinearConfig.__dataclass_fields__)
        group = config.get("linear_attn_config", {})
        fields = {k: v for k, v in config.items() if k in names}
        fields.update(
            kda_layers=tuple(group.get("kda_layers", ())), full_attn_layers=tuple(group.get("full_attn_layers", ())),
            linear_attn_num_heads=group["num_heads"], linear_attn_head_dim=group["head_dim"],
            short_conv_kernel_size=group["short_conv_kernel_size"],
        )
        fields.update(program)
        return KimiLinearConfig(**fields)

    @staticmethod
    def tiny(**over) -> "KimiLinearConfig":
        """Test-sized: a dense KDA layer, a KDA and a latent expert layer and
        a KDA one, 8 experts top-2 beside a shared one; the kernels run (in
        the interpreter off the chip)."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
            num_hidden_layers=4, kda_layers=(1, 2, 4), full_attn_layers=(3,),
            linear_attn_num_heads=2, linear_attn_head_dim=16, num_attention_heads=2,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=8, num_experts_per_token=2, routed_scaling_factor=1.5, dtype=jnp.float32,
        )
        base.update(over)
        return KimiLinearConfig(**base)


@functools.lru_cache(maxsize=8)
def _heads_matrix(width: int, heads: int):
    """``[width, heads]`` float32 of zeros and ones: channel ``c`` belongs to
    head ``c // (width / heads)``."""
    return (np.arange(width)[:, None] // (width // heads) == np.arange(heads)[None, :]).astype(np.float32)


def _head_sums(x32, heads: int):
    """``x32 [B, T, H D]`` summed over each head's ``D`` channels: ``[B, T,
    H]``.  On a TPU ``[B, T, H D]`` and ``[B, T, H, D]`` are two tilings, so a
    sum over a reshaped last axis is a relayout and a pass of its own; the
    product with the heads' 0/1 matrix is a sum XLA fuses its neighbours
    into (float32 at ``highest``: a term a channel, each times one)."""
    return jnp.einsum("btc,ch->bth", x32, _heads_matrix(x32.shape[-1], heads), precision="highest")


def _head_spread(s, width: int):
    """``s [B, T, H]`` float32 repeated over each head's channels: ``[B, T,
    width]``, exact (one term a channel)."""
    return jnp.einsum("bth,ch->btc", s, _heads_matrix(width, s.shape[-1]), precision="highest")


def _normed(x, scale, heads: int, eps: float, share: float):
    """``(y, r)``: ``y = x r [scale]`` in ``x``'s dtype with ``r [B, T, H] =
    rsqrt(share * sum over a head of x^2 + eps)`` in float32; ``scale [D]`` is
    one weight for every head, or None."""
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(share * _head_sums(jnp.square(x32), heads) + eps)
    y = x32 * _head_spread(r, x.shape[-1])
    if scale is not None:
        y = y * jnp.tile(scale.astype(jnp.float32), heads)
    return y.astype(x.dtype), r


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _head_norm(x, scale, heads: int, eps: float, share: float):
    return _normed(x, scale, heads, eps, share)[0]


def _head_norm_fwd(x, scale, heads, eps, share):
    # kept for the backward pass: the input as it came and a statistic a head, nothing wide in float32
    y, r = _normed(x, scale, heads, eps, share)
    return y, (x, scale, r)


def _head_norm_bwd(heads, eps, share, kept, dy):
    x, scale, r = kept
    width = x.shape[-1]
    x32, z, wide_r = x.astype(jnp.float32), dy.astype(jnp.float32), _head_spread(r, width)
    dscale = None
    if scale is not None:
        dscale = jnp.sum((z * x32 * wide_r).reshape(-1, heads, width // heads), axis=(0, 1)).astype(scale.dtype)
        z = z * jnp.tile(scale.astype(jnp.float32), heads)
    # y = x r(s) with s the head's sum of squares: dr/ds = -share r^3 / 2, ds/dx = 2 x
    pull = share * _head_sums(z * x32, heads) * r ** 3
    return (z * wide_r - x32 * _head_spread(pull, width)).astype(x.dtype), dscale


_head_norm.defvjp(_head_norm_fwd, _head_norm_bwd)


def l2norm(x, heads: int):
    """``x rsqrt(sum of x^2 over a head + L2_EPS)`` for ``x [B, T, H D]``, head
    ``h`` its channels ``h D … (h + 1) D``; the sum and the root in float32."""
    return _head_norm(x, None, heads, L2_EPS, 1.0)


def o_norm(x, scale, heads: int, eps: float):
    """RMSNorm over each head of ``x [B, T, H D]`` with the one weight ``scale
    [D]`` for every head: ``x rsqrt(mean of x^2 over a head + eps) scale``."""
    return _head_norm(x, scale, heads, eps, heads / x.shape[-1])


class HeadRMSNorm(nn.Module):
    """:func:`o_norm` with its weight: ``scale [D]``, ones at the start
    (``lm.RMSNorm``'s parameter, over a head of the flat array)."""

    eps: float
    heads: int

    @nn.compact
    def __call__(self, x):
        return o_norm(x, self.param("scale", nn.initializers.ones, (x.shape[-1] // self.heads,)), self.heads, self.eps)


class KDAMixer(nn.Module):
    """Kimi Delta Attention: q, k, v behind a short convolution, a per-channel
    decay and a write strength from the token, the gated delta rule, a gated
    normed output."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        from adapcc_tpu.ops.kda import kda
        from adapcc_tpu.ops.short_conv import short_conv

        cfg = self.cfg
        H, D, K = cfg.linear_attn_num_heads, cfg.linear_attn_head_dim, cfg.short_conv_kernel_size

        def mixed(name, normed):
            # the silu is the kernel's, and so is q's and k's l2norm (:func:`l2norm` is its plain form)
            y = dense(H * D, cfg, f"{name}_proj")(x)
            taps = self.param(f"{name}_conv", taps_init, (K, H * D))
            return short_conv(y, taps, norm_heads=H if normed else None, norm_eps=L2_EPS)

        # one layout from the projections through the scan to o_proj: [B, T, H D], head h its channels h D … (h + 1) D
        q, k, v = mixed("q", True), mixed("k", True), mixed("v", False)
        with jax.named_scope("kda_gate"):
            a_log = self.param("A_log", a_log_init, (H,))
            dt_bias = self.param("dt_bias", dt_bias_init, (H * D,))
            f = dense(H * D, cfg, "f_b_proj")(dense(D, cfg, "f_a_proj")(x))
            g = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), D) * jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
            beta = jax.nn.sigmoid(dense(H, cfg, "b_proj")(x).astype(jnp.float32))
        with jax.named_scope("kda_scan"):
            o = kda(q, k, v, g, beta)
        o = HeadRMSNorm(cfg.rms_norm_eps, H, name="o_norm")(o)     # one weight of head_dim for every head
        gate = dense(H * D, cfg, "g_b_proj")(dense(D, cfg, "g_a_proj")(x))
        return dense(cfg.hidden_size, cfg, "o_proj")(o * jax.nn.sigmoid(gate))


@functools.lru_cache(maxsize=8)
def _pair_turns(T: int, D: int, start: int, theta: float):
    """``(cos, sin [T, D], swap [D, D])`` float32 for :func:`rotate_pairs`,
    from float64 angles on the host: with ``i`` counted from ``start``,
    channels ``start + 2i`` and ``start + 2i + 1`` of position ``m`` hold
    ``cos(m theta^(-2i / (D - start)))``, and ``-sin`` and ``+sin`` of it; the
    channels before ``start`` hold 1 and 0 (they do not turn).  ``x @ swap``
    puts each turning channel's partner in its place."""
    turned = D - start
    angle = np.arange(T, dtype=np.float64)[:, None] * theta ** (-np.arange(0, turned, 2, dtype=np.float64) / turned)
    cos, sin = np.ones((T, D)), np.zeros((T, D))
    cos[:, start:] = np.repeat(np.cos(angle), 2, axis=-1)
    sin[:, start:] = np.repeat(np.sin(angle), 2, axis=-1)
    sin[:, start::2] *= -1.0
    swap = np.zeros((D, D))
    channels = np.arange(start, D)
    swap[channels ^ 1, channels] = 1.0        # start is even: a pair is (2j, 2j + 1)
    return cos.astype(np.float32), sin.astype(np.float32), swap.astype(np.float32)


def _turn(x, cos, sin, swap):
    """``x cos + partner(x) sin`` in float32.  The partners come by a product
    with a 0/1 matrix (exact: one term a column): on a TPU a lane shuffle
    written as two rolls is two slices written out, a ``[D, D]`` product is
    nothing beside the projections."""
    partner = jnp.einsum("bthd,de->bthe", x, swap.astype(x.dtype), precision="highest", preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)


@jax.custom_vjp
def _turned(x, cos, sin, swap):
    return _turn(x, cos, sin, swap)


def _turned_fwd(x, cos, sin, swap):
    return _turn(x, cos, sin, swap), (cos, sin, swap)


def _turned_bwd(tables, g):
    # a rotation's transpose is the rotation back: the same pass with the sines' signs changed
    cos, sin, swap = tables
    return _turn(g, cos, -sin, swap), None, None, None


_turned.defvjp(_turned_fwd, _turned_bwd)


def rotate_pairs(x, theta: float, start: int = 0):
    """Rotary positions over the channels of ``x [B, T, H, D]`` from ``start``
    on, with neighbouring channels ``(start + 2i, start + 2i + 1)`` as the
    rotated pairs (``rope_interleave``): the pair at position ``m`` turns by
    ``m theta^(-2i / (D - start))``; the channels before ``start`` stay.  One
    pass over the whole head: no channel moves (no de-interleaving, no split
    and no concatenation of the head's two parts), the turn in float32, and
    its backward pass is the turn back."""
    T, D = x.shape[1], x.shape[-1]
    if start % 2 or D % 2:
        raise ValueError(f"channels {start}..{D} make no pairs (2i, 2i + 1)")
    cos, sin, swap = _pair_turns(T, D, start, float(theta))
    return _turned(x, jnp.asarray(cos)[None, :, None, :], jnp.asarray(sin)[None, :, None, :], jnp.asarray(swap))


class MLAMixer(nn.Module):
    """Latent attention: keys and values come up from a normed latent of
    ``kv_lora_rank``; ``qk_rope_head_dim`` more key channels come straight
    from the token, the same for every head.  Kimi-Linear's as published has
    one query projection and rotates nothing (``mla_use_nope``);
    JoyAI-LLM-Flash's brings the queries up from a normed latent of
    ``q_lora_rank`` too and rotates the shared key channels and each head's
    last ``qk_rope_head_dim`` query channels (:func:`rotate_pairs`).

    ``cfg`` is either model's configuration: read are ``num_attention_heads``,
    ``q_lora_rank``, ``kv_lora_rank``, the three head sizes, ``mla_use_nope``,
    ``rope_theta``, ``rms_norm_eps``, ``hidden_size`` and ``dtype``."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, T, _ = x.shape
        H, nope, pe, dv = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if cfg.q_lora_rank is None:
            q = dense(H * (nope + pe), cfg, "q_proj")(x)
        else:
            q_latent = RMSNorm(cfg.rms_norm_eps, name="q_a_layernorm")(dense(cfg.q_lora_rank, cfg, "q_a_proj")(x))
            q = dense(H * (nope + pe), cfg, "q_b_proj")(q_latent)
        q = q.reshape(B, T, H, nope + pe)
        latent, k_pe = jnp.split(dense(cfg.kv_lora_rank + pe, cfg, "kv_a_proj_with_mqa")(x), [cfg.kv_lora_rank], axis=-1)
        up = dense(H * (nope + dv), cfg, "kv_b_proj")(RMSNorm(cfg.rms_norm_eps, name="kv_a_layernorm")(latent))
        k_nope, v = jnp.split(up.reshape(B, T, H, nope + dv), [nope], axis=-1)
        k_pe = k_pe[:, :, None, :]
        if not cfg.mla_use_nope:
            with jax.named_scope("mla_rope"):
                q, k_pe = rotate_pairs(q, cfg.rope_theta, start=nope), rotate_pairs(k_pe, cfg.rope_theta)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, T, H, pe))], axis=-1)
        from adapcc_tpu.ops import flash_attention

        with jax.named_scope("mla_attn"):
            o = flash_attention(q, k, v, causal=True)
        return dense(cfg.hidden_size, cfg, "o_proj")(o.reshape(B, T, H * dv))


class Block(nn.Module):
    """One layer, two norms: ``h += mixer(norm(h))``, then ``h += ffn(norm(h))``
    (``cfg`` Kimi-Linear's configuration or JoyAI-LLM-Flash's)."""

    cfg: Any
    kind: str
    sparse: bool

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        mixer = KDAMixer if self.kind == "kda" else MLAMixer
        h = h + mixer(cfg, name="self_attn")(RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(h))
        x = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(h)
        if self.sparse:
            m, sizes = SparseExperts(cfg, name="mlp")(x)
        else:
            m, sizes = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(x), None
        return h + m, sizes


class KimiLinear(nn.Module):
    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → ``(logits [B, T, vocab] float32, sizes [expert
        layers, experts_held] int32)``; with ``return_hidden`` the final
        norm's output stands in for the logits.  No positions anywhere: the
        KDA layers carry the order."""
        cfg = self.cfg
        h = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )(tokens)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        sizes = []
        for i, kind in enumerate(cfg.kinds):
            h, given = block(cfg, kind, i >= cfg.first_k_dense_replace, name=f"layers_{i}")(h)
            if given is not None:
                sizes.append(given)
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(h)
        sizes = jnp.stack(sizes) if sizes else jnp.zeros((0, cfg.held), jnp.int32)
        head = self.param("lm_head", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.hidden_size))
        if return_hidden:
            return h, sizes
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), head.astype(cfg.dtype))
        return logits.astype(jnp.float32), sizes
