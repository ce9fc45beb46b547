"""Phi-4-mini-flash-reasoning's decoder (``model_type`` ``phi4flash``, the
SambaY architecture): Mamba-1 selective scans alternating with differential
attention, whose second half reads what the first half made, a gated memory
unit on one earlier layer's scan output and cross-attention on one earlier
layer's keys and values.

Written from the published ``config.json``
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json)
and the layer equations docs/PHI4_FLASH.md states; the fields of
:class:`Phi4FlashConfig` are that file's keys, the Mamba sizes (the published
code's defaults and no key) and ``layers_held``: the published indices run
here, from which a layer's kind and its ``lambda_0`` follow.

With ``L`` published layers, layer ``l`` is (:func:`kind_of`)

- ``"M"`` (even ``l < L/2``) a Mamba-1 mixer (:mod:`adapcc_tpu.ops.selective_scan`),
  ``"M*"`` (``l = L/2``) the same, which also hands on its scan's output
  ``y`` (with the skip, before the gate) as the *memory* ``m``;
- ``"S"`` (odd ``l < L/2``) differential attention on a window,
  ``"F"`` (``l = L/2 + 1``) the same over the whole causal triangle, which
  also hands on its keys and values;
- ``"G"`` (even ``l > L/2``) a gated memory unit ``(m * silu(u W_in)) W_out``:
  no scan, no convolution;
- ``"X"`` (odd ``l > L/2 + 1``) differential attention that projects a query
  only and reads layer ``L/2 + 1``'s keys and values.

``m`` therefore takes gradient from ``M*``'s own gate and from every ``G``,
the shared keys and values from ``F`` and from every ``X``.  A :class:`Block`
takes and returns what is carried (``{"m": ..., "kv": ...}``) beside the
stream, so that ``nn.remat(Block)`` still wraps one layer.

Differential attention, a pair of query heads at a time: two softmaxes
against one doubled value (two :func:`flash_attention` calls a layer, 20 heads
of 64 on 10 K/V heads of 64 over values of 128), ``a1 - lambda a2`` with one
learned scalar a layer, an RMS norm over the pair's 128 channels, times ``1 -
lambda_0``.  **The projection's columns lie group by group** (every pair's
first query head, then every second, likewise the keys, then the doubled
values): no strided slice of ``[B, T, 40, 64]`` is re-laid on the chip.  The
published order pairs heads ``2p, 2p + 1``; :func:`grouped_columns` is the
fixed permutation between the two, which the benchmark's weight maker hands
the plain reference the same weights through.

No positions anywhere; LayerNorm with bias; the head is the embedding.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models.lm import REMAT, GatedMLP, dense, dt_bias_init, next_token_loss, taps_init
from adapcc_tpu.utils.observability import default_registry

def kind_of(layer: int, published_layers: int, mb_per_layer: int = 2) -> str:
    """The kind of published layer ``layer`` of ``published_layers``."""
    half = published_layers // 2
    if layer % mb_per_layer == 0:
        return "M" if layer < half else "M*" if layer == half else "G"
    return "S" if layer < half else "F" if layer == half + 1 else "X"


def lambda_init(layer: int) -> float:
    """``lambda_0`` of the published layer index: 0.2 at layer 0, towards 0.8."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def grouped_columns(heads: int, kv_heads: int, head_dim: int, cross: bool = False) -> np.ndarray:
    """For each column of the program's attention projection, the column of
    the published one it holds.  Published: ``[q | k | v]``, heads in order.
    Here: ``[q1 | q2 | k1 | k2 | V]``, ``q1`` the query heads ``0, 2, 4, ...``
    and ``q2`` the heads ``1, 3, 5, ...``, ``k1`` and ``k2`` the K/V heads
    likewise, ``V`` the values as published (a pair's two heads already lie
    side by side).  ``cross``: the query's columns only."""
    def halves(first, n):
        cols = first + np.arange(n * head_dim).reshape(n, head_dim)
        return [cols[0::2].reshape(-1), cols[1::2].reshape(-1)]

    q, kv = heads * head_dim, kv_heads * head_dim
    parts = halves(0, heads)
    if not cross:
        parts += halves(q, kv_heads) + [q + kv + np.arange(kv)]
    return np.concatenate(parts)


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    #: the published depth: where a layer's kind changes follows from it
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    hidden_act: str = "silu"
    mlp_bias: bool = False
    lm_head_bias: bool = False
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    # the published code's Mamba defaults, no key of config.json
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None    # None: ceil(hidden_size / 16)
    #: the published indices of the layers run here, ascending; None: all
    layers_held: Optional[Tuple[int, ...]] = None
    dtype: jnp.dtype = jnp.bfloat16
    #: recomputation of a layer in the backward pass: "none", "dots", "full"
    remat: str = "none"

    def __post_init__(self):
        if (
            self.hidden_act != "silu" or not self.tie_word_embeddings or self.mlp_bias or self.lm_head_bias
            or self.embd_pdrop or self.resid_pdrop or self.mb_per_layer != 2 or self.num_hidden_layers % 4
        ):
            raise ValueError(
                "only the published phi4flash settings are implemented: silu, a tied head, no bias in the MLP or the "
                "head, no dropout, a Mamba layer every second (mb_per_layer 2), a depth that is a multiple of four"
            )
        if self.remat not in REMAT:
            raise ValueError(f"remat {self.remat!r} not in {sorted(REMAT)}")
        H, Hkv = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % H or H % 2 or Hkv % 2 or H % Hkv:
            raise ValueError(f"heads {H} over {Hkv} of {self.hidden_size}: differential attention pairs them up")
        held = self.held
        if list(held) != sorted(set(held)) or not held or held[0] < 0 or held[-1] >= self.num_hidden_layers:
            raise ValueError(f"layers_held {held} of {self.num_hidden_layers} published layers")
        kinds = self.kinds
        for reader, maker in (("G", "M*"), ("X", "F")):
            if reader in kinds and maker not in kinds[:kinds.index(reader)]:
                raise ValueError(f"layers_held {held}: a {reader} layer reads what no {maker} layer before it made")

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(range(self.num_hidden_layers)) if self.layers_held is None else tuple(self.layers_held)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(kind_of(i, self.num_hidden_layers, self.mb_per_layer) for i in self.held)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16) if self.mamba_dt_rank is None else self.mamba_dt_rank

    @staticmethod
    def from_config(config: Dict[str, Any], **program) -> "Phi4FlashConfig":
        """From a ``config.json``-shaped mapping (keys that are no field are
        passed over), ``program`` the fields that are the program's own."""
        names = set(Phi4FlashConfig.__dataclass_fields__)
        fields = {k: v for k, v in config.items() if k in names}
        fields.update(program)
        if fields.get("layers_held") is not None:
            fields["layers_held"] = tuple(fields["layers_held"])
        return Phi4FlashConfig(**fields)

    @staticmethod
    def tiny(**over) -> "Phi4FlashConfig":
        """Test-sized: twelve published layers of which eight are run, one of
        each kind and two ``G`` and two ``X`` (a sum over readers of one would
        pass for it); both kernels run (in the interpreter off the chip)."""
        base = dict(
            vocab_size=256, hidden_size=32, intermediate_size=64, num_hidden_layers=12,
            layers_held=(0, 1, 6, 7, 8, 9, 10, 11), num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
            mamba_d_state=4, dtype=jnp.float32,
        )
        base.update(over)
        return Phi4FlashConfig(**base)


class LayerNorm(nn.Module):
    """LayerNorm with bias, in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale + bias).astype(x.dtype)


def _biased(features: int, cfg: Phi4FlashConfig, name: str):
    return nn.Dense(features, use_bias=True, dtype=cfg.dtype, name=name, kernel_init=nn.initializers.normal(0.02))


def state_log_init(key, shape, dtype=jnp.float32):
    """``A_log [channels, N] = log(1 .. N)`` along the state axis, as published."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class MambaMixer(nn.Module):
    """``[x, z] = u W_in``; a biased causal convolution and silu over ``x``;
    ``[r, B, C] = x W_x``; ``dt = softplus(r W_dt + b_dt)`` in float32; the
    selective scan; ``(y * silu(z)) W_out`` with no norm before it.  Returns
    ``(out, y)``: ``y`` is the memory where the layer is ``M*``."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, u):
        from adapcc_tpu.ops.selective_scan import selective_scan
        from adapcc_tpu.ops.short_conv import short_conv

        cfg = self.cfg
        d_in, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
        x, z = jnp.split(dense(2 * d_in, cfg, "in_proj")(u), 2, axis=-1)
        with jax.named_scope("sscan_conv"):
            taps = self.param("conv_taps", taps_init, (cfg.mamba_d_conv, d_in))
            bias = self.param("conv_bias", nn.initializers.zeros, (d_in,))
            x = short_conv(x, taps, bias)       # bias and silu in the kernel
        with jax.named_scope("sscan_gate"):
            r, B, C = jnp.split(dense(R + 2 * N, cfg, "x_proj")(x), [R, R + N], axis=-1)
            w_dt = self.param("dt_proj", nn.initializers.normal(R ** -0.5), (R, d_in))
            dt = jnp.dot(r, w_dt.astype(cfg.dtype), preferred_element_type=jnp.float32)
            dt = jax.nn.softplus(dt + self.param("dt_bias", dt_bias_init, (d_in,)))
            A = -jnp.exp(self.param("A_log", state_log_init, (d_in, N)).astype(jnp.float32))
        with jax.named_scope("sscan_scan"):
            y = selective_scan(x, dt, A, B, C, self.param("D", nn.initializers.ones, (d_in,)))
        return dense(cfg.hidden_size, cfg, "out_proj")(y * nn.silu(z)), y


class GatedMemoryUnit(nn.Module):
    """``(m * silu(u W_in)) W_out`` on the memory ``m`` an earlier layer's scan made."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, m):
        cfg = self.cfg
        with jax.named_scope("gmu"):
            gated = m * nn.silu(dense(cfg.d_inner, cfg, "in_proj")(u))
        return dense(cfg.hidden_size, cfg, "out_proj")(gated)


def band_waste(T: int, window: int, dtype, head_dim: int) -> float:
    """Score-plane area the window layer's kernels visit (whole tiles) over
    the area the mask leaves (``sum_t min(t + 1, window)``)."""
    from adapcc_tpu.ops.flash_attention import default_blocks, visited_tiles

    bq, bk = default_blocks(T, head_dim, dtype)
    w = min(window, T)
    seen = w * (w + 1) // 2 + (T - w) * w
    return visited_tiles(T, bq, bk, True, window if window < T else None) * bq * bk / seen


class DiffAttention(nn.Module):
    """Differential attention of kind ``"S"`` (window), ``"F"`` (full; both
    return ``(out, (k1, k2, V))``) or ``"X"`` (takes ``kv``); ``layer`` is the
    published index ``lambda_0`` follows."""

    cfg: Phi4FlashConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, u, kv=None):
        from adapcc_tpu.ops import flash_attention

        cfg = self.cfg
        Bt, T, _ = u.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        P, J = H // 2, Hkv // 2
        if self.kind == "X":
            q1, q2 = jnp.split(_biased(H * D, cfg, "q_proj")(u), 2, axis=-1)
            k1, k2, V = kv
        else:
            q1, q2, k1, k2, V = jnp.split(
                _biased((H + 2 * Hkv) * D, cfg, "qkv_proj")(u), np.cumsum([P * D, P * D, J * D, J * D]), axis=-1
            )
            k1, k2, V = k1.reshape(Bt, T, J, D), k2.reshape(Bt, T, J, D), V.reshape(Bt, T, J, 2 * D)
        window = cfg.sliding_window if self.kind == "S" else None
        if window is not None:
            default_registry().gauge("diffattn.band_waste", band_waste(T, window, cfg.dtype, D))
        with jax.named_scope("diff_attn"):
            a1 = flash_attention(q1.reshape(Bt, T, P, D), k1, V, causal=True, window=window)
            a2 = flash_attention(q2.reshape(Bt, T, P, D), k2, V, causal=True, window=window)
        with jax.named_scope("diff_mix"):
            vec = lambda name: self.param(name, nn.initializers.normal(0.1), (D,))  # noqa: E731
            lam0 = lambda_init(self.layer)
            first = jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1")))
            lam = first - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2"))) + lam0
            self.sow("intermediates", "lambda", lam)     # read under mutable=["intermediates"]; nothing otherwise
            a = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
            a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + 1e-5)
            a = a * self.param("subln", nn.initializers.ones, (2 * D,)) * (1.0 - lam0)
        out = _biased(cfg.hidden_size, cfg, "out_proj")(a.astype(cfg.dtype).reshape(Bt, T, H * D))
        return out, (k1, k2, V)


class Block(nn.Module):
    """One layer of kind ``kind`` at published index ``layer``: ``h +=
    mixer(LN(h))``, ``h += mlp(LN(h))``.  ``carried`` holds what earlier
    layers handed on (``"m"`` from ``M*``, ``"kv"`` from ``F``) and comes back
    with what this layer adds.  Returns ``(h, carried)``."""

    cfg: Phi4FlashConfig
    kind: str
    layer: int

    @nn.compact
    def __call__(self, h, carried):
        cfg, kind = self.cfg, self.kind
        u = LayerNorm(cfg.layer_norm_eps, name="input_layernorm")(h)
        if kind in ("M", "M*"):
            out, y = MambaMixer(cfg, name="mixer")(u)
            if kind == "M*":
                carried = {**carried, "m": y}
        elif kind == "G":
            out = GatedMemoryUnit(cfg, name="mixer")(u, carried["m"])
        else:
            out, kv = DiffAttention(cfg, kind, self.layer, name="mixer")(u, carried.get("kv"))
            if kind == "F":
                carried = {**carried, "kv": kv}
        h = h + out
        h = h + GatedMLP(cfg, cfg.intermediate_size, name="mlp")(
            LayerNorm(cfg.layer_norm_eps, name="post_attention_layernorm")(h)
        )
        return h, carried


class Phi4Flash(nn.Module):
    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, return_hidden: bool = False):
        """``tokens [B, T]`` → logits ``[B, T, vocab]`` float32; with
        ``return_hidden`` the final norm's output instead (its product with
        the embedding is the logits)."""
        cfg = self.cfg
        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype, name="embed_tokens",
        )
        h = embed(tokens)
        policy = REMAT[cfg.remat]
        block = Block if policy is False else nn.remat(Block, policy=policy)
        carried = {}
        for i, (layer, kind) in enumerate(zip(cfg.held, cfg.kinds)):
            h, carried = block(cfg, kind, layer, name=f"layers_{i}")(h, carried)
        h = LayerNorm(cfg.layer_norm_eps, name="norm")(h)
        if return_hidden:
            return h
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("btd,vd->btv", h.astype(cfg.dtype), embed.embedding.astype(cfg.dtype))
        return logits.astype(jnp.float32)


def stateful_loss(model: Phi4Flash, loss: str = "dense", block: int = 2048):
    """``loss_fn(params, model_state, batch) -> (loss, model_state)`` for
    ``DDPTrainer(stateful_loss=True)``: the mean next-token cross-entropy over
    the vocabulary held, through ``gpt2.lm_loss`` (or ``ops/chunked_ce.py``
    with ``loss="chunked"``, the head product fused into the loss) with the
    embedding as the head.  The model carries nothing from step to step: the
    state comes back as it went in (``init_state``'s empty default)."""
    hidden, value = next_token_loss(loss, block, model.cfg.dtype)

    def loss_fn(params, model_state, batch):
        out = model.apply(params, batch, return_hidden=hidden)
        return value(out, params["params"]["embed_tokens"]["embedding"], batch), model_state

    return loss_fn
