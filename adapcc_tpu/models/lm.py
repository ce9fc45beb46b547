"""What the decoder language models share (Trinity-Mini's, Kimi-Linear's,
JoyAI-LLM-Flash's, Granite 4.0-H's, Phi-4-mini-flash's, LFM2-24B-A2B's and
SmallThinker-21BA3B's blocks): the norm, the gated MLP, the bias-free
projection, the remat table, the state-space mixers' initialisers, and the
loss's one fork between float32 logits of the whole batch and the head product
fused into the loss.

A model file takes these by their public names from here and nothing private
from another model's file; what is one model's own stays in its file.
"""

from __future__ import annotations

import contextlib
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

#: a config's ``remat`` word to ``nn.remat``'s policy (False: no recomputation)
REMAT = {
    "none": False,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "full": None,   # recompute everything in the block
}

#: a step's ``loss`` word: float32 logits of the whole batch, or ``ops/chunked_ce.py``
LOSSES = ("dense", "chunked")


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def dense(features: int, cfg, name: str):
    """A projection with no bias, products in ``cfg.dtype``."""
    return nn.Dense(
        features, use_bias=False, dtype=cfg.dtype, name=name,
        kernel_init=nn.initializers.normal(0.02),
    )


class GatedMLP(nn.Module):
    """``(silu(x W1) ∘ x W3) W2``; ``cfg`` gives ``hidden_size`` and ``dtype``."""

    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h = nn.silu(dense(self.width, cfg, "gate_proj")(x)) * dense(self.width, cfg, "up_proj")(x)
        return dense(cfg.hidden_size, cfg, "down_proj")(h)


def taps_init(key, shape, dtype=jnp.float32):
    """A short convolution's taps ``[K, channels]``: uniform(-1/sqrt(K), 1/sqrt(K))."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def a_log_init(key, shape, dtype=jnp.float32):
    """``A_log = log(uniform(1, 16))``: a head forgets 1 to 16 times as fast as its gate says."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus(dt_bias)`` log-uniform in [0.001, 0.1], so that a step's
    decay ``exp(-exp(A_log) softplus(dt_bias))`` starts between 0.2 and 0.999:
    neither forgetting all nor nothing."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1


def next_token_loss(loss: str, block: int, dtype):
    """The loss's fork, for a model's ``stateful_loss``: ``(hidden, value)``.
    ``hidden`` is what the model is asked for (``model.apply(...,
    return_hidden=hidden)``: true under "chunked"), and ``value(out, head,
    tokens)`` the mean next-token cross-entropy of ``tokens [B, T]`` over the
    vocabulary held: under "dense" ``out`` is the float32 logits
    (``gpt2.lm_loss``; ``head`` is not read), under "chunked" what stands in
    front of the head ``[vocab, hidden]``, whose product is fused into the
    loss ``block`` rows of the vocabulary at a time with products in ``dtype``
    (``ops/chunked_ce.py``, under the scope ``loss``; ``scope=None`` where the
    caller stands in a scope of its own: a second term's)."""
    from adapcc_tpu.models.gpt2 import lm_loss

    if loss not in LOSSES:
        raise ValueError(f"loss {loss!r} not in {LOSSES}")
    chunked = loss == "chunked"

    def value(out, head, tokens, scope="loss"):
        if not chunked:
            return lm_loss(out, tokens)
        from adapcc_tpu.ops.chunked_ce import chunked_lm_loss

        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            return chunked_lm_loss(out, head, tokens, block, dtype)

    return chunked, value
