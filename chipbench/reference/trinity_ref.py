"""Trinity-Mini's block as published, in plain ``jax.numpy`` float32: forward
pass, loss, gradients and the AdamW steps the ``train_moe_lm`` cells compare
against.

Written from the published ``config.json`` (``model_type`` ``afmoe``) and the
layer equations of ISSUE 26 / docs/TRINITY.md; it imports nothing of
``adapcc_tpu`` and takes nothing the program made (the weights come from
:mod:`chipbench.weights_moe_lm`, by the seed).  RMSNorm(x) = x · rsqrt(mean(x²)
+ eps) · g throughout.

- Embedding times ``sqrt(hidden_size)`` (``mup_enabled``); no learned positions.
- A layer, four norms: ``h += norm(attn(norm(h)))``; ``h += norm(ffn(norm(h)))``.
- Attention: 32 query heads on 4 KV heads of 128 (head ``i`` reads KV head
  ``i // 8``), no biases; q and k RMS-normed per head; rotary positions over
  the whole head on a ``sliding_attention`` layer and none on a
  ``full_attention`` layer; scores / sqrt(128); the mask written out (causal,
  and on a sliding layer ``t - s < sliding_window``); the output times
  ``sigmoid(x Wg)``, then ``Wo``.
- Dense FFN ``(silu(x W1) ∘ x W3) W2``.  Expert FFN: ``s = sigmoid(x Wr)``;
  the top 8 of ``s + b`` (``b`` held at zero, no gradient); weights ``s[top] /
  (sum + 1e-20) · route_scale``; the shared expert plus, for each HELD expert,
  its gated MLP over every token times that token's weight for it (0 where
  it was not chosen): a loop over the held experts with a 0/1 mask.  What the
  experts not held would have added is left out, as in the program.
- Final RMSNorm, untied head, mean next-token cross-entropy over the
  vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, attention one
query head at a time, the head and loss over slices of the sequence, the
three AdamW steps as three donating calls.

``precision`` rounds every product's operands (``float32``: none, products at
``highest``; ``bfloat16``; ``float8``): the first is the reference, the others
the controls ``correct`` has to fail (``gpt2_ref._product``).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, leaf_norms

#: positions per slice of the head and the loss: float32 logits of a slice
#: are 1,024 x 25,024 x 4 B = 103 MB
SEQ_SLICE = 1024


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta: float):
    """``x [T, H, D]``: pairs are ``(x[i], x[i + D/2])``, angle ``t · theta^(-2i/D)``."""
    T, _, D = x.shape
    inv_freq = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    angle = jnp.asarray(np.arange(T)[:, None] * inv_freq[None, :], jnp.float32)[:, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1
    )


def silu(x):
    return x * jax.nn.sigmoid(x)


def gated_mlp(x, w1, w3, w2, prod):
    return prod("td,dh->th", silu(prod("td,dh->th", x, w1)) * prod("td,dh->th", x, w3), w2)


def attention(x, p, sliding: bool, cfg, prod):
    T = x.shape[0]
    H, Hkv, D = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    q = prod("td,de->te", x, p["q_proj"]["kernel"]).reshape(T, H, D)
    k = prod("td,de->te", x, p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = prod("td,de->te", x, p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    gate = prod("td,de->te", x, p["gate_proj"]["kernel"])
    q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding:
        q, k = rotary(q, float(cfg["rope_theta"])), rotary(k, float(cfg["rope_theta"]))
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0
    if sliding:
        seen = seen & (ahead < int(cfg["sliding_window"]))
    group = H // Hkv

    @jax.checkpoint
    def head(_, i):
        s = prod("qd,kd->qk", q[:, i], k[:, i // group]) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return None, prod("qk,kd->qd", a, v[:, i // group])

    _, o = jax.lax.scan(head, None, jnp.arange(H))                 # [H, T, D]
    o = o.transpose(1, 0, 2).reshape(T, H * D) * jax.nn.sigmoid(gate)
    return prod("te,ed->td", o, p["o_proj"]["kernel"])


def route(x, p, cfg, prod):
    """``(ids [T, k], weights [T, k])`` over ALL experts."""
    scores = jax.nn.sigmoid(prod("td,de->te", x, p["router"]))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(p["expert_bias"]), int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("route_norm", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return ids, chosen * float(cfg["route_scale"])


def sparse_ffn(x, p, cfg, prod):
    shared = p["shared_experts"]
    y = gated_mlp(
        x, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"], shared["down_proj"]["kernel"], prod
    )
    ids, weights = route(x, p, cfg, prod)
    # weight of every expert for every token: 0 where it was not chosen
    table = jnp.sum(jax.nn.one_hot(ids, int(cfg["num_experts"]), dtype=x.dtype) * weights[..., None], axis=1)
    held = p["experts_w1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(table, int(cfg.get("expert_offset", 0)), held, axis=1)

    @jax.checkpoint
    def expert(y, e):
        w1, w3, w2, weight = e
        return y + weight[:, None] * gated_mlp(x, w1, w3, w2, prod), None

    y, _ = jax.lax.scan(expert, y, (p["experts_w1"], p["experts_w3"], p["experts_w2"], mine.T))
    return y


def after_attention(h, p, kind: str, cfg, prod):
    eps = float(cfg["rms_norm_eps"])
    a = attention(
        rms_norm(h, p["input_layernorm"]["scale"], eps), p["self_attn"],
        kind == "sliding_attention", cfg, prod,
    )
    return h + rms_norm(a, p["post_attention_layernorm"]["scale"], eps)


def after_ffn(h, p, sparse: bool, cfg, prod):
    eps = float(cfg["rms_norm_eps"])
    x = rms_norm(h, p["pre_mlp_layernorm"]["scale"], eps)
    if sparse:
        m = sparse_ffn(x, p["mlp"], cfg, prod)
    else:
        mlp = p["mlp"]
        m = gated_mlp(x, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], prod)
    return h + rms_norm(m, p["post_mlp_layernorm"]["scale"], eps)


def layer(h, p, kind: str, sparse: bool, cfg, prod):
    return after_ffn(after_attention(h, p, kind, cfg, prod), p, sparse, cfg, prod)


def kinds_of(cfg) -> tuple:
    return tuple(cfg.get("layer_types_here") or cfg["layer_types"][: int(cfg["num_hidden_layers"])])


def hidden_fn(params, tokens, cfg, precision: str = "float32"):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    h = embed(params, tokens, cfg)
    for i, kind in enumerate(kinds_of(cfg)):
        sparse = i >= int(cfg["num_dense_layers"])
        one = jax.checkpoint(lambda h, lp, kind=kind, sparse=sparse: layer(h, lp, kind, sparse, cfg, prod))
        h = one(h, p[f"layers_{i}"])
    return rms_norm(h, p["norm"]["scale"], float(cfg["rms_norm_eps"]))


def embed(params, tokens, cfg):
    h = params["params"]["embed_tokens"]["embedding"][tokens]
    return h * math.sqrt(int(cfg["hidden_size"])) if cfg.get("mup_enabled", True) else h


def routing_ids(params, tokens, cfg, precision: str = "float32"):
    """The experts each token of ``tokens [T]`` chose in each expert layer,
    ``[expert layers, T, k]`` (to count the choices that flip with the
    precision)."""
    p = params["params"]
    prod = _product(precision)
    h, chosen = embed(params, tokens, cfg), []
    for i, kind in enumerate(kinds_of(cfg)):
        lp, sparse = p[f"layers_{i}"], i >= int(cfg["num_dense_layers"])
        h = after_attention(h, lp, kind, cfg, prod)
        if sparse:
            x = rms_norm(h, lp["pre_mlp_layernorm"]["scale"], float(cfg["rms_norm_eps"]))
            chosen.append(route(x, lp["mlp"], cfg, prod)[0])
        h = after_ffn(h, lp, sparse, cfg, prod)
    return jnp.stack(chosen)


def logits_fn(params, tokens, cfg, precision: str = "float32"):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    h = hidden_fn(params, tokens, cfg, precision)
    return _product(precision)("td,vd->tv", h, params["params"]["lm_head"])


def nll_sum(params, tokens, cfg, precision: str = "float32", seq_slice: int = SEQ_SLICE):
    """Summed next-token negative log-likelihood of one row ``tokens [T]``,
    the head and the loss over slices of the sequence."""
    prod = _product(precision)
    h = hidden_fn(params, tokens, cfg, precision)[:-1]
    targets = tokens[1:]
    n = h.shape[0]
    size = min(seq_slice, n)
    pad = (-n) % size
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, size, h.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, size)
    live = (jnp.arange(n + pad) < n).reshape(-1, size)
    head = params["params"]["lm_head"]

    @jax.checkpoint
    def one(total, part):
        x, y, keep = part
        logp = jax.nn.log_softmax(prod("td,vd->tv", x, head), axis=-1)
        picked = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return total - jnp.sum(jnp.where(keep, picked, 0.0)), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (h, targets, live))
    return total


def loss_and_grads(params, batch, cfg, precision: str = "float32"):
    """Mean next-token loss of ``batch [B, T]`` and its gradient, a row at a time."""
    B, T = batch.shape
    count = B * (T - 1)

    def one(carry, row):
        loss, grads = jax.value_and_grad(nll_sum)(params, row, cfg, precision)
        return (carry[0] + loss, jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    if B == 1:   # no second copy of the gradients for a sum of one
        loss, grads = jax.value_and_grad(nll_sum)(params, batch[0], cfg, precision)
    else:
        zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(one, zero, batch)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grads)


def train_steps(params, batches, cfg, opt: Dict[str, float], init, precision: str = "float32"):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each, each a donating call so that parameters and
    both moments exist once.  ``init()`` makes the initial parameters anew
    (they are not kept).  Returns what ``gpt2_ref.train_steps`` returns."""

    def step(p, mu, nu, count, batch):
        loss, grads = loss_and_grads(p, batch, cfg, precision)
        grads = clip_by_global_norm(grads, opt["clip_norm"])
        norms = leaf_norms(grads)
        p, mu, nu = adamw_update(p, grads, mu, nu, count, opt)
        return p, mu, nu, loss, norms

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches, start=1):
        params, mu, nu, loss, norms = step(params, mu, nu, jnp.asarray(float(i)), jnp.asarray(batch))
        losses.append(loss)
        first = norms if first is None else first
    del mu, nu
    moved = jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0)))
    return {"losses": jnp.stack(losses), "grad_norms": first, "update_norms": moved(params, init())}
