"""Kimi-Linear's block as published, in plain ``jax.numpy`` float32: forward
pass, loss, gradients and the AdamW steps the ``train_hybrid_lm`` cells
compare against.

Written from the published ``config.json`` (``model_type`` ``kimi_linear``)
and the layer equations of ISSUE 32 / docs/KIMI_LINEAR.md; it imports nothing
of ``adapcc_tpu`` and takes nothing the program made (the weights come from
:mod:`chipbench.weights_hybrid_lm`, by the seed).  What it shares with
:mod:`chipbench.reference.trinity_ref` is reference code too: the norm, the
gated MLP and the expert layer by a loop over the held experts with a 0/1
mask (Kimi's router is Trinity's to the letter).  RMSNorm(x) = x · rsqrt(mean(x²)
+ eps) · g; l2norm(x) = x · rsqrt(sum(x²) + 1e-6).

- Embedding as it is; no positions anywhere (``mla_use_nope``: the 64
  ``qk_rope_head_dim`` channels are carried and never rotated).
- A layer, two norms: ``h += mixer(norm(h))``; ``h += ffn(norm(h))``.
- KDA (32 heads of 128): ``q = l2norm(silu(conv4(x Wq)))``, ``k`` likewise,
  ``v = silu(conv4(x Wv))``; ``conv4`` causal, depthwise, 4 taps, no bias;
  ``g = -exp(A_log[h]) · softplus(x Wf↓ Wf↑ + dt_bias)``, ``beta = sigmoid(x
  Wb)``; the state **a step at a time**: ``S = diag(exp g_t) S``; ``S += beta_t
  k_t (v_t - Sᵀ k_t)ᵀ``; ``o_t = Sᵀ q_t / sqrt(128)`` (:func:`kda_recurrence`:
  no chunk, no inverse, the algorithm under test shares nothing with it);
  ``y = (rmsnorm_128(o) ∘ sigmoid(x Wg↓ Wg↑)) Wo``.
- Latent attention (32 heads): ``q = x Wq`` of 192; ``[c, k_pe] = x Wkv↓``;
  ``[k_nope, v] = rmsnorm(c) Wkv↑``; ``k = [k_nope, k_pe]``, ``k_pe`` the same
  for every head; causal softmax of ``q kᵀ / sqrt(192)`` a head at a time,
  the mask written out; ``(P v) Wo``.
- FFN: layer 1 ``(silu(x W1) ∘ x W3) W2`` at 9,216; after it the shared
  expert plus the held routed experts' part (``trinity_ref.sparse_ffn``).
- Final RMSNorm, untied head, mean next-token cross-entropy over the
  vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, the recurrence
rematerialised in blocks of 64 steps, attention one head at a time, the head
and loss over slices of the sequence, the AdamW steps as donating calls.
Loops are ``lax.scan``s, not unrolled code: the compiled entry stays small.

``precision`` rounds every product's operands (``gpt2_ref._product``), the
recurrence's three products a step among them: ``float32`` is the reference,
``bfloat16`` and ``float8`` the controls.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, leaf_norms
from chipbench.reference.trinity_ref import gated_mlp, rms_norm, silu, sparse_ffn
from chipbench.weights_hybrid_lm import layer_kinds

SEQ_SLICE = 1024      # positions per slice of the head and the loss
SCAN_BLOCK = 64       # steps of the recurrence rematerialised together
L2_EPS = 1e-6


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def short_conv(x, taps):
    """``y_t = sum_j taps[j] · x_{t-(K-1)+j}`` for ``x [T, C]``, ``taps [K, C]``."""
    K, T = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[j] * padded[j:j + T] for j in range(K))


def kda_recurrence(q, k, v, g, beta, scale: float, prod, block: int = SCAN_BLOCK):
    """The gated delta rule a step at a time over ``q, k, g [T, H, dk]``,
    ``v [T, H, dv]``, ``beta [T, H]`` from a zero state: ``o [T, H, dv]``."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    pad = (-T) % block                       # a padded step forgets and writes nothing
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) for x in (q, k, v, g, beta)]
    xs = [x.reshape(((T + pad) // block, block) + x.shape[1:]) for x in xs]

    def step(S, x):
        q, k, v, g, b = x
        S = S * jnp.exp(g)[..., None]
        S = S + prod("hk,hv->hkv", b[:, None] * k, v - prod("hkv,hk->hv", S, k))
        return S, prod("hkv,hk->hv", S, q) * scale

    @jax.checkpoint
    def steps(S, x):
        return jax.lax.scan(step, S, x)

    _, o = jax.lax.scan(steps, jnp.zeros((H, dk, dv), jnp.float32), xs)
    return o.reshape(T + pad, H, dv)[:T]


def kda_mixer(x, p, cfg, prod):
    T = x.shape[0]
    group = cfg["linear_attn_config"]
    H, D = int(group["num_heads"]), int(group["head_dim"])

    def mixed(name):
        return silu(short_conv(prod("td,de->te", x, p[f"{name}_proj"]["kernel"]), p[f"{name}_conv"])).reshape(T, H, D)

    q, k, v = l2norm(mixed("q")), l2norm(mixed("k")), mixed("v")
    f = prod("tr,re->te", prod("td,dr->tr", x, p["f_a_proj"]["kernel"]), p["f_b_proj"]["kernel"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"]).reshape(T, H, D)
    beta = jax.nn.sigmoid(prod("td,dh->th", x, p["b_proj"]["kernel"]))
    o = kda_recurrence(q, k, v, g, beta, 1.0 / math.sqrt(D), prod)
    o = rms_norm(o, p["o_norm"]["scale"], float(cfg["rms_norm_eps"]))
    gate = prod("tr,re->te", prod("td,dr->tr", x, p["g_a_proj"]["kernel"]), p["g_b_proj"]["kernel"])
    return prod("te,ed->td", o.reshape(T, H * D) * jax.nn.sigmoid(gate), p["o_proj"]["kernel"])


def mla_mixer(x, p, cfg, prod):
    T = x.shape[0]
    H, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, pe, dv = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    q = prod("td,de->te", x, p["q_proj"]["kernel"]).reshape(T, H, nope + pe)
    down = prod("td,de->te", x, p["kv_a_proj_with_mqa"]["kernel"])
    latent, k_pe = down[:, :rank], down[:, rank:]
    up = prod(
        "tr,re->te", rms_norm(latent, p["kv_a_layernorm"]["scale"], float(cfg["rms_norm_eps"])),
        p["kv_b_proj"]["kernel"],
    ).reshape(T, H, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    @jax.checkpoint
    def head(_, i):
        k = jnp.concatenate([k_nope[:, i], k_pe], axis=-1)          # k_pe the same for every head, not rotated
        s = prod("qd,kd->qk", q[:, i], k) / math.sqrt(nope + pe)
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return None, prod("qk,kd->qd", a, v[:, i])

    _, o = jax.lax.scan(head, None, jnp.arange(H))                  # [H, T, dv]
    return prod("te,ed->td", o.transpose(1, 0, 2).reshape(T, H * dv), p["o_proj"]["kernel"])


def router_keys(cfg) -> Dict[str, Any]:
    """The router's settings under the key names ``trinity_ref.sparse_ffn`` reads."""
    return {
        "num_experts": cfg["num_experts"], "num_experts_per_tok": cfg["num_experts_per_token"],
        "route_norm": cfg["moe_renormalize"], "route_scale": cfg["routed_scaling_factor"],
        "expert_offset": cfg.get("expert_offset", 0),
    }


def layer(h, p, kind: str, sparse: bool, cfg, prod):
    eps = float(cfg["rms_norm_eps"])
    mixer = kda_mixer if kind == "kda" else mla_mixer
    h = h + mixer(rms_norm(h, p["input_layernorm"]["scale"], eps), p["self_attn"], cfg, prod)
    x = rms_norm(h, p["post_attention_layernorm"]["scale"], eps)
    if sparse:
        return h + sparse_ffn(x, p["mlp"], router_keys(cfg), prod)
    mlp = p["mlp"]
    return h + gated_mlp(x, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], prod)


def hidden_fn(params, tokens, cfg, precision: str = "float32"):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    h = p["embed_tokens"]["embedding"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        sparse = i >= int(cfg["first_k_dense_replace"])
        one = jax.checkpoint(lambda h, lp, kind=kind, sparse=sparse: layer(h, lp, kind, sparse, cfg, prod))
        h = one(h, p[f"layers_{i}"])
    return rms_norm(h, p["norm"]["scale"], float(cfg["rms_norm_eps"]))


def logits_fn(params, tokens, cfg, precision: str = "float32"):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    return _product(precision)("td,vd->tv", hidden_fn(params, tokens, cfg, precision), params["params"]["lm_head"])


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
    loss, grads = jax.value_and_grad(nll_sum)(params, batch[0], cfg, precision)
    for row in batch[1:]:
        more, g = jax.value_and_grad(nll_sum)(params, row, cfg, precision)
        loss, grads = loss + more, jax.tree_util.tree_map(jnp.add, grads, g)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grads)


def train_steps(params, batches, cfg, opt: Dict[str, float], init, precision: str = "float32"):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each, each a donating call so that parameters and
    both moments exist once.  ``init()`` makes the initial parameters anew.
    Returns what ``gpt2_ref.train_steps`` returns."""

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
