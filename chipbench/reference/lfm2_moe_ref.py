"""LFM2-24B-A2B's block as published, in plain ``jax.numpy`` float32: forward
pass, loss, gradients and the AdamW steps the ``train_lfm2_lm`` cells compare
against.

Written from the published ``config.json`` (``model_type`` ``lfm2_moe``) and
the layer equations of ISSUE 45 / docs/LFM2_MOE.md; it imports nothing of
``adapcc_tpu`` and takes nothing the program made (the weights come from
:mod:`chipbench.weights_lfm2_lm`, by the seed).  What it shares with the other
references is reference code too: the rounded product, the norm, the gated
MLP, the rotation, the clipped AdamW.  RMSNorm(x) = x · rsqrt(mean(x²) + eps)
· g, a plain weight.

- ``h = E[ids]``: no scaling, no learned positions.
- A layer, two norms: ``h += mixer(norm(h))`` (``operator_norm``); ``h +=
  ffn(norm(h))`` (``ffn_norm``).  Layer ``l`` of the published forty mixes by
  ``layer_types[l]`` and feeds forward densely where ``l`` is under the
  published ``num_dense_layers``.
- ``conv``: ``[B, C, x] = u W_in`` (three equal thirds in that order); ``z = B
  ∘ x``; ``c_t = sum_i taps[i] z_{t-(K-1)+i}``, depthwise, causal, zeros before
  the row, **as K shifted products**, no bias, no activation; ``out = (C ∘ c)
  W_out``.
- ``full_attention`` (32 heads of 64 on 8 K/V heads): ``q, k, v = u Wq, u Wk, u
  Wv``; q and k RMS-normed per head, **then** rotated over the whole head (the
  two halves the pairs, ``rope_theta``); causal softmax of ``q kᵀ / sqrt(64)``
  a head and 1,024 queries at a time, the mask written out; ``(P v) Wo``.
- Dense MLP ``(silu(u W1) ∘ u W3) W2``.  Experts: ``s = sigmoid(u Wr)``; the
  top 4 of ``s + b`` (``b`` held at zero, no gradient); ``w = s[top] / (sum +
  1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``; for each HELD
  expert its gated MLP over every token times that token's weight for it (0
  where it was not chosen): a loop over the held experts.  No shared expert.
  What the experts not held would have added is left out, as in the program.
- ``logits = rmsnorm(h) Eᵀ`` (``embedding_norm``) through the embedding itself;
  mean next-token cross-entropy over the vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, attention one head
and one block of queries at a time, the experts one at a time, the head and
loss over slices of the sequence, the AdamW steps as donating calls.

``precision`` rounds every product's operands (``gpt2_ref._product``):
``float32`` is the reference, ``bfloat16`` and ``float8`` the controls.
``fault`` makes ten further controls ``correct`` has to fail, each the
reference with one piece of the mathematics changed, in the program's place
(:data:`FAULTS`, :func:`knobs`).  A fault is numbers the compiled step is
*given*, so the reference and the faults are one compiled program.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, leaf_norms
from chipbench.reference.trinity_ref import gated_mlp, rms_norm, rotary, silu
from chipbench.weights_lfm2_lm import layer_plan, sizes

SEQ_SLICE = 1024      # positions per slice of the head and the loss
QUERY_BLOCK = 1024    # queries per block of a head's attention
TOPK_NORM_EPS = 1e-6  # under the sum of a token's chosen scores, as published
FAULTS = (
    "", "no_in_gate", "no_out_gate", "silu_on_conv", "taps_late", "gates_swapped", "softmax_router", "no_topk_norm",
    "rope_before_norm", "no_qk_norm", "untied_head",
)


def knobs(cfg, fault: str = "") -> Dict[str, Any]:
    """What a fault changes, each a flag the compiled step is given:
    ``no_in_gate`` (``B`` left out: ``z = x``), ``no_out_gate`` (``C`` left
    out), ``silu_on_conv`` (the activation the scans' convolutions apply),
    ``taps_late`` (the convolution one step less causal: ``z_{t-1 .. t+1}``),
    ``gates_swapped`` (``B ∘ conv(C ∘ x)``), ``softmax_router`` (the scores a
    softmax over the experts), ``no_topk_norm`` (the chosen scores as they
    are), ``rope_before_norm`` (rotated, then normed), ``no_qk_norm``,
    ``untied_head`` (the head's product hands the embedding no gradient: the
    head a matrix of its own that happens to start equal)."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    return {name: jnp.asarray(fault == name) for name in FAULTS if name}


def short_conv(z, taps, late):
    """``c_t = sum_i taps[i] z_{t-(K-1)+i}`` as ``K`` shifted products of
    ``z [T, C]`` from zeros; ``late``: every tap one step later (the fault)."""
    K, T = taps.shape[0], z.shape[0]
    padded = jnp.pad(z, ((K - 1, 1), (0, 0)))
    causal = sum(taps[i] * padded[i:i + T] for i in range(K))
    return jnp.where(late, sum(taps[i] * padded[i + 1:i + 1 + T] for i in range(K)), causal)


def conv_mixer(u, p, cfg, prod, knob):
    d = u.shape[-1]
    bcx = prod("td,de->te", u, p["in_proj"]["kernel"])
    B, C, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    B, C = jnp.where(knob["gates_swapped"], C, B), jnp.where(knob["gates_swapped"], B, C)
    z = jnp.where(knob["no_in_gate"], x, B * x)
    c = short_conv(z, p["conv_taps"], knob["taps_late"])
    c = jnp.where(knob["silu_on_conv"], silu(c), c)
    return prod("te,ed->td", jnp.where(knob["no_out_gate"], c, C * c), p["out_proj"]["kernel"])


def attention_mixer(u, p, cfg, prod, knob, query_block: int = QUERY_BLOCK):
    T = u.shape[0]
    s = sizes(cfg)
    H, Hkv, D = s["H"], s["Hkv"], s["head"]
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_parameters"]["rope_theta"])
    q = prod("td,de->te", u, p["q_proj"]["kernel"]).reshape(T, H, D)
    k = prod("td,de->te", u, p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = prod("td,de->te", u, p["v_proj"]["kernel"]).reshape(T, Hkv, D)

    def positioned(x, scale):
        normed = jnp.where(knob["no_qk_norm"], x, rms_norm(x, scale, eps))
        return jnp.where(knob["rope_before_norm"], rms_norm(rotary(x, theta), scale, eps), rotary(normed, theta))

    q, k = positioned(q, p["q_layernorm"]["scale"]), positioned(k, p["k_layernorm"]["scale"])
    size = min(query_block, T)
    pad = (-T) % size
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, size, H, D)
    starts = jnp.arange(q.shape[0]) * size

    def head(_, i):
        kh, vh = k[:, i // (H // Hkv)], v[:, i // (H // Hkv)]     # the K/V head that query head i reads

        @jax.checkpoint
        def block(_, inp):
            qb, start = inp                                       # the head's queries start .. start + size
            scores = prod("qd,kd->qk", qb, kh) / math.sqrt(D)
            seen = (start + jnp.arange(size))[:, None] >= jnp.arange(T)[None, :]
            a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return None, prod("qk,kd->qd", a, vh)

        _, o = jax.lax.scan(block, None, (q[:, :, i], starts))
        return None, o.reshape(-1, D)[:T]

    _, o = jax.lax.scan(head, None, jnp.arange(H))                # [H, T, D]
    return prod("te,ed->td", o.transpose(1, 0, 2).reshape(T, H * D), p["out_proj"]["kernel"])


def route(x, p, cfg, prod, knob):
    """``(ids [T, k], weights [T, k])`` over ALL experts."""
    logits = prod("td,de->te", x, p["router"])
    scores = jnp.where(knob["softmax_router"], jax.nn.softmax(logits, axis=-1), jax.nn.sigmoid(logits))
    ranked = scores + jax.lax.stop_gradient(p["expert_bias"]) if cfg.get("use_expert_bias", True) else scores
    _, ids = jax.lax.top_k(ranked, int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        normed = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + TOPK_NORM_EPS)
        chosen = jnp.where(knob["no_topk_norm"], chosen, normed)
    return ids, chosen * float(cfg.get("routed_scaling_factor", 1.0))


def sparse_ffn(x, p, cfg, prod, knob):
    """The held experts' part: experts ``expert_offset … + held`` of ``num_experts``."""
    ids, weights = route(x, p, cfg, prod, knob)
    # weight of every expert for every token: 0 where it was not chosen
    table = jnp.sum(jax.nn.one_hot(ids, int(cfg["num_experts"]), dtype=x.dtype) * weights[..., None], axis=1)
    held = p["experts_w1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(table, int(cfg.get("expert_offset", 0)), held, axis=1)

    @jax.checkpoint
    def expert(y, e):
        w1, w3, w2, weight = e
        return y + weight[:, None] * gated_mlp(x, w1, w3, w2, prod), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (p["experts_w1"], p["experts_w3"], p["experts_w2"], mine.T))
    return y


def layer(h, p, kind: str, sparse: bool, cfg, prod, knob):
    eps = float(cfg["norm_eps"])
    u = rms_norm(h, p["operator_norm"]["scale"], eps)
    if kind == "conv":
        h = h + conv_mixer(u, p["conv"], cfg, prod, knob)
    else:
        h = h + attention_mixer(u, p["self_attn"], cfg, prod, knob)
    x, ffn = rms_norm(h, p["ffn_norm"]["scale"], eps), p["feed_forward"]
    if sparse:
        return h + sparse_ffn(x, ffn, cfg, prod, knob)
    return h + gated_mlp(x, ffn["gate_proj"]["kernel"], ffn["up_proj"]["kernel"], ffn["down_proj"]["kernel"], prod)


def hidden_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    knob = knobs(cfg) if knob is None else knob
    h = p["embed_tokens"]["embedding"][tokens]
    for i, (kind, sparse) in enumerate(layer_plan(cfg)):
        one = jax.checkpoint(lambda h, lp, kind=kind, sparse=sparse: layer(h, lp, kind, sparse, cfg, prod, knob))
        h = one(h, p[f"layers_{i}"])
    return rms_norm(h, p["embedding_norm"]["scale"], float(cfg["norm_eps"]))


def head_of(params, knob):
    """The head: the embedding itself; under ``untied_head`` a matrix that
    takes no gradient back to it."""
    embedding = params["params"]["embed_tokens"]["embedding"]
    return jnp.where(knob["untied_head"], jax.lax.stop_gradient(embedding), embedding)


def logits_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    knob = knobs(cfg) if knob is None else knob
    return _product(precision)("td,vd->tv", hidden_fn(params, tokens, cfg, precision, knob), head_of(params, knob))


def nll_sum(params, tokens, cfg, precision: str = "float32", knob=None, seq_slice: int = SEQ_SLICE):
    """Summed next-token negative log-likelihood of one row ``tokens [T]``,
    the tied head and the loss over slices of the sequence."""
    prod = _product(precision)
    knob = knobs(cfg) if knob is None else knob
    h = hidden_fn(params, tokens, cfg, precision, knob)[:-1]
    targets = tokens[1:]
    n = h.shape[0]
    size = min(seq_slice, n)
    pad = (-n) % size
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, size, h.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, size)
    live = (jnp.arange(n + pad) < n).reshape(-1, size)
    head = head_of(params, knob)

    @jax.checkpoint
    def one(total, part):
        x, y, keep = part
        logp = jax.nn.log_softmax(prod("td,vd->tv", x, head), axis=-1)
        picked = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return total - jnp.sum(jnp.where(keep, picked, 0.0)), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (h, targets, live))
    return total


def loss_and_grads(params, batch, cfg, precision: str = "float32", knob=None):
    """Mean next-token loss of ``batch [B, T]`` and its gradient, a row at a time."""
    B, T = batch.shape
    count = B * (T - 1)
    loss, grads = jax.value_and_grad(nll_sum)(params, batch[0], cfg, precision, knob)
    for row in batch[1:]:
        more, g = jax.value_and_grad(nll_sum)(params, row, cfg, precision, knob)
        loss, grads = loss + more, jax.tree_util.tree_map(jnp.add, grads, g)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grads)


@functools.lru_cache(maxsize=4)
def _compiled_step(stated: str, precision: str):
    """One clipped AdamW step as a donating call, for the configuration and
    optimizer ``stated`` (their JSON): kept, so that every seed and every
    fault of a process run the program compiled for the first."""
    cfg, opt = json.loads(stated)

    def step(p, mu, nu, count, batch, knob):
        loss, grads = loss_and_grads(p, batch, cfg, precision, knob)
        grads = clip_by_global_norm(grads, opt["clip_norm"])
        norms = leaf_norms(grads)
        p, mu, nu = adamw_update(p, grads, mu, nu, count, opt)
        return p, mu, nu, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_steps(params, batches, cfg, opt: Dict[str, float], init, precision: str = "float32", fault: str = ""):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each, each a donating call so that parameters and
    both moments exist once.  ``init()`` makes the initial parameters anew.
    Returns what ``gpt2_ref.train_steps`` returns."""
    step = _compiled_step(json.dumps([cfg, opt], sort_keys=True), precision)
    knob = knobs(cfg, fault)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches, start=1):
        params, mu, nu, loss, norms = step(params, mu, nu, jnp.asarray(float(i)), jnp.asarray(batch), knob)
        losses.append(loss)
        first = norms if first is None else first
    del mu, nu
    moved = jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0)))
    return {"losses": jnp.stack(losses), "grad_norms": first, "update_norms": moved(params, init())}
