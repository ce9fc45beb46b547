"""SmallThinker-21BA3B-Instruct's block as published, in plain ``jax.numpy``
float32: forward pass, loss, gradients and the AdamW steps the
``train_smallthinker_lm`` cells compare against.

Written from the published ``config.json`` (``model_name``
``smallthinker_21b_instruct``) and the layer equations of ISSUE 49 /
docs/SMALLTHINKER.md; it imports nothing of ``adapcc_tpu`` and takes nothing
the program made (the weights come from
:mod:`chipbench.weights_smallthinker_lm`, by the seed).  What it shares with
the other references is reference code too: the rounded product, the norm, the
rotation, the clipped AdamW.  RMSNorm(x) = x · rsqrt(mean(x²) + eps) · g, a
plain weight.

- ``h = E[ids]``: no scaling, no learned positions.
- A layer ``l`` of the published 52, stream ``h [T, 2560]``:
  ``logits = h W_r`` from the layer's input **as it comes, before any norm**;
  ``top, ids = top_k(logits, 6)``; ``w = softmax(top)`` over the six.  No
  bias vector, no scale, no auxiliary term.
- ``x = rmsnorm_in(h)``; ``q, k, v = x W_q, x W_k, x W_v`` (28 query heads on
  4 K/V heads of 128, no bias, no q/k norm, no gate); where ``rope_layout[l]``
  q and k rotated over the whole head (the two halves the pairs,
  ``rope_theta``), else no positions; causal softmax of ``q kᵀ / sqrt(128)`` a
  head and 1,024 queries at a time, the mask written out: where
  ``sliding_window_layout[l]`` query ``t`` sees keys ``t - 4095 … t``, else
  all ``<= t``; query head ``j`` reads K/V head ``j // 7``; ``h += (P v) W_o``.
- ``y = rmsnorm_post(h)``; for each HELD expert ``(relu(y W_gate) ∘ y W_up)
  W_down`` over every token times that token's weight for it (0 where it was
  not chosen): a loop over the held experts.  No shared expert.  What the
  experts not held would have added is left out, as in the program.
- ``logits = rmsnorm(h) W_headᵀ``, an untied head; mean next-token
  cross-entropy over the vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, attention one head
and one block of queries at a time, the experts one at a time, the head and
loss over slices of the sequence, the AdamW steps as donating calls.  In the
code the routing is computed behind the attention (a fault needs the stream
there); in the reference proper it reads the layer's input, which the
attention does not change.

``precision`` rounds every product's operands (``gpt2_ref._product``):
``float32`` is the reference, ``bfloat16`` and ``float8`` the controls.
``fault`` makes further controls ``correct`` has to fail, each the reference
with one piece of the mathematics changed, in the program's place
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
from chipbench.reference.trinity_ref import rms_norm, rotary, silu
from chipbench.weights_smallthinker_lm import layer_plan, sizes

SEQ_SLICE = 1024      # positions per slice of the head and the loss
QUERY_BLOCK = 1024    # queries per block of a head's attention
FAULTS = (
    "", "router_after_attention", "router_on_normed_input", "silu_experts", "softmax_over_all", "rope_on_global",
    "no_rope_on_window", "window_off",
)


def knobs(cfg, fault: str = "") -> Dict[str, Any]:
    """What a fault changes, each a flag the compiled step is given:
    ``router_after_attention`` (the router reads ``rmsnorm_post(h)`` behind
    the attention, as every other block of this repo does),
    ``router_on_normed_input`` (it reads ``rmsnorm_in(h)``: the alternative the
    configuration file names beside its assumption), ``silu_experts`` (the
    gate's activation the other models'), ``softmax_over_all`` (a softmax over
    the 64 logits, the chosen six as they come, not summing to 1),
    ``rope_on_global`` (positions on the layer that has none),
    ``no_rope_on_window`` (none on the layers that have them), ``window_off``
    (every layer sees all keys ``<= t``)."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    return {name: jnp.asarray(fault == name) for name in FAULTS if name}


def attention(x, p, rotated: bool, windowed: bool, cfg, prod, knob, query_block: int = QUERY_BLOCK):
    T = x.shape[0]
    s = sizes(cfg)
    H, Hkv, D, theta = s["H"], s["Hkv"], s["head"], float(cfg["rope_theta"])
    q = prod("td,de->te", x, p["q_proj"]["kernel"]).reshape(T, H, D)
    k = prod("td,de->te", x, p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = prod("td,de->te", x, p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    turn = knob["no_rope_on_window"] if rotated else knob["rope_on_global"]       # the fault that flips this layer's positions
    q, k = (jnp.where(turn != rotated, rotary(a, theta), a) for a in (q, k))
    banded = jnp.asarray(windowed) & ~knob["window_off"]
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
            ahead = (start + jnp.arange(size))[:, None] - jnp.arange(T)[None, :]
            seen = (ahead >= 0) & (~banded | (ahead < s["window"]))    # t - window < key <= t: window keys, t among them
            a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return None, prod("qk,kd->qd", a, vh)

        _, o = jax.lax.scan(block, None, (q[:, :, i], starts))
        return None, o.reshape(-1, D)[:T]

    _, o = jax.lax.scan(head, None, jnp.arange(H))                # [H, T, D]
    return prod("te,ed->td", o.transpose(1, 0, 2).reshape(T, H * D), p["o_proj"]["kernel"])


def route(r, router, cfg, prod, knob):
    """``(ids [T, k], weights [T, k])`` over ALL experts from the router's input ``r``."""
    logits = prod("td,de->te", r, router)
    top, ids = jax.lax.top_k(logits, int(cfg["moe_num_active_primary_experts"]))
    over_all = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), ids, axis=-1)
    return ids, jnp.where(knob["softmax_over_all"], over_all, jax.nn.softmax(top, axis=-1))


def held_experts(y, ids, weights, p, cfg, prod, knob):
    """The held experts' part: experts ``expert_offset … + held`` of all."""
    # weight of every expert for every token: 0 where it was not chosen
    table = jnp.sum(jax.nn.one_hot(ids, int(cfg["moe_num_primary_experts"]), dtype=y.dtype) * weights[..., None], axis=1)
    held = p["experts_w1"].shape[0]
    mine = jax.lax.dynamic_slice_in_dim(table, int(cfg.get("expert_offset", 0)), held, axis=1)

    @jax.checkpoint
    def expert(out, e):
        w1, w3, w2, weight = e
        gate = prod("td,dh->th", y, w1)
        gate = jnp.where(knob["silu_experts"], silu(gate), jnp.maximum(gate, 0.0))
        return out + weight[:, None] * prod("th,hd->td", gate * prod("td,dh->th", y, w3), w2), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y), (p["experts_w1"], p["experts_w3"], p["experts_w2"], mine.T))
    return out


def layer(h, p, rotated: bool, windowed: bool, cfg, prod, knob):
    eps = float(cfg["rms_norm_eps"])
    x = rms_norm(h, p["input_layernorm"]["scale"], eps)
    after = h + attention(x, p["self_attn"], rotated, windowed, cfg, prod, knob)
    y = rms_norm(after, p["post_attention_layernorm"]["scale"], eps)
    # the router's input: the layer's own input, which the attention has not touched; a fault's is another
    r = jnp.where(knob["router_after_attention"], y, jnp.where(knob["router_on_normed_input"], x, h))
    ids, weights = route(r, p["router"]["kernel"], cfg, prod, knob)
    return after + held_experts(y, ids, weights, p["block_sparse_moe"], cfg, prod, knob)


def hidden_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    knob = knobs(cfg) if knob is None else knob
    h = p["embed_tokens"]["embedding"][tokens]
    for i, (rotated, windowed) in enumerate(layer_plan(cfg)):
        one = jax.checkpoint(lambda h, lp, r=rotated, w=windowed: layer(h, lp, r, w, cfg, prod, knob))
        h = one(h, p[f"layers_{i}"])
    return rms_norm(h, p["norm"]["scale"], float(cfg["rms_norm_eps"]))


def logits_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    return _product(precision)("td,vd->tv", hidden_fn(params, tokens, cfg, precision, knob), params["params"]["lm_head"])


def nll_sum(params, tokens, cfg, precision: str = "float32", knob=None, seq_slice: int = SEQ_SLICE):
    """Summed next-token negative log-likelihood of one row ``tokens [T]``,
    the head and the loss over slices of the sequence."""
    prod = _product(precision)
    h = hidden_fn(params, tokens, cfg, precision, knob)[:-1]
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


def loss_and_grads(params, batch, cfg, precision: str = "float32", knob=None):
    """Mean next-token loss of ``batch [B, T]`` and its gradient, a row at a time."""
    B, T = batch.shape
    count = B * (T - 1)
    row_grads = jax.value_and_grad(nll_sum)

    def one(carry, row):
        loss, grads = row_grads(params, row, cfg, precision, knob)
        return (carry[0] + loss, jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    if B == 1:   # no second copy of the gradients for a sum of one
        loss, grads = row_grads(params, batch[0], cfg, precision, knob)
    else:
        zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(one, zero, batch)
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
