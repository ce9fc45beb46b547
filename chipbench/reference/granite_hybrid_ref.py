"""Granite 4.0-H's block as published, in plain ``jax.numpy`` float32: forward
pass, loss, gradients and the AdamW steps the ``train_ssm_lm`` cells compare
against.

Written from the published ``config.json`` (``model_type``
``granitemoehybrid``, ``num_local_experts`` 0) and the layer equations of
ISSUE 39 / docs/GRANITE_HYBRID.md; it imports nothing of ``adapcc_tpu`` and
takes nothing the program made (the weights come from
:mod:`chipbench.weights_ssm_lm`, by the seed).  What it shares with the other
references is reference code too: the rounded product, the norm, the gated
MLP, the short convolution, the clipped AdamW.  RMSNorm(x) = x · rsqrt(mean(x²)
+ eps) · g.

- ``h = embedding_multiplier · E[ids]``; no positions anywhere.
- A layer, two norms, both branches scaled: ``h += r · mixer(norm(h))``;
  ``h += r · mlp(norm(h))``, ``r = residual_multiplier``, ``mlp(u) = (silu(u
  W1) ∘ u W3) W2`` at ``shared_intermediate_size``.
- Mamba-2 (64 heads of 64, state 128, one group): ``[z, xBC, dt] = u W_in``;
  ``xBC = silu(conv4(xBC) + b)``, causal, depthwise, 4 taps; ``[x, B, C] =
  split(xBC)``; ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state
  **a step at a time**: ``H = exp(Δ_t A) H + Δ_t x_t ⊗ B_t``; ``y_t = H C_t +
  D x_t`` (:func:`ssm_recurrence`: no chunk, no masked product, the algorithm
  under test shares nothing with it); ``out = (rmsnorm_4096(y ∘ silu(z))) W_out``.
- Attention (32 heads of 64 on 8 K/V heads): ``q, k, v = u Wq, u Wk, u Wv``,
  no rotation; causal softmax of ``attention_multiplier · q kᵀ`` a head and
  1,024 queries at a time, the mask written out; ``(P v) Wo``.
- ``logits = rmsnorm(h) Eᵀ / logits_scaling`` through the embedding itself;
  mean next-token cross-entropy over the vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, the recurrence
rematerialised in blocks of 64 steps, attention one head and one block of
queries at a time, the head and loss over slices of the sequence, the AdamW
steps as donating calls.  Loops are ``lax.scan``s: the compiled entry stays
small.

``precision`` rounds every product's operands (``gpt2_ref._product``), the
recurrence's two products a step among them: ``float32`` is the reference,
``bfloat16`` and ``float8`` the controls.  ``fault`` makes three further
controls ``correct`` has to fail, each the reference with one part of the
mathematics changed, in the program's place: ``"sqrt_scale"`` (the scores
times ``1 / sqrt(64)``, not ``attention_multiplier``), ``"norm_before_gate"``
(``rmsnorm(y) ∘ silu(z)``) and ``"no_skip"`` (``D`` left out).  A fault is
three numbers the compiled step is *given*, so the reference and the faults
are one compiled program, kept from one call to the next.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, leaf_norms
from chipbench.reference.kimi_linear_ref import short_conv
from chipbench.reference.trinity_ref import gated_mlp, rms_norm, silu
from chipbench.weights_ssm_lm import layer_kinds

SEQ_SLICE = 1024      # positions per slice of the head and the loss
QUERY_BLOCK = 1024    # queries per block of a head's attention
SCAN_BLOCK = 64       # steps of the recurrence rematerialised together
FAULTS = ("", "sqrt_scale", "norm_before_gate", "no_skip")


def knobs(cfg, fault: str = "") -> Dict[str, Any]:
    """The numbers a fault changes: the scores' scale, whether the gate comes
    before the norm, what ``D`` is multiplied by."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    head = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    scale = 1.0 / math.sqrt(head) if fault == "sqrt_scale" else float(cfg["attention_multiplier"])
    return {
        "scale": jnp.asarray(scale, jnp.float32),
        "gate_first": jnp.asarray(fault != "norm_before_gate"),
        "skip": jnp.asarray(0.0 if fault == "no_skip" else 1.0, jnp.float32),
    }


def ssm_recurrence(x, dt, A, B, C, D, prod, block: int = SCAN_BLOCK):
    """The diagonal recurrence a step at a time over ``x [T, H, P]``, ``dt [T,
    H]``, ``A, D [H]``, ``B, C [T, N]`` from a zero state: ``y [T, H, P]``."""
    T, H, P = x.shape
    N = B.shape[-1]
    pad = (-T) % block                       # a padded step (dt = 0) forgets nothing and writes nothing
    xs = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in (x, dt, B, C)]
    xs = [a.reshape(((T + pad) // block, block) + a.shape[1:]) for a in xs]

    def step(S, inp):
        x, dt, B, C = inp
        S = S * jnp.exp(dt * A)[:, None, None] + prod("hp,n->hpn", dt[:, None] * x, B)
        return S, prod("hpn,n->hp", S, C) + D[:, None] * x

    @jax.checkpoint
    def steps(S, inp):
        return jax.lax.scan(step, S, inp)

    _, y = jax.lax.scan(steps, jnp.zeros((H, P, N), jnp.float32), xs)
    return y.reshape(T + pad, H, P)[:T]


def mamba_mixer(u, p, cfg, prod, knob):
    T = u.shape[0]
    H, P, N = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_d_state"])
    d_in = H * P
    proj = prod("td,de->te", u, p["in_proj"]["kernel"])
    z, xBC, dt = proj[:, :d_in], proj[:, d_in:2 * d_in + 2 * N], proj[:, 2 * d_in + 2 * N:]
    xBC = silu(short_conv(xBC, p["conv_taps"]) + p["conv_bias"])
    x, B, C = xBC[:, :d_in], xBC[:, d_in:d_in + N], xBC[:, d_in + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(x.reshape(T, H, P), dt, -jnp.exp(p["A_log"]), B, C, knob["skip"] * p["D"], prod).reshape(T, d_in)
    eps, scale = float(cfg["rms_norm_eps"]), p["norm"]["scale"]
    y = jnp.where(knob["gate_first"], rms_norm(y * silu(z), scale, eps), rms_norm(y, scale, eps) * silu(z))
    return prod("te,ed->td", y, p["out_proj"]["kernel"])


def attention_mixer(u, p, cfg, prod, knob, query_block: int = QUERY_BLOCK):
    T = u.shape[0]
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    D = int(cfg["hidden_size"]) // H
    q = prod("td,de->te", u, p["q_proj"]["kernel"]).reshape(T, H, D)
    k = prod("td,de->te", u, p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = prod("td,de->te", u, p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    size = min(query_block, T)
    pad = (-T) % size
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, size, H, D)
    starts = jnp.arange(q.shape[0]) * size

    def head(_, i):
        kh, vh = k[:, i // (H // Hkv)], v[:, i // (H // Hkv)]     # the K/V head that query head i reads

        @jax.checkpoint
        def block(_, inp):
            qb, start = inp                                       # the head's queries start .. start + size
            s = prod("qd,kd->qk", qb, kh) * knob["scale"]
            seen = (start + jnp.arange(size))[:, None] >= jnp.arange(T)[None, :]
            a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return None, prod("qk,kd->qd", a, vh)

        _, o = jax.lax.scan(block, None, (q[:, :, i], starts))
        return None, o.reshape(-1, D)[:T]

    _, o = jax.lax.scan(head, None, jnp.arange(H))                # [H, T, D]
    return prod("te,ed->td", o.transpose(1, 0, 2).reshape(T, H * D), p["o_proj"]["kernel"])


def layer(h, p, kind: str, cfg, prod, knob):
    eps, r = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = h + r * mixer(rms_norm(h, p["input_layernorm"]["scale"], eps), p["mixer"], cfg, prod, knob)
    mlp = p["mlp"]
    u = rms_norm(h, p["post_attention_layernorm"]["scale"], eps)
    return h + r * gated_mlp(u, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], prod)


def hidden_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    knob = knobs(cfg) if knob is None else knob
    h = float(cfg["embedding_multiplier"]) * p["embed_tokens"]["embedding"][tokens]
    for i, kind in enumerate(layer_kinds(cfg)):
        one = jax.checkpoint(lambda h, lp, kind=kind: layer(h, lp, kind, cfg, prod, knob))
        h = one(h, p[f"layers_{i}"])
    return rms_norm(h, p["norm"]["scale"], float(cfg["rms_norm_eps"]))


def logits_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    hidden = hidden_fn(params, tokens, cfg, precision, knob)
    head = params["params"]["embed_tokens"]["embedding"]
    return _product(precision)("td,vd->tv", hidden, head) / float(cfg["logits_scaling"])


def nll_sum(params, tokens, cfg, precision: str = "float32", knob=None, seq_slice: int = SEQ_SLICE):
    """Summed next-token negative log-likelihood of one row ``tokens [T]``,
    the tied head and the loss over slices of the sequence."""
    prod = _product(precision)
    h = hidden_fn(params, tokens, cfg, precision, knob)[:-1]
    targets = tokens[1:]
    n = h.shape[0]
    size = min(seq_slice, n)
    pad = (-n) % size
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, size, h.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, size)
    live = (jnp.arange(n + pad) < n).reshape(-1, size)
    head, scaling = params["params"]["embed_tokens"]["embedding"], float(cfg["logits_scaling"])

    @jax.checkpoint
    def one(total, part):
        x, y, keep = part
        logp = jax.nn.log_softmax(prod("td,vd->tv", x, head) / scaling, axis=-1)
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
