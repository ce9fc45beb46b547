"""Phi-4-mini-flash-reasoning's SambaY decoder as published, in plain
``jax.numpy`` float32: forward pass, loss, gradients and the AdamW steps the
``train_sambay_lm`` cells compare against.

Written from the published ``config.json`` (``model_type`` ``phi4flash``) and
the layer equations of ISSUE 41 / docs/PHI4_FLASH.md; it imports nothing of
``adapcc_tpu`` and takes nothing the program made (the weights come from
:mod:`chipbench.weights_sambay_lm`, by the seed, **with the attention
projections' columns in the published order**: heads pair up as ``2p, 2p +
1`` here, where the program keeps each group's columns together).  What it
shares with the other references is reference code too: the rounded product,
the LayerNorm, the gated MLP, the short convolution, the clipped AdamW.

- ``h = E[ids]``; no scaling, no positions anywhere.
- A layer: ``h += mixer(LN(h))``; ``h += mlp(LN(h))``, LayerNorm with bias,
  ``mlp(u) = (silu(u W1) * u W3) W2``.  Which mixer follows from the published
  index (``weights_sambay_lm.kind_of``).
- ``M`` (Mamba-1): ``[x, z] = u W_in``; ``x = silu(conv4(x) + b)``; ``[r, B,
  C] = x W_x``; ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; the
  state **a step at a time**: ``H = exp(dt_t A) * H + (dt_t x_t) (x) B_t``,
  ``y_t = H C_t + D x_t`` (:func:`selective_recurrence`: every channel and
  state its own decay, the algorithm under test shares nothing with it);
  ``out = (y * silu(z)) W_out``, no norm.  ``M*`` also hands on ``m = y``.
- ``G``: ``(m * silu(u W_in)) W_out``.
- ``S``, ``F`` (differential attention): ``[q, k, v] = u W_qkv + b``; pair
  ``p`` is query heads ``2p, 2p + 1`` on K/V heads ``2j, 2j + 1``, ``j = p //
  2``, ``V = [v_2j, v_2j+1]``; two dense masked softmaxes a pair (a block of
  queries at a time, the mask written out: causal, in ``S`` the last
  ``sliding_window`` keys); ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_0(l)``; ``(1 - lambda_0) rmsnorm_128(a1 - lambda a2)``; ``a W_o +
  b_o``.  ``X``: the query only, layer ``F``'s ``k, v``.
- ``logits = LN(h) E^T`` through the embedding itself; mean next-token
  cross-entropy over the vocabulary held.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: layers under ``jax.checkpoint``, the recurrence
rematerialised in blocks of 64 steps, attention a pair and a block of queries
at a time, the head and loss over slices of the sequence, the AdamW steps as
donating calls.  Loops are ``lax.scan``s: the compiled entry stays small.

``precision`` rounds every product's operands (``gpt2_ref._product``), the
recurrence's two products a step among them: ``float32`` is the reference,
``bfloat16`` and ``float8`` the controls.  ``fault`` makes further controls
``correct`` has to fail, each the reference with one piece of the mathematics
changed, in the program's place: ``"no_lambda"`` (``lambda = 0``: ``a2`` left
out), ``"norm_before_diff"`` (``rmsnorm(a1) - lambda rmsnorm(a2)``),
``"memory_after_gate"`` (``G`` fed ``y silu(z)``), ``"no_skip"`` (``D`` left
out), ``"window_off"`` (``S`` sees the whole triangle) and ``"kv_own"`` (``X``
reading a copy of ``F``'s keys and values: the same forward, but ``F``'s
projection takes no gradient from any ``X``).  A fault is a few numbers the
compiled step is *given*, so the reference and the faults are one compiled
program, kept from one call to the next.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, layer_norm, leaf_norms
from chipbench.reference.kimi_linear_ref import short_conv
from chipbench.reference.trinity_ref import gated_mlp, silu
from chipbench.weights_sambay_lm import key_bias_apart, layer_kinds, sizes

SEQ_SLICE = 1024      # positions per slice of the head and the loss
QUERY_BLOCK = 1024    # queries per block of a pair's attention
SCAN_BLOCK = 64       # steps of the recurrence rematerialised together
NORM_EPS = 1e-5       # of the pair's RMS norm
FAULTS = ("", "no_lambda", "norm_before_diff", "memory_after_gate", "no_skip", "window_off", "kv_own")


def knobs(cfg, fault: str = "") -> Dict[str, Any]:
    """The numbers a fault changes."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    one = lambda on: jnp.asarray(1.0 if on else 0.0, jnp.float32)  # noqa: E731
    return {
        "lam": one(fault != "no_lambda"),                  # what lambda is multiplied by
        "diff_first": jnp.asarray(fault != "norm_before_diff"),
        "gated_memory": one(fault == "memory_after_gate"),
        "skip": one(fault != "no_skip"),
        "window": jnp.asarray(2**30 if fault == "window_off" else int(cfg["sliding_window"]), jnp.int32),
        "shared": one(fault != "kv_own"),                  # the share of X's gradient that reaches F's k, v
    }


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def selective_recurrence(x, dt, A, B, C, D, prod, block: int = SCAN_BLOCK):
    """The recurrence a step at a time over ``x, dt [T, C]``, ``A [C, N]``,
    ``B, C [T, N]``, ``D [C]`` from a zero state: ``y [T, C]``."""
    T, Ch = x.shape
    pad = (-T) % block                       # a padded step (dt = 0) forgets nothing and writes nothing
    xs = [jnp.pad(a, ((0, pad), (0, 0))).reshape((T + pad) // block, block, a.shape[-1]) for a in (x, dt, B, C)]

    def step(S, inp):
        x, dt, B, C = inp
        S = S * jnp.exp(dt[:, None] * A) + prod("c,n->cn", dt * x, B)
        return S, prod("cn,n->c", S, C) + D * x

    @jax.checkpoint
    def steps(S, inp):
        return jax.lax.scan(step, S, inp)

    _, y = jax.lax.scan(steps, jnp.zeros(A.shape, jnp.float32), xs)
    return y.reshape(T + pad, Ch)[:T]


def mamba_mixer(u, p, cfg, prod, knob):
    """``(out, m)``: ``m`` is what an ``M*`` layer hands on."""
    s = sizes(cfg)
    d_in, N, R = s["d_in"], s["N"], s["R"]
    xz = prod("td,de->te", u, p["in_proj"]["kernel"])
    x, z = xz[:, :d_in], xz[:, d_in:]
    x = silu(short_conv(x, p["conv_taps"]) + p["conv_bias"])
    rBC = prod("te,ef->tf", x, p["x_proj"]["kernel"])
    r, B, C = rBC[:, :R], rBC[:, R:R + N], rBC[:, R + N:]
    dt = jax.nn.softplus(prod("tr,re->te", r, p["dt_proj"]) + p["dt_bias"])
    y = selective_recurrence(x, dt, -jnp.exp(p["A_log"]), B, C, knob["skip"] * p["D"], prod)
    gated = y * silu(z)
    memory = knob["gated_memory"] * gated + (1 - knob["gated_memory"]) * y
    return prod("te,ed->td", gated, p["out_proj"]["kernel"]), memory


def memory_unit(u, m, p, prod):
    return prod("te,ed->td", m * silu(prod("td,de->te", u, p["in_proj"]["kernel"])), p["out_proj"]["kernel"])


def _softmax_rows(q, k, v, start, window, prod, scale):
    """Queries ``q [n, D]`` at positions ``start ..`` against all of ``k [T,
    D]``, ``v [T, Dv]``: causal, the last ``window`` keys."""
    T, n = k.shape[0], q.shape[0]
    s = prod("qd,kd->qk", q, k) * scale
    ahead = (start + jnp.arange(n))[:, None] - jnp.arange(T)[None, :]
    seen = (ahead >= 0) & (ahead < window)
    return prod("qk,kd->qd", jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)


def rms(a):
    return a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + NORM_EPS)


def diff_attention(u, p, kind: str, layer: int, cfg, prod, knob, kv=None, query_block: int = QUERY_BLOCK):
    """``(out, (k, v))``; ``kind`` ``"S"``, ``"F"`` or ``"X"`` (``kv`` given)."""
    T = u.shape[0]
    s = sizes(cfg)
    H, Hkv, D = s["H"], s["Hkv"], s["head"]
    P, J = H // 2, Hkv // 2
    if kind == "X":
        q = prod("td,de->te", u, p["q_proj"]["kernel"]) + p["q_proj"]["bias"]
        k, v = kv
        k, v = [knob["shared"] * a + (1 - knob["shared"]) * jax.lax.stop_gradient(a) for a in (k, v)]
    else:
        qkv = prod("td,de->te", u, p["qkv_proj"]["kernel"]) + p["qkv_proj"]["bias"]
        q, k, v = qkv[:, :H * D], qkv[:, H * D:(H + Hkv) * D], qkv[:, (H + Hkv) * D:]
    q = q.reshape(T, P, 2, D).transpose(1, 2, 0, 3)              # [P, 2, T, D]: pair p is heads 2p, 2p + 1
    k2 = k.reshape(T, J, 2, D).transpose(1, 2, 0, 3)             # [J, 2, T, D]
    V = v.reshape(T, J, 2 * D).transpose(1, 0, 2)                # [J, T, 2 D]: v_2j beside v_2j+1
    window = knob["window"] if kind == "S" else jnp.asarray(2**30, jnp.int32)
    lam0 = lambda_init(layer)
    lam = knob["lam"] * (
        jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0
    )
    size = min(query_block, T)
    pad = (-T) % size
    starts = jnp.arange((T + pad) // size) * size
    scale = 1.0 / math.sqrt(D)

    def pair(_, i):
        kp, Vp = k2[i // (P // J)], V[i // (P // J)]
        qs = jnp.pad(q[i], ((0, 0), (0, pad), (0, 0))).reshape(2, -1, size, D)

        @jax.checkpoint
        def block(_, inp):
            q1, q2, start = inp
            a1 = _softmax_rows(q1, kp[0], Vp, start, window, prod, scale)
            a2 = _softmax_rows(q2, kp[1], Vp, start, window, prod, scale)
            a = jnp.where(knob["diff_first"], rms(a1 - lam * a2), rms(a1) - lam * rms(a2))
            return None, a * p["subln"] * (1.0 - lam0)

        _, o = jax.lax.scan(block, None, (qs[0], qs[1], starts))
        return None, o.reshape(-1, 2 * D)[:T]

    _, o = jax.lax.scan(pair, None, jnp.arange(P))               # [P, T, 2 D]
    o = o.transpose(1, 0, 2).reshape(T, H * D)                   # the pair's channels back to heads 2p, 2p + 1
    return prod("te,ed->td", o, p["out_proj"]["kernel"]) + p["out_proj"]["bias"], (k, v)


def layer(h, carried, p, kind: str, index: int, cfg, prod, knob):
    eps = float(cfg["layer_norm_eps"])
    norm = lambda x, n: layer_norm(x, n["scale"], n["bias"], eps)  # noqa: E731
    u = norm(h, p["input_layernorm"])
    if kind in ("M", "M*"):
        out, m = mamba_mixer(u, p["mixer"], cfg, prod, knob)
        if kind == "M*":
            carried = {**carried, "m": m}
    elif kind == "G":
        out = memory_unit(u, carried["m"], p["mixer"], prod)
    else:
        out, kv = diff_attention(u, p["mixer"], kind, index, cfg, prod, knob, carried.get("kv"))
        if kind == "F":
            carried = {**carried, "kv": kv}
    h = h + out
    mlp = p["mlp"]
    u = norm(h, p["post_attention_layernorm"])
    out = gated_mlp(u, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], prod)
    return h + out, carried


def hidden_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> the final norm's output ``[T, hidden]``."""
    p = params["params"]
    prod = _product(precision)
    knob = knobs(cfg) if knob is None else knob
    h, carried = p["embed_tokens"]["embedding"][tokens], {}
    for i, (kind, index) in enumerate(zip(layer_kinds(cfg), cfg["layers_held"])):
        one = jax.checkpoint(
            lambda h, carried, lp, kind=kind, index=int(index): layer(h, carried, lp, kind, index, cfg, prod, knob)
        )
        h, carried = one(h, carried, p[f"layers_{i}"])
    return layer_norm(h, p["norm"]["scale"], p["norm"]["bias"], float(cfg["layer_norm_eps"]))


def logits_fn(params, tokens, cfg, precision: str = "float32", knob=None):
    """``tokens [T]`` -> float32 logits ``[T, vocab]`` (small sizes only)."""
    hidden = hidden_fn(params, tokens, cfg, precision, knob)
    return _product(precision)("td,vd->tv", hidden, params["params"]["embed_tokens"]["embedding"])


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
    head = params["params"]["embed_tokens"]["embedding"]

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
        norms = leaf_norms(key_bias_apart(grads, cfg))
        p, mu, nu = adamw_update(p, grads, mu, nu, count, opt)
        return p, mu, nu, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def _stated(cfg) -> Dict[str, Any]:
    """The keys of the configuration file the mathematics reads."""
    keys = (
        "vocab_size", "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
        "sliding_window", "layer_norm_eps", "layers_held",
    )
    return {
        **{k: cfg[k] for k in keys}, "published": {"num_hidden_layers": cfg["published"]["num_hidden_layers"]},
        "assumed": {"mamba": cfg.get("assumed", {}).get("mamba", {})},
    }


def train_steps(params, batches, cfg, opt: Dict[str, float], init, precision: str = "float32", fault: str = ""):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each, each a donating call so that parameters and
    both moments exist once.  ``params`` and what ``init()`` makes anew are in
    the published column order.  Returns what ``gpt2_ref.train_steps``
    returns, the norms leaf by leaf with each key's bias a leaf of its own
    (``weights_sambay_lm.key_bias_apart``)."""
    step = _compiled_step(json.dumps([_stated(cfg), opt], sort_keys=True), precision)
    knob = knobs(cfg, fault)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches, start=1):
        params, mu, nu, loss, norms = step(params, mu, nu, jnp.asarray(float(i)), jnp.asarray(batch), knob)
        losses.append(loss)
        first = norms if first is None else first
    del mu, nu
    moved = jax.jit(lambda p, p0: leaf_norms(key_bias_apart(jax.tree_util.tree_map(jnp.subtract, p, p0), cfg)))
    return {"losses": jnp.stack(losses), "grad_norms": first, "update_norms": moved(params, init())}
