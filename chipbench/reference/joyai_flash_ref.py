"""JoyAI-LLM-Flash's block as published, in plain ``jax.numpy`` float32:
forward pass, both loss terms, gradients and the AdamW steps the
``train_mla_lm`` cells compare against.

Written from the published ``config.json`` (``model_type``
``joyai_llm_flash``) and the layer equations of ISSUE 34 /
docs/JOYAI_FLASH.md; it imports nothing of ``adapcc_tpu`` and takes nothing
the program made (the weights come from :mod:`chipbench.weights_mla_lm`, by
the seed).  What it shares with :mod:`chipbench.reference.trinity_ref` is
reference code too: the norm, the gated MLP and the expert layer by a loop
over the held experts with a 0/1 mask (``noaux_tc`` in one group is Trinity's
router to the letter).  RMSNorm(x) = x · rsqrt(mean(x²) + eps) · g.

- A layer, two norms: ``h += mla(norm(h))``; ``h += ffn(norm(h))``.
- Latent attention (32 heads): ``c_q = rmsnorm(x Wq↓)`` (1,536), ``q = c_q
  Wq↑`` of ``[128 | 64]`` a head; ``[c, k_pe] = x Wkv↓`` (512 + 64);
  ``[k_nope, v] = rmsnorm(c) Wkv↑``.  **Rotation** of each head's 64 ``q_pe``
  channels and of the 64 ``k_pe`` channels the heads share: the plain pair
  ``(2i, 2i+1)`` of position ``m`` as the complex number ``x_2i + i x_2i+1``
  times ``exp(i m theta^(-2i/64))``, the angle formed in float64 and its
  cosine and sine rounded to float32 (:func:`rotate`).  The products' real
  parts are laid before their imaginary parts: a permutation applied alike to
  ``q_pe`` and ``k_pe``, which changes no score.  ``k = [k_nope, k_pe]``;
  causal softmax of ``q kᵀ / sqrt(192)`` a head at a time and 1,024 queries
  at a time against every key, the mask written out; ``(P v) Wo``.
- FFN: layer 1 ``(silu(x W1) ∘ x W3) W2`` at 7,168; after it the shared
  expert plus the held routed experts' part (``trinity_ref.sparse_ffn``).
- Trunk: final RMSNorm ``hbar``, untied head, ``L_main`` the mean over places
  ``0 .. T-2`` of the cross-entropy against the next token.
- MTP module on places ``0 .. T-2``, **sliced, not shifted**: ``z_i =
  [rmsnorm_e(Emb(t_{i+1})) | rmsnorm_h(hbar_i)] W_eh``; one more latent +
  expert layer over ``z``; ``rmsnorm_s``, the trunk's own head; ``L_mtp`` the
  mean over places ``0 .. T-3`` against the token two on.
  ``L = L_main + assumed.mtp_loss_weight · L_mtp``.

Departures in order of summation only, so that it fits on one chip after the
program's state is freed: each half of a layer under ``jax.checkpoint``, attention one head
and one block of queries at a time, the heads and losses over slices of the
sequence, the AdamW steps as donating calls.

``precision`` rounds every product's operands (``gpt2_ref._product``):
``float32`` is the reference, ``bfloat16`` and ``float8`` the controls.
``fault`` makes the two further controls ``correct`` has to fail, each the
reference with one part of the mathematics left out, in the program's place:
``"no_rotation"`` (``q_pe`` and ``k_pe`` as they come) and ``"no_mtp_term"``
(the module's term out of the loss).  A fault is two numbers the compiled
step is *given* (does the rotation turn, what the module's term weighs), so
the reference and both faults are one compiled program, and
:func:`train_steps` keeps that program from one call to the next: a tool that
reads many seeds in one process compiles it once.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt2_ref import _product, adamw_update, clip_by_global_norm, leaf_norms
from chipbench.reference.trinity_ref import gated_mlp, rms_norm, sparse_ffn

SEQ_SLICE = 1024      # positions per slice of a head and its loss
QUERY_BLOCK = 1024    # queries per block of a head's attention: float32 scores of a block are 1,024 x 8,192 x 4 B = 34 MB
FAULTS = ("", "no_rotation", "no_mtp_term")


def rotate(x, theta: float, turn=True):
    """``x [T, H, D]``: the plain pairs ``(2i, 2i+1)`` multiplied as complex
    numbers by ``exp(i m theta^(-2i/D))``; returns ``[T, H, D]`` with the
    ``D/2`` real parts before the ``D/2`` imaginary parts.  ``turn`` false
    (the control) multiplies by 1."""
    T, D = x.shape[0], x.shape[-1]
    angle = np.arange(T, dtype=np.float64)[:, None] * theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    c = jnp.where(turn, jnp.asarray(np.cos(angle), jnp.float32), 1.0)[:, None, :]
    s = jnp.where(turn, jnp.asarray(np.sin(angle), jnp.float32), 0.0)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * c - b * s, a * s + b * c], axis=-1)       # (a + ib)(c + is)


def mla_mixer(x, p, cfg, prod, turn=True):
    T = x.shape[0]
    H, eps = int(cfg["num_attention_heads"]), float(cfg["rms_norm_eps"])
    rank, nope, pe, dv = (int(cfg[k]) for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    turned = lambda y: rotate(y, float(cfg["rope_theta"]), turn)  # noqa: E731
    q_latent = rms_norm(prod("td,dr->tr", x, p["q_a_proj"]["kernel"]), p["q_a_layernorm"]["scale"], eps)
    q = prod("tr,re->te", q_latent, p["q_b_proj"]["kernel"]).reshape(T, H, nope + pe)
    q_nope, q_pe = q[..., :nope], turned(q[..., nope:])
    down = prod("td,de->te", x, p["kv_a_proj_with_mqa"]["kernel"])
    latent, k_pe = down[:, :rank], turned(down[:, None, rank:])[:, 0]     # once, shared by the heads
    up = prod(
        "tr,re->te", rms_norm(latent, p["kv_a_layernorm"]["scale"], eps), p["kv_b_proj"]["kernel"]
    ).reshape(T, H, nope + dv)
    k_nope, v = up[..., :nope], up[..., nope:]
    rows = min(QUERY_BLOCK, T)
    pad = (-T) % rows                        # a padded query sees every key and is dropped
    places = jnp.arange(T + pad).reshape(-1, rows)

    @jax.checkpoint
    def head(_, i):
        qi = jnp.pad(jnp.concatenate([q_nope[:, i], q_pe[:, i]], axis=-1), ((0, pad), (0, 0)))
        ki = jnp.concatenate([k_nope[:, i], k_pe], axis=-1)

        @jax.checkpoint
        def block(_, part):                  # a block of queries against every key, the mask written out
            qb, at = part
            s = prod("qd,kd->qk", qb, ki) / math.sqrt(nope + pe)
            a = jax.nn.softmax(jnp.where(at[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf), axis=-1)
            return None, prod("qk,kd->qd", a, v[:, i])

        _, o = jax.lax.scan(block, None, (qi.reshape(-1, rows, nope + pe), places))
        return None, o.reshape(T + pad, dv)[:T]

    _, o = jax.lax.scan(head, None, jnp.arange(H))                  # [H, T, dv]
    return prod("te,ed->td", o.transpose(1, 0, 2).reshape(T, H * dv), p["o_proj"]["kernel"])


def router_keys(cfg) -> Dict[str, Any]:
    """The router's settings under the key names ``trinity_ref.sparse_ffn`` reads."""
    return {
        "num_experts": cfg["n_routed_experts"], "num_experts_per_tok": cfg["num_experts_per_tok"],
        "route_norm": cfg["norm_topk_prob"], "route_scale": cfg["routed_scaling_factor"],
        "expert_offset": cfg.get("expert_offset", 0),
    }


def layer(h, p, sparse: bool, cfg, prod, turn=True):
    """One block; its two halves are rematerialised apart, so that the
    backward pass holds the inside of one of them at a time."""
    eps = float(cfg["rms_norm_eps"])

    @jax.checkpoint
    def mixer(h, p):
        return mla_mixer(rms_norm(h, p["input_layernorm"]["scale"], eps), p["self_attn"], cfg, prod, turn)

    @jax.checkpoint
    def ffn(h, p):
        x = rms_norm(h, p["post_attention_layernorm"]["scale"], eps)
        if sparse:
            return sparse_ffn(x, p["mlp"], router_keys(cfg), prod)
        mlp = p["mlp"]
        return gated_mlp(x, mlp["gate_proj"]["kernel"], mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"], prod)

    h = h + mixer(h, p)
    return h + ffn(h, p)


def hidden_fn(params, tokens, cfg, precision: str = "float32", turn=True):
    """``tokens [T]`` -> ``(hbar [T, hidden], u [T-1, hidden])``: the trunk's
    final norm's output, and the MTP module's norm's output in front of the
    head, place ``i`` made from ``hbar_i`` and token ``i + 1``."""
    p, eps = params["params"], float(cfg["rms_norm_eps"])
    prod = _product(precision)

    h = p["embed_tokens"]["embedding"][tokens]
    for i in range(int(cfg["num_hidden_layers"])):
        h = layer(h, p[f"layers_{i}"], i >= int(cfg["first_k_dense_replace"]), cfg, prod, turn)
    hbar = rms_norm(h, p["norm"]["scale"], eps)
    m = p["mtp"]
    merged = jnp.concatenate([
        rms_norm(p["embed_tokens"]["embedding"][tokens[1:]], m["enorm"]["scale"], eps),    # the embedding's half first
        rms_norm(hbar[:-1], m["hnorm"]["scale"], eps),
    ], axis=-1)
    u = layer(prod("te,ed->td", merged, m["eh_proj"]["kernel"]), m["block"], True, cfg, prod, turn)
    return hbar, rms_norm(u, m["shared_head_norm"]["scale"], eps)


def logits_fn(params, tokens, cfg, precision: str = "float32"):
    """``tokens [T]`` -> float32 ``(logits [T, vocab], mtp_logits [T-1,
    vocab])`` (small sizes only)."""
    prod = _product(precision)
    hbar, u = hidden_fn(params, tokens, cfg, precision)
    head = params["params"]["lm_head"]
    return prod("td,vd->tv", hbar, head), prod("td,vd->tv", u, head)


def _nll_sum(h, targets, head, prod, seq_slice: int):
    """Summed negative log-likelihood of ``targets [n]`` under ``h [n, d]``
    read through ``head``, over slices of the sequence."""
    n = h.shape[0]
    size = min(seq_slice, n)
    pad = (-n) % size
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, size, h.shape[-1])
    targets = jnp.pad(targets, (0, pad)).reshape(-1, size)
    live = (jnp.arange(n + pad) < n).reshape(-1, size)

    @jax.checkpoint
    def one(total, part):
        x, y, keep = part
        logp = jax.nn.log_softmax(prod("td,vd->tv", x, head), axis=-1)
        picked = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return total - jnp.sum(jnp.where(keep, picked, 0.0)), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (h, targets, live))
    return total


def nll_sums(params, tokens, cfg, precision: str = "float32", turn=True, seq_slice: int = SEQ_SLICE):
    """``(trunk's, module's)`` summed negative log-likelihood of one row
    ``tokens [T]``: ``T - 1`` places against the next token, ``T - 2``
    against the token two on."""
    prod = _product(precision)
    hbar, u = hidden_fn(params, tokens, cfg, precision, turn)
    head = params["params"]["lm_head"]
    return _nll_sum(hbar[:-1], tokens[1:], head, prod, seq_slice), _nll_sum(u[:-1], tokens[2:], head, prod, seq_slice)


def loss_and_grads(params, batch, cfg, precision: str = "float32", turn=True, weight=None):
    """``(L, (L_main, L_mtp))`` of ``batch [B, T]`` and ``L``'s gradient, a row
    at a time; each term the mean over its own places.  ``weight`` is the
    module's term's (the file's ``assumed.mtp_loss_weight`` unless given)."""
    B, T = batch.shape
    weight = float(cfg["assumed"]["mtp_loss_weight"]) if weight is None else weight

    def row_loss(p, row):
        main, mtp = nll_sums(p, row, cfg, precision, turn)
        main, mtp = main / (B * (T - 1)), mtp / (B * (T - 2))
        return main + weight * mtp, (main, mtp)

    (loss, terms), grads = jax.value_and_grad(row_loss, has_aux=True)(params, batch[0])
    for row in batch[1:]:
        (more, more_terms), g = jax.value_and_grad(row_loss, has_aux=True)(params, row)
        loss, grads = loss + more, jax.tree_util.tree_map(jnp.add, grads, g)
        terms = (terms[0] + more_terms[0], terms[1] + more_terms[1])
    return (loss, terms), grads


@functools.lru_cache(maxsize=4)
def _compiled_step(stated: str, precision: str):
    """One clipped AdamW step as a donating call, for the configuration and
    optimizer ``stated`` (their JSON): kept, so that every seed and both
    faults of a process run the program compiled for the first."""
    cfg, opt = json.loads(stated)

    def step(p, mu, nu, count, batch, turn, weight):
        (loss, terms), grads = loss_and_grads(p, batch, cfg, precision, turn, weight)
        grads = clip_by_global_norm(grads, opt["clip_norm"])
        norms = leaf_norms(grads)
        p, mu, nu = adamw_update(p, grads, mu, nu, count, opt)
        return p, mu, nu, jnp.stack([loss, *terms]), norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_steps(params, batches, cfg, opt: Dict[str, float], init, precision: str = "float32", fault: str = ""):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each, each a donating call so that parameters and
    both moments exist once.  ``init()`` makes the initial parameters anew.
    Returns what ``gpt2_ref.train_steps`` returns, and ``losses_main`` and
    ``losses_mtp`` beside ``losses``."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    step = _compiled_step(json.dumps([cfg, opt], sort_keys=True), precision)
    turn = jnp.asarray(fault != "no_rotation")
    weight = jnp.asarray(0.0 if fault == "no_mtp_term" else float(cfg["assumed"]["mtp_loss_weight"]), jnp.float32)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches, start=1):
        params, mu, nu, loss, norms = step(params, mu, nu, jnp.asarray(float(i)), jnp.asarray(batch), turn, weight)
        losses.append(loss)
        first = norms if first is None else first
    del mu, nu
    moved = jax.jit(lambda p, p0: leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0)))
    losses = jnp.stack(losses)
    return {
        "losses": losses[:, 0], "losses_main": losses[:, 1], "losses_mtp": losses[:, 2],
        "grad_norms": first, "update_norms": moved(params, init()),
    }
