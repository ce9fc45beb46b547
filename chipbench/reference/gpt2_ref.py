"""GPT-2 as published, in plain ``jax.numpy`` float32: forward pass, loss,
gradients and the AdamW steps the benchmark's training cells compare against.

Written from the published description (Radford et al. 2019; the layer
equations of ``openai-community/gpt2``): learned token and position
embeddings, pre-LayerNorm blocks (attention, then a 4x MLP with the tanh
GELU), a final LayerNorm, and the output head tied to the token embedding.
It imports nothing of ``adapcc_tpu`` and takes nothing the program made: the
weights come from :mod:`chipbench.weights`, by the seed.

Departures, each noted where it is made: the LayerNorm epsilon is an argument
(the configuration file states the one the program runs), and rows go through
in blocks so that float32 logits of a whole batch never exist at once.

``precision`` is how the operands of every matrix product are rounded before
the float32 product: ``float32`` (none, products at ``highest``), ``bfloat16``
or ``float8`` (e4m3 with one scale per tensor, the usual fp8 recipe).  The
first is the reference; the others are the controls that ``correct`` has to
fail (chipbench/runners/train.py, tests/chipbench).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "float8")

#: rows per block of the reference's batch loop: float32 logits of one block
#: are rows x 1,024 x 50,257 x 4 B = 206 MB a row
ROW_BLOCK = 2


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)

        def fp8(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
            scale = jax.lax.stop_gradient(scale)
            return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

        return fp8
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def _product(precision: str):
    """``einsum`` whose operands, and whose cotangents in the backward
    products, are rounded to ``precision``; accumulation stays float32."""
    rnd = _rounder(precision)

    def es(spec: str, a, b):
        return jnp.einsum(spec, a, b, precision="highest")

    if precision == "float32":
        return es

    def prod(spec: str, a, b):
        ins, out = spec.split("->")
        sa, sb = ins.split(",")

        @jax.custom_vjp
        def f(a, b):
            return es(spec, rnd(a), rnd(b))

        def fwd(a, b):
            return f(a, b), (a, b)

        def bwd(res, g):
            a, b = res
            g = rnd(g)
            return (
                es(f"{out},{sb}->{sa}", g, rnd(b)),
                es(f"{sa},{out}->{sb}", rnd(a), g),
            )

        f.defvjp(fwd, bwd)
        return f(a, b)

    return prod


def layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def _block(x, p, n_head: int, eps: float, prod):
    B, T, d = x.shape
    hd = d // n_head
    h = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], eps)
    qkv = prod("btd,de->bte", h, p["attn"]["qkv"]["kernel"]) + p["attn"]["qkv"]["bias"]
    q, k, v = (t.reshape(B, T, n_head, hd) for t in jnp.split(qkv, 3, axis=-1))
    s = prod("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = prod("bhqk,bkhd->bqhd", a, v).reshape(B, T, d)
    x = x + prod("btd,de->bte", o, p["attn"]["proj"]["kernel"]) + p["attn"]["proj"]["bias"]
    h = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], eps)
    h = gelu_tanh(prod("btd,de->bte", h, p["fc"]["kernel"]) + p["fc"]["bias"])
    return x + prod("btd,de->bte", h, p["proj"]["kernel"]) + p["proj"]["bias"]


def logits_fn(params: Dict[str, Any], tokens, cfg: Dict[str, Any], precision: str = "float32"):
    """``tokens [B, T]`` -> float32 logits ``[B, T, vocab]``."""
    p = params["params"]
    prod = _product(precision)
    eps = float(cfg["layer_norm_epsilon"])
    T = tokens.shape[1]
    x = p["wte"]["embedding"][tokens] + p["wpe"]["embedding"][:T][None]
    layers = [p[f"h{i}"] for i in range(int(cfg["n_layer"]))]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)

    @jax.checkpoint
    def body(x, layer):
        return _block(x, layer, int(cfg["n_head"]), eps, prod), None

    x, _ = jax.lax.scan(body, x, stacked)
    x = layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], eps)
    return prod("btd,vd->btv", x, p["wte"]["embedding"])


def nll_sum(params, tokens, cfg, precision: str = "float32"):
    """Summed next-token negative log-likelihood over ``tokens [B, T]``."""
    logp = jax.nn.log_softmax(logits_fn(params, tokens, cfg, precision)[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def loss_and_grads(params, tokens, cfg, precision: str = "float32", row_block: int = ROW_BLOCK):
    """Mean next-token loss of the whole batch and its gradient, rows taken
    ``row_block`` at a time (a departure in order of summation only)."""
    B, T = tokens.shape
    if B % row_block:
        raise ValueError(f"{B} rows do not divide into blocks of {row_block}")
    blocks = tokens.reshape(B // row_block, row_block, T)
    count = B * (T - 1)

    def one(carry, rows):
        loss, grads = jax.value_and_grad(nll_sum)(params, rows, cfg, precision)
        return (
            carry[0] + loss,
            jax.tree_util.tree_map(jnp.add, carry[1], grads),
        ), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(one, zero, blocks)
    return loss / count, jax.tree_util.tree_map(lambda g: g / count, grads)


def leaf_norms(tree) -> jnp.ndarray:
    """The Euclidean norm of every leaf, in ``tree_leaves`` order."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree_util.tree_leaves(tree)
    ])


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
    factor = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def adamw_update(params, grads, mu, nu, step, opt: Dict[str, float]):
    """One AdamW update (Loshchilov & Hutter 2019, decoupled decay on every
    parameter) with bias correction; ``step`` counts from 1."""
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1**step, 1 - b2**step

    def new(p, m, v):
        return p - opt["learning_rate"] * (
            (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]) + opt["weight_decay"] * p
        )

    return jax.tree_util.tree_map(new, params, mu, nu), mu, nu


def train_steps(params, batches, cfg, opt: Dict[str, float], precision: str = "float32",
                row_block: int = ROW_BLOCK):
    """Follow the program's first steps: ``batches [steps, B, T]``, one
    clipped AdamW step on each.  Returns each step's loss (before its
    update), the per-leaf norm of the first gradient as the optimizer gets
    it (after clipping), and the per-leaf norm of the parameters' change
    after the last step."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def one(carry, batch):
        p, mu, nu, step, first = carry
        loss, grads = loss_and_grads(p, batch, cfg, precision, row_block)
        grads = clip_by_global_norm(grads, opt["clip_norm"])
        first = jnp.where(step == 1, leaf_norms(grads), first)
        p, mu, nu = adamw_update(p, grads, mu, nu, step.astype(jnp.float32), opt)
        return (p, mu, nu, step + 1, first), loss

    n_leaves = len(jax.tree_util.tree_leaves(params))
    carry = (params, zeros, zeros, jnp.ones((), jnp.int32), jnp.zeros((n_leaves,), jnp.float32))
    (last, _, _, _, first), losses = jax.lax.scan(one, carry, batches)
    moved = leaf_norms(jax.tree_util.tree_map(jnp.subtract, last, params))
    return {"losses": losses, "grad_norms": first, "update_norms": moved}
