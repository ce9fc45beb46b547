"""Seeded random weights for the ``train_hybrid_lm`` runner, made on the
device in one jitted call, in the layout
``adapcc_tpu.models.kimi_linear.KimiLinear`` reads
(``params/layers_<i>/self_attn/q_proj/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): every matrix and embedding
normal(0, 0.02); the projections back into the residual stream (``o_proj``,
every ``down_proj``, the experts' ``w2``) scaled by ``1/sqrt(2 * layers)`` as
``chipbench/weights.py`` scales GPT-2's; every norm's scale 1; the router's
``expert_bias`` 0; a short convolution's four taps uniform(-1/2, 1/2)
(``1/sqrt(taps)``, the framework default for a depthwise convolution);
``A_log = log(uniform(1, 16))``; ``dt_bias = softplus^-1(dt)`` with ``dt``
log-uniform in [0.001, 0.1], so that a step's decay ``exp(-exp(A_log) dt)``
starts between 0.2 and 0.999.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)


def layer_kinds(cfg: Dict[str, Any]) -> Tuple[str, ...]:
    """``"kda"`` / ``"mla"`` for the layers run here: the first
    ``num_hidden_layers`` of the published lists (numbered from 1)."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return tuple("mla" if i in full else "kda" for i in range(1, int(cfg["num_hidden_layers"]) + 1))


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or
    one of ``ones``, ``zeros``, ``taps``, ``a_log``, ``dt_bias``."""
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    group = cfg["linear_attn_config"]
    Hk, Dk, K = int(group["num_heads"]), int(group["head_dim"]), int(group["short_conv_kernel_size"])
    H, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, pe, dv = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    E, held = int(cfg["num_experts"]), int(cfg["num_experts_held"])
    wide, narrow = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    resid = 0.02 / math.sqrt(2 * L)

    def norm(n=d):
        return {"scale": ((n,), "ones")}

    def dense(rows, cols, std=0.02):
        return {"kernel": ((rows, cols), std)}

    def mlp(width):
        return {"gate_proj": dense(d, width), "up_proj": dense(d, width), "down_proj": dense(width, d, resid)}

    def kda():
        wide_k = Hk * Dk
        return {
            "q_proj": dense(d, wide_k), "k_proj": dense(d, wide_k), "v_proj": dense(d, wide_k),
            "q_conv": ((K, wide_k), "taps"), "k_conv": ((K, wide_k), "taps"), "v_conv": ((K, wide_k), "taps"),
            "A_log": ((Hk,), "a_log"), "dt_bias": ((wide_k,), "dt_bias"),
            "f_a_proj": dense(d, Dk), "f_b_proj": dense(Dk, wide_k), "b_proj": dense(d, Hk),
            "g_a_proj": dense(d, Dk), "g_b_proj": dense(Dk, wide_k),
            "o_norm": norm(Dk), "o_proj": dense(wide_k, d, resid),
        }

    def mla():
        return {
            "q_proj": dense(d, H * (nope + pe)), "kv_a_proj_with_mqa": dense(d, rank + pe),
            "kv_a_layernorm": norm(rank), "kv_b_proj": dense(rank, H * (nope + dv)),
            "o_proj": dense(H * dv, d, resid),
        }

    tree = {
        "embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)},
        "norm": norm(),
        "lm_head": ((int(cfg["vocab_size"]), d), 0.02),
    }
    for i, kind in enumerate(layer_kinds(cfg)):
        layer = {
            "input_layernorm": norm(), "post_attention_layernorm": norm(),
            "self_attn": kda() if kind == "kda" else mla(),
        }
        if i < int(cfg["first_k_dense_replace"]):
            layer["mlp"] = mlp(wide)
        else:
            layer["mlp"] = {
                "router": ((d, E), 0.02),
                "expert_bias": ((E,), "zeros"),
                "shared_experts": mlp(narrow),
                "experts_w1": ((held, d, narrow), 0.02),
                "experts_w3": ((held, d, narrow), 0.02),
                "experts_w2": ((held, narrow, d), resid),
            }
        tree[f"layers_{i}"] = layer
    return {"params": tree}


def draw(key, shape, how):
    """One leaf from its key."""
    if how in ("ones", "zeros"):
        return getattr(jnp, how)(shape, jnp.float32)
    if how == "taps":
        bound = shape[0] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(0.001), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1
    return how * jax.random.normal(key, shape, jnp.float32)


def _frozen(cfg: Dict[str, Any]) -> str:
    """The keys the table reads, as a hashable static argument."""
    import json

    keys = (
        "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts", "num_experts_held",
        "intermediate_size", "moe_intermediate_size", "linear_attn_config",
    )
    return json.dumps({k: cfg[k] for k in keys}, sort_keys=True)


def _table(frozen: str):
    import json

    return jax.tree_util.tree_flatten(leaf_table(json.loads(frozen)), is_leaf=_is_leaf)


def _build(key, frozen: str):
    leaves, treedef = _table(frozen)
    return jax.tree_util.tree_unflatten(
        treedef, [draw(jax.random.fold_in(key, i), shape, how) for i, (shape, how) in enumerate(leaves)]
    )


def make_params(seed: int, cfg: Dict[str, Any], sharding: Optional[Any] = None):
    """The whole tree in one jitted program (on every chip of ``sharding``)."""
    return jax.jit(_build, static_argnums=1, out_shardings=sharding)(seed_key(seed), _frozen(cfg))


def moved_norms(params, seed: int, cfg: Dict[str, Any]):
    """The Euclidean norm of every leaf's change from the weights the seed
    made, in ``tree_leaves`` order; a leaf at a time, so that the initial
    weights never exist whole beside a full chip."""

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def one(leaf, key, shape, how):
        return jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32) - draw(key, shape, how))))

    specs, _ = _table(_frozen(cfg))
    leaves = jax.tree_util.tree_leaves(params)
    if len(leaves) != len(specs):
        raise ValueError(f"{len(leaves)} leaves against {len(specs)} in the table")
    key = seed_key(seed)
    return jnp.stack([
        one(leaf, jax.random.fold_in(key, i), shape, how)
        for i, (leaf, (shape, how)) in enumerate(zip(leaves, specs))
    ])
