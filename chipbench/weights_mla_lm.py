"""Seeded random weights for the ``train_mla_lm`` runner, made on the device
in one jitted call, in the layout
``adapcc_tpu.models.joyai_flash.JoyAIFlash`` reads
(``params/layers_<i>/self_attn/q_a_proj/kernel`` ...,
``params/mtp/block/...``), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): every matrix and embedding
normal(0, 0.02), the MTP module's ``eh_proj`` among them; the projections back
into a residual stream (``o_proj``, every ``down_proj``, the experts' ``w2``,
the module's block's too) scaled by ``1/sqrt(2 * num_hidden_layers)`` as
``chipbench/weights.py`` scales GPT-2's; every norm's scale 1; the router's
``expert_bias`` 0.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)
from chipbench.weights_hybrid_lm import draw

_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_held",
    "intermediate_size", "moe_intermediate_size",
)


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or
    one of ``ones``, ``zeros``."""
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    H, q_rank, rank = int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, pe, dv = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    E, held = int(cfg["n_routed_experts"]), int(cfg["num_experts_held"])
    wide, narrow = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    resid = 0.02 / math.sqrt(2 * L)

    def norm(n=d):
        return {"scale": ((n,), "ones")}

    def dense(rows, cols, std=0.02):
        return {"kernel": ((rows, cols), std)}

    def mlp(width):
        return {"gate_proj": dense(d, width), "up_proj": dense(d, width), "down_proj": dense(width, d, resid)}

    def block(sparse: bool):
        experts = {
            "router": ((d, E), 0.02),
            "expert_bias": ((E,), "zeros"),
            "shared_experts": mlp(narrow),
            "experts_w1": ((held, d, narrow), 0.02),
            "experts_w3": ((held, d, narrow), 0.02),
            "experts_w2": ((held, narrow, d), resid),
        }
        return {
            "input_layernorm": norm(), "post_attention_layernorm": norm(),
            "self_attn": {
                "q_a_proj": dense(d, q_rank), "q_a_layernorm": norm(q_rank), "q_b_proj": dense(q_rank, H * (nope + pe)),
                "kv_a_proj_with_mqa": dense(d, rank + pe), "kv_a_layernorm": norm(rank),
                "kv_b_proj": dense(rank, H * (nope + dv)), "o_proj": dense(H * dv, d, resid),
            },
            "mlp": experts if sparse else mlp(wide),
        }

    tree = {
        "embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)},
        "norm": norm(),
        "mtp": {
            "enorm": norm(), "hnorm": norm(), "eh_proj": dense(2 * d, d), "block": block(True),
            "shared_head_norm": norm(),
        },
        "lm_head": ((int(cfg["vocab_size"]), d), 0.02),
    }
    for i in range(L):
        tree[f"layers_{i}"] = block(i >= int(cfg["first_k_dense_replace"]))
    return {"params": tree}


def _frozen(cfg: Dict[str, Any]) -> str:
    """The keys the table reads, as a hashable static argument."""
    return json.dumps({k: int(cfg[k]) for k in _KEYS}, sort_keys=True)


def _table(frozen: str):
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
