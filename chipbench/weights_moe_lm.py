"""Seeded random weights for the ``train_moe_lm`` runner, made on the device
in one jitted call, in the layout ``adapcc_tpu.models.trinity.Trinity`` reads
(``params/layers_<i>/self_attn/q_proj/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): every matrix and embedding
normal(0, 0.02); the projections back into the residual stream (``o_proj``,
every ``down_proj``, the experts' ``w2``) scaled by ``1/sqrt(2 * layers)`` as
``chipbench/weights.py`` scales GPT-2's; every norm's scale 1; the router's
``expert_bias`` 0.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax

from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)


def layer_kinds(cfg: Dict[str, Any]) -> Tuple[str, ...]:
    """The layers run here, by kind: ``layer_types_here`` where the cut names
    them, else the first ``num_hidden_layers`` of the published list."""
    kinds = cfg.get("layer_types_here") or cfg["layer_types"][: int(cfg["num_hidden_layers"])]
    return tuple(kinds)


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    H, Hkv, D = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    E, held = int(cfg["num_experts"]), int(cfg["num_experts_held"])
    wide, narrow = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    resid = 0.02 / math.sqrt(2 * L)

    def norm(n=d):
        return {"scale": ((n,), "ones")}

    def mlp(width):
        return {
            "gate_proj": {"kernel": ((d, width), 0.02)},
            "up_proj": {"kernel": ((d, width), 0.02)},
            "down_proj": {"kernel": ((width, d), resid)},
        }

    tree = {
        "embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)},
        "norm": norm(),
        "lm_head": ((int(cfg["vocab_size"]), d), 0.02),
    }
    for i in range(L):
        layer = {
            "input_layernorm": norm(), "post_attention_layernorm": norm(),
            "pre_mlp_layernorm": norm(), "post_mlp_layernorm": norm(),
            "self_attn": {
                "q_proj": {"kernel": ((d, H * D), 0.02)},
                "k_proj": {"kernel": ((d, Hkv * D), 0.02)},
                "v_proj": {"kernel": ((d, Hkv * D), 0.02)},
                "gate_proj": {"kernel": ((d, H * D), 0.02)},
                "o_proj": {"kernel": ((H * D, d), resid)},
                "q_norm": norm(D), "k_norm": norm(D),
            },
        }
        if i < int(cfg["num_dense_layers"]):
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


_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts", "num_experts_held", "intermediate_size",
    "moe_intermediate_size",
)


def _build(key, cfg_items: Tuple[Tuple[str, Any], ...]):
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(leaf_table(dict(cfg_items)), is_leaf=_is_leaf)
    out = []
    for i, (shape, std) in enumerate(leaves):
        if std in ("ones", "zeros"):
            out.append(getattr(jnp, std)(shape, jnp.float32))
        else:
            out.append(std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _widths(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple((k, int(cfg[k])) for k in _KEYS)


def make_params(seed: int, cfg: Dict[str, Any], sharding: Optional[Any] = None):
    """The whole tree in one jitted program (on every chip of ``sharding``)."""
    return jax.jit(_build, static_argnums=1, out_shardings=sharding)(seed_key(seed), _widths(cfg))


def moved_norms(params, seed: int, cfg: Dict[str, Any]):
    """The Euclidean norm of every leaf's change from the weights the seed
    made, in ``tree_leaves`` order; a leaf at a time, so that the initial
    weights never exist whole beside a full chip."""
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def one(leaf, key, shape, std):
        if std in ("ones", "zeros"):
            start = getattr(jnp, std)(shape, jnp.float32)
        else:
            start = std * jax.random.normal(key, shape, jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32) - start)))

    specs = jax.tree_util.tree_leaves(leaf_table(dict(_widths(cfg))), is_leaf=_is_leaf)
    leaves = jax.tree_util.tree_leaves(params)
    if len(leaves) != len(specs):
        raise ValueError(f"{len(leaves)} leaves against {len(specs)} in the table")
    key = seed_key(seed)
    return jnp.stack([
        one(leaf, jax.random.fold_in(key, i), shape, std)
        for i, (leaf, (shape, std)) in enumerate(zip(leaves, specs))
    ])
