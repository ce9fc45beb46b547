"""Seeded random weights for the ``train_lfm2_lm`` runner, made on the device
in one jitted call, in the layout ``adapcc_tpu.models.lfm2_moe.Lfm2Moe`` reads
(``params/layers_<i>/conv/in_proj/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): cells 3-7's recipe: every
matrix and the embedding normal(0, 0.02); the projections back into the
residual stream (both mixers' ``out_proj``, the dense ``down_proj``, the
experts' ``w2``) scaled by ``1/sqrt(2 * layers run)``; every ``hidden_size``-wide
norm's scale 1; the convolution's taps uniform(-1/sqrt(K), 1/sqrt(K))
(``chipbench/weights_hybrid_lm.draw``); the router's ``expert_bias`` 0.  No
head of its own: the embedding is the head.  **Not cells 3-7's**: the two
per-head norms' scales (``q_layernorm``, ``k_layernorm``) are normal(1.5,
0.5) a channel (:data:`HEAD_NORM`).  At exactly one a rotation before the norm
*is* the rotation after it (a rotation keeps a head's mean square) and the
comparison cannot tell a program that rotates first from one that norms
first; a scale that differs between the two channels of a rotated pair does
not commute with the rotation.  The spread is the mildest of three read on
the chip under which that order fails a limit at every seed (1 + normal(0,
0.1) moved no leaf's norm by more than the sound runs' own gap: at scales
near one the scores' spread is near one, a layer's attention is close to the
mean of ``v``, and q and k hardly reach the loss; PERF.md section 6, PR 45).

**Which layer is what** follows the published indices: ``layers_held`` names
them, ``layer_types[l]`` is layer ``l``'s mixer, and its feed-forward is the
dense MLP where ``l`` is under the *published* ``num_dense_layers`` (the
file's own ``num_dense_layers`` counts the dense layers run here and is
listed in ``reduced``).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)
from chipbench.weights_hybrid_lm import draw as _draw

#: mean and spread of the per-head q/k norm scales
HEAD_NORM = (1.5, 0.5)

_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "num_experts", "num_experts_held", "conv_L_cache",
)


def draw(key, shape, how):
    """One leaf from its key: ``weights_hybrid_lm.draw``'s kinds and ``head_norm``."""
    if how == "head_norm":
        mean, std = HEAD_NORM
        return mean + std * jax.random.normal(key, shape, jnp.float32)
    return _draw(key, shape, how)


def layer_plan(cfg: Dict[str, Any]) -> Tuple[Tuple[str, bool], ...]:
    """``(mixer, sparse)`` of each layer run: of the published indices ``layers_held``."""
    dense = int(cfg["published"]["num_dense_layers"])
    return tuple((cfg["layer_types"][int(l)], int(l) >= dense) for l in cfg["layers_held"])


def layer_kinds(cfg: Dict[str, Any]) -> Tuple[str, ...]:
    return tuple(kind for kind, _ in layer_plan(cfg))


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "d": d, "H": H, "Hkv": int(cfg["num_key_value_heads"]),
        "head": int(cfg.get("assumed", {}).get("head_dim") or d // H), "K": int(cfg["conv_L_cache"]),
        "wide": int(cfg["intermediate_size"]), "narrow": int(cfg["moe_intermediate_size"]),
        "E": int(cfg["num_experts"]), "held": int(cfg["num_experts_held"]), "k": int(cfg["num_experts_per_tok"]),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or
    one of ``ones``, ``head_norm``, ``zeros``, ``taps``."""
    s = sizes(cfg)
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["head"]
    plan = layer_plan(cfg)
    resid = 0.02 / math.sqrt(2 * len(plan))

    def norm(n=d, how="ones"):
        return {"scale": ((n,), how)}

    def dense(rows, cols, std=0.02):
        return {"kernel": ((rows, cols), std)}

    mixers = {
        "conv": ("conv", {
            "in_proj": dense(d, 3 * d), "conv_taps": ((s["K"], d), "taps"), "out_proj": dense(d, d, resid),
        }),
        "full_attention": ("self_attn", {
            "q_proj": dense(d, H * D), "k_proj": dense(d, Hkv * D), "v_proj": dense(d, Hkv * D),
            "out_proj": dense(H * D, d, resid), "q_layernorm": norm(D, "head_norm"), "k_layernorm": norm(D, "head_norm"),
        }),
    }
    feed = {
        False: {"gate_proj": dense(d, s["wide"]), "up_proj": dense(d, s["wide"]), "down_proj": dense(s["wide"], d, resid)},
        True: {
            "router": ((d, s["E"]), 0.02), "expert_bias": ((s["E"],), "zeros"),
            "experts_w1": ((s["held"], d, s["narrow"]), 0.02), "experts_w3": ((s["held"], d, s["narrow"]), 0.02),
            "experts_w2": ((s["held"], s["narrow"], d), resid),
        },
    }
    tree = {"embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)}, "embedding_norm": norm()}
    for i, (kind, sparse) in enumerate(plan):
        name, mixer = mixers[kind]
        tree[f"layers_{i}"] = {"operator_norm": norm(), "ffn_norm": norm(), name: mixer, "feed_forward": feed[sparse]}
    return {"params": tree}


def _frozen(cfg: Dict[str, Any]) -> str:
    """The keys the table reads, as a hashable static argument."""
    return json.dumps({
        **{k: int(cfg[k]) for k in _KEYS}, "num_experts_per_tok": int(cfg["num_experts_per_tok"]),
        "layer_types": list(cfg["layer_types"]), "layers_held": [int(l) for l in cfg["layers_held"]],
        "published": {"num_dense_layers": int(cfg["published"]["num_dense_layers"])},
        "assumed": {"head_dim": cfg.get("assumed", {}).get("head_dim")},
    }, sort_keys=True)


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


def leaf_names(cfg: Dict[str, Any]) -> list:
    """The leaves by name, in the order of both sides' norms."""
    table = leaf_table(cfg)
    return [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(table, is_leaf=_is_leaf)]


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
