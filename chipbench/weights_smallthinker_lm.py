"""Seeded random weights for the ``train_smallthinker_lm`` runner, made on the
device in one jitted call, in the layout
``adapcc_tpu.models.smallthinker.SmallThinker`` reads
(``params/layers_<i>/router/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): cells 3-8's recipe: every
matrix, the router and the head normal(0, 0.02); the projections back into
the residual stream (``o_proj``, the experts' ``w2``) scaled by
``1/sqrt(2 * 52)``, the published depth; every norm's scale 1.

**The embedding is normal(0, 0.2), ten times the recipe's** (:data:`EMBEDDING_STD`).
This block's router reads the stream un-normed, so the stream's scale is the
routing's business as in no other cell.  A softmax's weights sum to 1, so the
mean value of a layer's keys passes every head unchanged, and ``o_proj``
writes it into the stream at 0.117 of a unit value: six times an embedding of
0.02.  The part of the stream that every token shares then grows sixfold a
layer (its mean over tokens reads 0.0005, 0.004, 0.025, 0.085 at the four
routers against a token's own 0.02), by the third layer every token chooses
the same six experts (fullest / mean 8-10 over the 64), and whether those six
lie among the sixteen held is the seed's draw: 11,149-12,972 assignments a
layer-step over twelve seeds, 1.3% of the step's time, for which the driver
refused the cell as too noisy (PERF.md section 6).  No trained router splits
its tokens so.  At 0.2 the embedding outweighs what a layer writes, the shared
part's gain is 0.6 a layer and it stays under a twentieth of the token's own:
every layer routes by the token (fullest / mean 1.1-1.2), every seed gives the
held experts their fair quarter, the same amount of work, and 150 steps of
training do not undo it (a shared part that small gives the router nothing to
learn it by).  ``config.json`` states no ``initializer_range``.

**Which layer is what** follows the published indices: ``layers_held`` names
them, ``rope_layout[l]`` says whether layer ``l`` rotates its q and k,
``sliding_window_layout[l]`` whether it masks to the window.  Every layer has
the same leaves: the two layouts change the function, not the tree.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)
from chipbench.weights_hybrid_lm import draw

#: the embedding's standard deviation: the stream a router reads un-normed has to outweigh what a layer writes into it
EMBEDDING_STD = 0.2

_KEYS = (
    "vocab_size", "hidden_size", "head_dim", "num_attention_heads", "num_key_value_heads", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts", "num_experts_held", "sliding_window_size",
)


def layer_plan(cfg: Dict[str, Any]) -> Tuple[Tuple[bool, bool], ...]:
    """``(rotated, windowed)`` of each layer run: of the published indices ``layers_held``."""
    return tuple((bool(cfg["rope_layout"][int(l)]), bool(cfg["sliding_window_layout"][int(l)])) for l in cfg["layers_held"])


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {
        "d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]), "Hkv": int(cfg["num_key_value_heads"]),
        "head": int(cfg["head_dim"]), "width": int(cfg["moe_ffn_hidden_size"]), "E": int(cfg["moe_num_primary_experts"]),
        "held": int(cfg["num_experts_held"]), "k": int(cfg["moe_num_active_primary_experts"]),
        "window": int(cfg["sliding_window_size"]), "vocab": int(cfg["vocab_size"]),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or ``ones``."""
    s = sizes(cfg)
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["head"]
    resid = 0.02 / math.sqrt(2 * int(cfg["published"]["num_hidden_layers"]))

    def dense(rows, cols, std=0.02):
        return {"kernel": ((rows, cols), std)}

    layer = {
        "router": dense(d, s["E"]),
        "input_layernorm": {"scale": ((d,), "ones")},
        "self_attn": {
            "q_proj": dense(d, H * D), "k_proj": dense(d, Hkv * D), "v_proj": dense(d, Hkv * D),
            "o_proj": dense(H * D, d, resid),
        },
        "post_attention_layernorm": {"scale": ((d,), "ones")},
        "block_sparse_moe": {
            "experts_w1": ((s["held"], d, s["width"]), 0.02), "experts_w3": ((s["held"], d, s["width"]), 0.02),
            "experts_w2": ((s["held"], s["width"], d), resid),
        },
    }
    tree = {
        "embed_tokens": {"embedding": ((s["vocab"], d), EMBEDDING_STD)}, "norm": {"scale": ((d,), "ones")},
        "lm_head": ((s["vocab"], d), 0.02),
    }
    tree.update({f"layers_{i}": layer for i in range(len(cfg["layers_held"]))})
    return {"params": tree}


def _frozen(cfg: Dict[str, Any]) -> str:
    """The keys the table reads, as a hashable static argument."""
    return json.dumps({
        **{k: int(cfg[k]) for k in _KEYS}, "layers_held": [int(l) for l in cfg["layers_held"]],
        "published": {"num_hidden_layers": int(cfg["published"]["num_hidden_layers"])},
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
