"""Seeded random weights for the ``train_ssm_lm`` runner, made on the device
in one jitted call, in the layout
``adapcc_tpu.models.granite_hybrid.GraniteHybrid`` reads
(``params/layers_<i>/mixer/in_proj/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): every matrix and the
embedding normal(0, 0.02); the projections back into the residual stream
(``out_proj``, ``o_proj``, every ``down_proj``) scaled by ``1/sqrt(2 *
num_hidden_layers)`` as ``chipbench/weights.py`` scales GPT-2's; every norm's
scale 1; ``D`` 1; the convolution's bias 0; its taps, ``A_log`` and
``dt_bias`` as ``chipbench/weights_hybrid_lm.draw`` draws Kimi-Linear's
(uniform(-1/2, 1/2); log(uniform(1, 16)); softplus^-1 of a step log-uniform in
[0.001, 0.1]).  No head of its own: the embedding is the head.
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
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "shared_intermediate_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
)


def layer_kinds(cfg: Dict[str, Any]) -> tuple:
    """The mixer of each layer run: the first ``num_hidden_layers`` of the published ``layer_types``."""
    return tuple(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or
    one of ``ones``, ``zeros``, ``taps``, ``a_log``, ``dt_bias``."""
    d, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    head = d // H
    heads, P, N, K = (int(cfg[k]) for k in ("mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv"))
    d_in, wide = heads * P, int(cfg["shared_intermediate_size"])
    resid = 0.02 / math.sqrt(2 * L)

    def norm(n=d):
        return {"scale": ((n,), "ones")}

    def dense(rows, cols, std=0.02):
        return {"kernel": ((rows, cols), std)}

    mixers = {
        "mamba": {
            "in_proj": dense(d, 2 * d_in + 2 * N + heads), "conv_taps": ((K, d_in + 2 * N), "taps"),
            "conv_bias": ((d_in + 2 * N,), "zeros"), "A_log": ((heads,), "a_log"), "dt_bias": ((heads,), "dt_bias"),
            "D": ((heads,), "ones"), "norm": norm(d_in), "out_proj": dense(d_in, d, resid),
        },
        "attention": {
            "q_proj": dense(d, H * head), "k_proj": dense(d, Hkv * head), "v_proj": dense(d, Hkv * head),
            "o_proj": dense(H * head, d, resid),
        },
    }
    tree = {"embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)}, "norm": norm()}
    for i, kind in enumerate(layer_kinds(cfg)):
        tree[f"layers_{i}"] = {
            "input_layernorm": norm(), "post_attention_layernorm": norm(), "mixer": mixers[kind],
            "mlp": {"gate_proj": dense(d, wide), "up_proj": dense(d, wide), "down_proj": dense(wide, d, resid)},
        }
    return {"params": tree}


def _frozen(cfg: Dict[str, Any]) -> str:
    """The keys the table reads, as a hashable static argument."""
    return json.dumps({**{k: int(cfg[k]) for k in _KEYS}, "layer_types": list(layer_kinds(cfg))}, sort_keys=True)


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
