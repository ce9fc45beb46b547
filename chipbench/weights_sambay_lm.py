"""Seeded random weights for the ``train_sambay_lm`` runner, made on the device
in one jitted call, in the layout ``adapcc_tpu.models.phi4_flash.Phi4Flash``
reads (``params/layers_<i>/mixer/in_proj/kernel`` ...), float32.

Assumed (the published checkpoint's initialisation is not in ``config.json``;
the configuration file says so under ``assumed``): cell 6's recipe: every
matrix and the embedding normal(0, 0.02); the projections back into the
residual stream (every mixer's ``out_proj``, every ``down_proj``) scaled by
``1/sqrt(2 * layers run)``; every norm's scale 1 and every bias 0; ``D`` 1;
the convolution's taps uniform(-1/2, 1/2) and ``dt_bias`` the softplus^-1 of a
step log-uniform in [0.001, 0.1] (``chipbench/weights_hybrid_lm.draw``); and,
as the published Mamba code makes them, ``dt_proj`` uniform(-1/sqrt(dt_rank),
1/sqrt(dt_rank)) and ``A_log = log(1 .. 16)`` along the state axis, the same
for every channel; the four ``lambda`` vectors of an attention layer
normal(0, 0.1).  No head of its own: the embedding is the head.

**The attention projections' columns lie group by group in the program**
(``[q1 | q2 | k1 | k2 | V]``: every pair's first query head, then every
second, the keys likewise, then the values) where the published order, which
the plain reference pairs as heads ``2p, 2p + 1``, is ``[q | k | v]`` with the
heads in order.  :func:`grouped_columns` is the fixed permutation and
:func:`published_order` hands the reference the same weights through it.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import keystr

from chipbench import weights_hybrid_lm
from chipbench.weights import _is_leaf, seed_key  # noqa: F401  (seed_key is this module's too)

MAMBA = {"d_state": 16, "d_conv": 4, "expand": 2}      # the published code's defaults; no key of config.json


def kind_of(layer: int, published_layers: int) -> str:
    """The kind of published layer ``layer``: ``M`` / ``S`` below the middle,
    ``M*`` at it, ``F`` after it, then ``G`` / ``X``."""
    half = published_layers // 2
    if layer % 2 == 0:
        return "M" if layer < half else "M*" if layer == half else "G"
    return "S" if layer < half else "F" if layer == half + 1 else "X"


def layer_kinds(cfg: Dict[str, Any]) -> tuple:
    """The kind of each layer run: of the published indices ``layers_held``."""
    return tuple(kind_of(int(i), int(cfg["published"]["num_hidden_layers"])) for i in cfg["layers_held"])


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    mamba = {**MAMBA, **cfg.get("assumed", {}).get("mamba", {})}
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return {
        "d": d, "wide": int(cfg["intermediate_size"]), "H": H, "Hkv": Hkv, "head": d // H,
        "d_in": int(mamba["expand"]) * d, "N": int(mamba["d_state"]), "K": int(mamba["d_conv"]),
        "R": int(mamba.get("dt_rank", -(-d // 16))),
    }


def grouped_columns(heads: int, kv_heads: int, head_dim: int, cross: bool = False) -> np.ndarray:
    """For each column of the program's projection, the published column it holds."""
    def halves(first, n):
        cols = first + np.arange(n * head_dim).reshape(n, head_dim)
        return [cols[0::2].reshape(-1), cols[1::2].reshape(-1)]

    q, kv = heads * head_dim, kv_heads * head_dim
    parts = halves(0, heads)
    if not cross:
        parts += halves(q, kv_heads) + [q + kv + np.arange(kv)]
    return np.concatenate(parts)


def published_order(params, cfg: Dict[str, Any]):
    """``params`` with every attention projection's columns (and its bias) in
    the published order: what the plain reference reads."""
    s = sizes(cfg)
    out = {"params": dict(params["params"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        name = {"S": "qkv_proj", "F": "qkv_proj", "X": "q_proj"}.get(kind)
        if name is None:
            continue
        back = np.argsort(grouped_columns(s["H"], s["Hkv"], s["head"], cross=kind == "X"))
        layer = dict(out["params"][f"layers_{i}"])
        mixer = dict(layer["mixer"])
        mixer[name] = {"kernel": mixer[name]["kernel"][:, back], "bias": mixer[name]["bias"][back]}
        layer["mixer"] = mixer
        out["params"][f"layers_{i}"] = layer
    return out


def bias_parts(cfg: Dict[str, Any]) -> list:
    """Where the query's part of a ``qkv_proj``'s bias ends and where the
    key's does: the same places in the program's column order and in the
    published one."""
    s = sizes(cfg)
    return [s["H"] * s["head"], (s["H"] + s["Hkv"]) * s["head"]]


def key_bias_apart(tree, cfg: Dict[str, Any]):
    """``tree`` (parameters, a gradient) with every ``qkv_proj``'s bias as
    three leaves, the query's part, the key's and the value's: **the key's
    bias has no gradient** (it adds the same number to every score of a row,
    which the softmax takes out again), so what either side holds there is
    rounding, and the comparison needs it as a leaf of its own to leave it out
    (``train_sambay_lm.compare``)."""
    out = {"params": dict(tree["params"])}
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind in ("S", "F"):
            layer = dict(out["params"][f"layers_{i}"])
            proj = dict(layer["mixer"]["qkv_proj"])
            proj["bias"] = tuple(jnp.split(proj["bias"], bias_parts(cfg)))
            layer["mixer"] = {**layer["mixer"], "qkv_proj": proj}
            out["params"][f"layers_{i}"] = layer
    return out


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``(shape, draw)`` for every leaf; ``draw`` is a standard deviation or
    one of ``ones``, ``zeros``, ``taps``, ``dt_bias``, ``a_log_states``."""
    s = sizes(cfg)
    d, wide, H, Hkv, head, d_in, N, K, R = (s[k] for k in ("d", "wide", "H", "Hkv", "head", "d_in", "N", "K", "R"))
    kinds = layer_kinds(cfg)
    resid = 0.02 / math.sqrt(2 * len(kinds))

    def norm():
        return {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}

    def dense(rows, cols, std=0.02, bias=False):
        return {"kernel": ((rows, cols), std), **({"bias": ((cols,), "zeros")} if bias else {})}

    def attention(first: str, cols: int):
        return {
            first: dense(d, cols, bias=True), **{f"lambda_{v}": ((head,), 0.1) for v in ("q1", "k1", "q2", "k2")},
            "subln": ((2 * head,), "ones"), "out_proj": dense(H * head, d, resid, bias=True),
        }

    mamba = {
        "in_proj": dense(d, 2 * d_in), "conv_taps": ((K, d_in), "taps"), "conv_bias": ((d_in,), "zeros"),
        "x_proj": dense(d_in, R + 2 * N), "dt_proj": ((R, d_in), "taps"), "dt_bias": ((d_in,), "dt_bias"),
        "A_log": ((d_in, N), "a_log_states"), "D": ((d_in,), "ones"), "out_proj": dense(d_in, d, resid),
    }
    both = attention("qkv_proj", (H + 2 * Hkv) * head)
    mixers = {
        "M": mamba, "M*": mamba, "G": {"in_proj": dense(d, d_in), "out_proj": dense(d_in, d, resid)},
        "S": both, "F": both, "X": attention("q_proj", H * head),
    }
    tree = {"embed_tokens": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)}, "norm": norm()}
    for i, kind in enumerate(kinds):
        tree[f"layers_{i}"] = {
            "input_layernorm": norm(), "post_attention_layernorm": norm(), "mixer": mixers[kind],
            "mlp": {"gate_proj": dense(d, wide), "up_proj": dense(d, wide), "down_proj": dense(wide, d, resid)},
        }
    return {"params": tree}


def draw(key, shape, how):
    """One leaf from its key: ``weights_hybrid_lm.draw``, and ``A_log = log(1
    .. N)`` along the state axis."""
    if how == "a_log_states":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    return weights_hybrid_lm.draw(key, shape, how)


def _frozen(cfg: Dict[str, Any]) -> str:
    """What the table reads, as a hashable static argument."""
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads")
    return json.dumps({
        **{k: int(cfg[k]) for k in keys}, "layers_held": [int(i) for i in cfg["layers_held"]],
        "published": {"num_hidden_layers": int(cfg["published"]["num_hidden_layers"])},
        "assumed": {"mamba": cfg.get("assumed", {}).get("mamba", {})},
    }, sort_keys=True)


@functools.lru_cache(maxsize=8)
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
    made, in :func:`key_bias_apart`'s ``tree_leaves`` order (a ``qkv_proj``'s
    bias gives three); a leaf at a time, so that the initial weights never
    exist whole beside a full chip."""

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def one(leaf, key, shape, how, parts):
        moved = leaf.astype(jnp.float32) - draw(key, shape, how)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(part))) for part in jnp.split(moved, parts)])

    specs, _ = _table(_frozen(cfg))
    leaves = jax.tree_util.tree_leaves_with_path(params)
    if len(leaves) != len(specs):
        raise ValueError(f"{len(leaves)} leaves against {len(specs)} in the table")
    key, parts = seed_key(seed), tuple(bias_parts(cfg))
    return jnp.concatenate([
        one(leaf, jax.random.fold_in(key, i), shape, how, parts if "['qkv_proj']['bias']" in keystr(path) else ())
        for i, ((path, leaf), (shape, how)) in enumerate(zip(leaves, specs))
    ])
