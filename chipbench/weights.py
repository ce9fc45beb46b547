"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights and hands the same ones to the program and
to the plain reference; neither takes anything the other made.  The tree has
the layout ``adapcc_tpu.models.gpt2.GPT2`` reads (the program's interface:
``params/wte/embedding``, ``params/h<i>/attn/qkv/kernel`` ...), float32, the
type the parameters are trained in.

The scheme is GPT-2's published one: every weight matrix and embedding
normal(0, 0.02), the two projections back into the residual stream scaled by
``1/sqrt(2 * n_layer)``, biases 0, LayerNorm scale 1.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree with ``(shape, std)`` at each leaf; ``std`` None is
    a constant leaf (``ones`` for a LayerNorm scale, ``zeros`` for a bias)."""
    d, L = int(cfg["d_model"]), int(cfg["n_layer"])
    resid = 0.02 / math.sqrt(2 * L)

    def ln():
        return {"scale": ((d,), "ones"), "bias": ((d,), "zeros")}

    def dense(n_in, n_out, std):
        return {"kernel": ((n_in, n_out), std), "bias": ((n_out,), "zeros")}

    tree = {
        "wte": {"embedding": ((int(cfg["vocab_size"]), d), 0.02)},
        "wpe": {"embedding": ((int(cfg["max_seq"]), d), 0.02)},
        "ln_f": ln(),
    }
    for i in range(L):
        tree[f"h{i}"] = {
            "ln1": ln(),
            "attn": {"qkv": dense(d, 3 * d, 0.02), "proj": dense(d, d, resid)},
            "ln2": ln(),
            "fc": dense(d, 4 * d, 0.02),
            "proj": dense(4 * d, d, resid),
        }
    return {"params": tree}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int):
    """A key from any whole number up to 2**62: the low 31 bits seed it, the
    rest is folded in (``PRNGKey`` itself takes 32 signed bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _build(key, cfg_items: Tuple[Tuple[str, Any], ...]):
    table = leaf_table(dict(cfg_items))
    leaves, treedef = jax.tree_util.tree_flatten(table, is_leaf=_is_leaf)
    out = []
    for i, (shape, std) in enumerate(leaves):
        if std == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif std == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


_WIDTH_KEYS = ("vocab_size", "max_seq", "n_layer", "n_head", "d_model")


def _widths(cfg: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple((k, int(cfg[k])) for k in _WIDTH_KEYS)


def make_params(seed: int, cfg: Dict[str, Any], sharding: Optional[Any] = None):
    """The whole tree in one jitted program; ``sharding`` (a replicated
    ``NamedSharding`` for a mesh) has every chip make its own copy, so
    nothing is transferred."""
    fn = jax.jit(_build, static_argnums=1, out_shardings=sharding)
    return fn(seed_key(seed), _widths(cfg))


def params_like(cfg: Dict[str, Any]):
    """``_build`` for use inside another jitted function (regenerating the
    initial weights where holding a second copy would cost memory)."""
    items = _widths(cfg)
    return lambda key: _build(key, items)
