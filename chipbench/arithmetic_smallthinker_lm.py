"""Operations the ``train_smallthinker_lm`` cells' work requires, computed from
the configuration file's shapes (``config.json`` keys of SmallThinker's
decoder and the published indices it holds), held against
``chipbench/arithmetic``'s table of peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  A layer: the router
(``hidden x 64``), the four attention projections (q and o at the 28 query
heads' width, k and v at the 4 K/V heads'), the two attention products over
what the mask leaves (on a windowed layer query ``t`` sees ``min(t + 1,
window)`` keys, on a global one ``t + 1``), and the routed experts **by the
assignments the steps really computed** (three matrices of ``hidden x 768`` an
assignment; a product whose gate relu zeroed still counts: the mathematics
multiplies by that zero).  The untied head's product on the ``T - 1`` places
that enter the loss; the embedding is a lookup.  Backward is twice forward;
nothing recomputed counts; norms, the rotation, the softmax over the chosen
six and the router's top-k are no matrix products and are left out.

The flash kernels count as ``arithmetic_moe_lm.attention_flops`` counts them
(two products forward, five backward, by the mask's area) at this file's
heads: a group of seven query heads shares a K/V head, which changes the bytes
(K and V at 4 heads' width), not the products.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_moe_lm import keys_seen, row_tokens  # noqa: F401  (the cells' row length is this module's too)
from chipbench.weights_smallthinker_lm import layer_plan, leaf_table, sizes


def parameter_count(cfg: Dict[str, Any]) -> int:
    """Parameters of the model as run: every leaf of the weights' table."""
    import jax
    import numpy as np

    from chipbench.weights import _is_leaf

    return sum(int(np.prod(shape)) for shape, _ in jax.tree_util.tree_leaves(leaf_table(cfg), is_leaf=_is_leaf))


def _kind(windowed: bool) -> str:
    """``arithmetic_moe_lm.keys_seen``'s word for a layer's mask."""
    return "sliding_attention" if windowed else "full_attention"


def pairs_seen(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Query-key pairs the mask leaves in one row, summed over the layers of each kind."""
    out = {"attn_full": 0.0, "attn_window": 0.0}
    for _, windowed in layer_plan(cfg):
        out["attn_window" if windowed else "attn_full"] += keys_seen(seq_len, _kind(windowed), sizes(cfg)["window"])
    return out


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> Dict[str, float]:
    """Forward FLOPs a token of the row, by part (a matrix product of ``m x
    k`` by ``k x n`` is ``2 m k n``).  ``assignments_per_token_layer``: the
    held experts' assignments a token, mean over the layers."""
    s, T = sizes(cfg), seq_len
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["head"]
    layers = len(layer_plan(cfg))
    return {
        "router": layers * 2 * d * s["E"],
        "attention_projections": layers * 2 * (d * (H + 2 * Hkv) * D + H * D * d),
        "attention_products": 2 * 2 * H * D * sum(pairs_seen(cfg, T).values()) / T,
        "routed_experts": layers * assignments_per_token_layer * 3 * 2 * d * s["width"],
        "head": 2 * d * s["vocab"] * (T - 1) / T,
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len, assignments_per_token_layer).values())


def attention_flops(batch: int, cfg: Dict[str, Any], seq_len: int, windowed: bool) -> Dict[str, float]:
    """One layer's flash kernels: two products forward, five backward, each
    ``2 · heads · head size`` a pair the mask leaves."""
    s = sizes(cfg)
    one = 2 * batch * s["H"] * s["head"] * keys_seen(seq_len, _kind(windowed), s["window"])
    return {"fwd": 2 * one, "bwd": 5 * one}


def attention_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes that cross HBM once in one layer's flash kernels: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv;
    K and V at the K/V heads' width."""
    s = sizes(cfg)
    row = batch * seq_len * s["head"] * itemsize
    wide, narrow = row * s["H"], row * s["Hkv"]
    return {"fwd": 2 * wide + 2 * narrow, "bwd": 4 * wide + 4 * narrow}
