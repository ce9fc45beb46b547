"""Operations the ``train_lfm2_lm`` cells' work requires, computed from the
configuration file's shapes (``config.json`` keys of an ``lfm2_moe`` decoder
and the published indices it holds), held against ``chipbench/arithmetic``'s
table of peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  A convolution layer's
mixer: its two projections (``2048 x 6144`` and ``2048 x 2048``); the gated
convolution itself is no matrix product and is left out of the step's FLOPs
(it has its own roofline, below).  The attention layer: its four projections
and the causal triangle with scores and values at the head size.  The dense
layer's gated MLP; an expert layer's router and the routed experts **by the
assignments the steps really computed**; the tied head's product on the ``T -
1`` places that enter the loss; the embedding is a lookup.  Backward is twice
forward; nothing recomputed counts; norms, the rotation, the gates and the
router's top-k are no matrix products and are left out.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_moe_lm import row_tokens  # noqa: F401  (the cells' row length is this module's too)
from chipbench.weights_lfm2_lm import layer_plan, leaf_table, sizes

#: ``name=`` of the gated convolution's two ``pallas_call``s (``adapcc_tpu/ops/short_conv.py``): what the device trace is read by
GCONV_KERNELS = ("gated_conv_fwd", "gated_conv_bwd")


def parameter_count(cfg: Dict[str, Any]) -> int:
    """Parameters of the model as run: every leaf of the weights' table."""
    import jax
    import numpy as np

    from chipbench.weights import _is_leaf

    return sum(int(np.prod(shape)) for shape, _ in jax.tree_util.tree_leaves(leaf_table(cfg), is_leaf=_is_leaf))


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> Dict[str, float]:
    """Forward FLOPs a token of the row, by part (a matrix product of ``m x
    k`` by ``k x n`` is ``2 m k n``).  ``assignments_per_token_layer``: the
    held experts' assignments a token, mean over the expert layers."""
    s, T = sizes(cfg), seq_len
    d, H, Hkv, D = s["d"], s["H"], s["Hkv"], s["head"]
    plan = layer_plan(cfg)
    convs = sum(kind == "conv" for kind, _ in plan)
    attns = len(plan) - convs
    sparse = sum(is_sparse for _, is_sparse in plan)
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    return {
        "conv_projections": convs * 2 * (d * 3 * d + d * d),
        "attention_projections": attns * 2 * (d * (H + 2 * Hkv) * D + H * D * d),
        "attention_products": attns * H * 2 * 2 * D * (T + 1) / 2,
        "dense_mlp": (len(plan) - sparse) * gated(s["wide"]),
        "router": sparse * 2 * d * s["E"],
        "routed_experts": sparse * assignments_per_token_layer * gated(s["narrow"]),
        "head": 2 * d * int(cfg["vocab_size"]) * (T - 1) / T,
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len, assignments_per_token_layer).values())


def gconv_flops(batch: int, cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """One layer's gated convolution: forward ``B x``, ``K`` taps multiplied
    and added, ``C``'s product (``2 + 2 K`` a channel and token); backward
    ``dy C``, ``dz``'s ``K`` taps, ``dB``, ``dx``, ``dC`` and the taps' own
    sums (``4 + 4 K``; forming ``c`` again is not required work)."""
    s = sizes(cfg)
    each = batch * seq_len * s["d"]
    return {"fwd": each * (2 + 2 * s["K"]), "bwd": each * (4 + 4 * s["K"])}


def gconv_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """The least any implementation must move across HBM: forward reads
    ``B``, ``C``, ``x`` and writes ``y`` once at the activations' two bytes;
    backward reads the three again with ``dy`` and writes three gradients
    (the taps and their gradient are ``K`` rows: nothing beside these)."""
    each = batch * seq_len * sizes(cfg)["d"] * itemsize
    return {"fwd": 4 * each, "bwd": 7 * each}
