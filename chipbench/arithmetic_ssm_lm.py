"""Operations the ``train_ssm_lm`` cells' work requires, computed from the
configuration file's shapes (``config.json`` keys of a Mamba-2 / attention
hybrid without experts), held against ``chipbench/arithmetic``'s table of
peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  A Mamba-2 layer: its two
projections and the recurrence a step at a time, ``6 P N`` a token a head
(decay, write and read of a ``[P, N]`` state: what ``arithmetic_hybrid_lm``
counts for KDA's, whose state is the same size); whatever a chunked form adds
(the products inside a chunk, the backward kernel's recomputation) is not
required.  An attention layer: four projections and the causal triangle at
the head size.  Every layer: the gated MLP.  The tied head's product on the
``T - 1`` places that enter the loss; the embedding is a lookup.  Backward is
twice forward; nothing recomputed counts, so a rematerialised step reads
lower; the convolution, norms, gates and scalings are no matrix products and
are left out.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_moe_lm import row_tokens  # noqa: F401  (the cells' row length is this module's too)
from chipbench.weights_ssm_lm import layer_kinds, leaf_table

#: ``name=`` of the scan's two ``pallas_call``s (``adapcc_tpu/ops/ssd.py``): what the device trace is read by
SSD_KERNELS = ("ssd_fwd", "ssd_bwd")


def parameter_count(cfg: Dict[str, Any]) -> int:
    """Parameters of the model as run: every leaf of the weights' table."""
    import jax
    import numpy as np

    from chipbench.weights import _is_leaf

    return sum(int(np.prod(shape)) for shape, _ in jax.tree_util.tree_leaves(leaf_table(cfg), is_leaf=_is_leaf))


def _ssm(cfg: Dict[str, Any]):
    return int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]), int(cfg["mamba_d_state"])


def recurrence_flops_per_token_layer(cfg: Dict[str, Any]) -> float:
    """Forward FLOPs of one Mamba-2 layer's recurrence a token."""
    H, P, N = _ssm(cfg)
    return 6.0 * H * P * N


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward FLOPs a token of the row, by part (a matrix product of ``m x
    k`` by ``k x n`` is ``2 m k n``)."""
    d, T = int(cfg["hidden_size"]), seq_len
    kinds = layer_kinds(cfg)
    mamba, attention = kinds.count("mamba"), kinds.count("attention")
    H, P, N = _ssm(cfg)
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    head = d // heads
    return {
        "ssm_projections": mamba * (2 * d * (2 * H * P + 2 * N + H) + 2 * H * P * d),
        "ssm_recurrence": mamba * recurrence_flops_per_token_layer(cfg),
        "attention_projections": attention * (2 * 2 * d * heads * head + 2 * 2 * d * kv * head),
        "attention_products": attention * 2 * heads * 2 * head * (T + 1) / 2,
        "mlp": len(kinds) * 3 * 2 * d * int(cfg["shared_intermediate_size"]),
        "head": 2 * d * int(cfg["vocab_size"]) * (T - 1) / T,
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def ssd_flops(batch: int, cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """One Mamba-2 layer's recurrence."""
    one = batch * seq_len * recurrence_flops_per_token_layer(cfg)
    return {"fwd": one, "bwd": 2 * one}


def ssd_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes that cross HBM once: forward reads x, B and C in the
    activations' dtype and the step sizes in float32 and writes y; backward
    reads them again with dy and writes the four arrays' gradients (those of
    ``A`` and ``D`` are 64 numbers each)."""
    H, P, N = _ssm(cfg)
    tokens = batch * seq_len
    read = tokens * ((H * P + 2 * N) * itemsize + H * 4)
    out = tokens * H * P * itemsize
    return {"fwd": read + out, "bwd": 2 * read + out}
