"""Operations the ``train_sambay_lm`` cells' work requires, computed from the
configuration file's shapes (``config.json`` keys of a ``phi4flash`` decoder,
the Mamba sizes it assumes and the published indices it holds), held against
``chipbench/arithmetic``'s table of peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  A Mamba-1 layer: its
four projections and the recurrence a step at a time, ``6 d_in N`` a token
(decay, write and read of a ``[d_in, N]`` state).  A gated memory unit: its
two projections.  A differential-attention layer: its projections (``X``: the
query's and the output's only) and, a pair of heads, two score maps at the
head size and two products against the doubled value, over the band a window
leaves (``sum_t min(t + 1, window)``) or the causal triangle.  Every layer:
the gated MLP.  The tied head's product on the ``T - 1`` places that enter
the loss; the embedding is a lookup.  Backward is twice forward; nothing
recomputed counts, so a rematerialised step reads lower; the convolution,
norms, gates, ``lambda`` and the pair's norm are no matrix products and are
left out.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_moe_lm import row_tokens  # noqa: F401  (the cells' row length is this module's too)
from chipbench.weights_sambay_lm import layer_kinds, leaf_table, sizes

#: ``name=`` of the scan's two ``pallas_call``s (``adapcc_tpu/ops/selective_scan.py``): what the device trace is read by
SSCAN_KERNELS = ("sscan_fwd", "sscan_bwd")


def parameter_count(cfg: Dict[str, Any]) -> int:
    """Parameters of the model as run: every leaf of the weights' table."""
    import jax
    import numpy as np

    from chipbench.weights import _is_leaf

    return sum(int(np.prod(shape)) for shape, _ in jax.tree_util.tree_leaves(leaf_table(cfg), is_leaf=_is_leaf))


def keys_seen(seq_len: int, kind: str, window: int) -> int:
    """Keys a query sees, summed over one row's queries: the band in ``S``,
    the causal triangle in ``F`` and ``X``."""
    if kind == "S" and window < seq_len:
        return window * (window + 1) // 2 + (seq_len - window) * window
    return seq_len * (seq_len + 1) // 2


def recurrence_flops_per_token_layer(cfg: Dict[str, Any]) -> float:
    """Forward FLOPs of one Mamba-1 layer's recurrence a token."""
    s = sizes(cfg)
    return 6.0 * s["d_in"] * s["N"]


def pair_flops_per_key(cfg: Dict[str, Any]) -> Dict[str, float]:
    """FLOPs of one head pair a key a query sees: forward two score maps at
    the head size and two products against the doubled value; backward, each
    softmax, the scores again, ``dV`` and ``dP`` at the value's width, ``dQ``
    and ``dK`` at the head size."""
    D = sizes(cfg)["head"]
    return {"fwd": 2 * (2 * D + 2 * 2 * D), "bwd": 2 * (3 * 2 * D + 2 * 2 * 2 * D)}


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward FLOPs a token of the row, by part (a matrix product of ``m x
    k`` by ``k x n`` is ``2 m k n``)."""
    s, T = sizes(cfg), seq_len
    d, d_in, N, R, H, Hkv, D = (s[k] for k in ("d", "d_in", "N", "R", "H", "Hkv", "head"))
    kinds = layer_kinds(cfg)
    scans, units = kinds.count("M") + kinds.count("M*"), kinds.count("G")
    own, cross = kinds.count("S") + kinds.count("F"), kinds.count("X")
    window = int(cfg["sliding_window"])
    keys = sum(keys_seen(T, kind, window) for kind in kinds if kind in ("S", "F", "X")) / T
    return {
        "scan_projections": scans * 2 * (d * 2 * d_in + d_in * (R + 2 * N) + R * d_in + d_in * d),
        "scan_recurrence": scans * recurrence_flops_per_token_layer(cfg),
        "memory_units": units * 2 * 2 * d * d_in,
        "attention_projections": own * 2 * (d * (H + 2 * Hkv) * D + H * D * d) + cross * 2 * 2 * d * H * D,
        "attention_products": (H // 2) * pair_flops_per_key(cfg)["fwd"] * keys,
        "mlp": len(kinds) * 3 * 2 * d * s["wide"],
        "head": 2 * d * int(cfg["vocab_size"]) * (T - 1) / T,
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def sscan_flops(batch: int, cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """One Mamba-1 layer's recurrence."""
    one = batch * seq_len * recurrence_flops_per_token_layer(cfg)
    return {"fwd": one, "bwd": 2 * one}


def sscan_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """The least any implementation must move across HBM: forward reads x,
    the step sizes, B and C and writes y, each once at the activations' two
    bytes; backward reads the four again with dy and writes their four
    gradients (those of ``A`` and ``D`` are no wider than the state)."""
    s = sizes(cfg)
    tokens = batch * seq_len
    four = tokens * (2 * s["d_in"] + 2 * s["N"]) * itemsize
    wide = tokens * s["d_in"] * itemsize
    return {"fwd": four + wide, "bwd": four + wide + four}


def diff_attention_flops(batch: int, cfg: Dict[str, Any], seq_len: int, kind: str) -> Dict[str, float]:
    """One differential-attention layer's products over the keys its mask leaves."""
    per_key = pair_flops_per_key(cfg)
    area = batch * (sizes(cfg)["H"] // 2) * keys_seen(seq_len, kind, int(cfg["sliding_window"]))
    return {"fwd": per_key["fwd"] * area, "bwd": per_key["bwd"] * area}


def diff_attention_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """q, K and V read once and the two softmaxes' outputs written, forward;
    backward reads them and the outputs' cotangents and writes dq, dK, dV."""
    s = sizes(cfg)
    tokens = batch * seq_len
    qkv = tokens * (s["H"] + 2 * s["Hkv"]) * s["head"] * itemsize
    out = tokens * 2 * s["H"] * s["head"] * itemsize          # a1 and a2: a pair's 128 channels each
    return {"fwd": qkv + out, "bwd": 2 * qkv + 2 * out}
