"""Operations and bytes the ``train_moe_lm`` cells' work requires, computed
from the configuration file's shapes (``config.json`` keys), held against
``chipbench/arithmetic``'s table of peaks.  Kept with the benchmark.

Attention counts what the mask leaves: on a ``sliding_attention`` layer query
``t`` sees ``min(t + 1, sliding_window)`` keys (the band), on a
``full_attention`` layer ``t + 1`` (the triangle).  K and V count at the KV
heads' width, q, o and their gradients at the query heads'.  The routed
experts count by the assignments the steps really computed, not by the
router's expectation.  Backward is twice forward; nothing recomputed counts.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.weights_moe_lm import layer_kinds


def row_tokens(mix: Dict[str, Any]) -> int:
    """Tokens of a training row: ``walks_per_row`` of the generator's walks of
    ``seq_len`` tokens, packed end to end."""
    return int(mix["seq_len"]) * int(mix.get("walks_per_row", 1))


def keys_seen(seq_len: int, kind: str, window: int) -> float:
    """Keys a query sees, summed over one row's queries."""
    if kind == "sliding_attention" and window < seq_len:
        return window * (window + 1) / 2 + (seq_len - window) * window
    return seq_len * (seq_len + 1) / 2


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> Dict[str, float]:
    """Forward FLOPs a token, by part (a matrix product of ``m x k`` by
    ``k x n`` is ``2 m k n``)."""
    d, H, Hkv, D = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim"))
    kinds = layer_kinds(cfg)
    sparse = len(kinds) - int(cfg["num_dense_layers"])
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    seen = sum(keys_seen(seq_len, kind, int(cfg["sliding_window"])) for kind in kinds) / seq_len
    return {
        "attention_products": 2 * 2 * H * D * seen,
        "attention_projections": len(kinds) * (2 * d * (2 * H * D + 2 * Hkv * D) + 2 * H * D * d),
        "dense_ffn": int(cfg["num_dense_layers"]) * gated(int(cfg["intermediate_size"])),
        "router": sparse * 2 * d * int(cfg["num_experts"]),
        "shared_experts": sparse * gated(int(cfg["moe_intermediate_size"])),
        "routed_experts": sparse * assignments_per_token_layer * gated(int(cfg["moe_intermediate_size"])),
        "head": 2 * d * int(cfg["vocab_size"]),
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len, assignments_per_token_layer).values())


def attention_flops(batch: int, cfg: Dict[str, Any], seq_len: int, kind: str) -> Dict[str, float]:
    """One attention call's kernels: two products forward, five backward."""
    one = 2 * batch * int(cfg["num_attention_heads"]) * int(cfg["head_dim"]) * keys_seen(
        seq_len, kind, int(cfg["sliding_window"])
    )
    return {"fwd": 2 * one, "bwd": 5 * one}


def attention_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes that cross HBM once: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    row = batch * seq_len * int(cfg["head_dim"]) * itemsize
    wide, narrow = row * int(cfg["num_attention_heads"]), row * int(cfg["num_key_value_heads"])
    return {"fwd": 2 * wide + 2 * narrow, "bwd": 4 * wide + 4 * narrow}
