"""Operations the ``train_mla_lm`` cells' work requires, computed from the
configuration file's shapes (``config.json`` keys of a latent-attention model
with a multi-token-prediction module), held against ``chipbench/arithmetic``'s
table of peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  Every layer's mixer is
latent attention: its projections (the query's two, the latent's two, the
output's) and the causal triangle with scores at ``qk_nope_head_dim +
qk_rope_head_dim`` and values at ``v_head_dim`` (the kernels' own FLOPs and
bytes are ``arithmetic_hybrid_lm.mla_flops`` / ``mla_bytes``: these keys are
the ones they read).  **The MTP module is required by the objective and
counted**: its merge, its block and its head, on the ``T - 1`` places it
exists on, and its loss's head product on ``T - 2``; the trunk's head on
``T - 1``.  The routed experts count by the assignments the steps really
computed.  Backward is twice forward; nothing recomputed counts; the norms,
the rotation and the router's top-k are no matrix products and are left out.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_hybrid_lm import mla_bytes, mla_flops  # noqa: F401  (this module's too)
from chipbench.arithmetic_moe_lm import row_tokens  # noqa: F401  (the cells' row length is this module's too)


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> Dict[str, float]:
    """Forward FLOPs a token of the row, by part (a matrix product of ``m x
    k`` by ``k x n`` is ``2 m k n``).  ``assignments_per_token_layer`` is the
    mean over the expert layers, the module's among them."""
    d, T = int(cfg["hidden_size"]), seq_len
    trunk, module = int(cfg["num_hidden_layers"]), int(cfg["num_nextn_predict_layers"])
    dense = int(cfg["first_k_dense_replace"])
    H, q_rank, rank = int(cfg["num_attention_heads"]), int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, pe, dv = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    there = (T - 1) / T                        # the module's places a token of the row
    projections = 2 * d * q_rank + 2 * q_rank * H * (nope + pe) + 2 * d * (rank + pe) + 2 * rank * H * (nope + dv) + 2 * H * dv * d
    sparse = trunk - dense + module * there    # expert layers a token meets
    return {
        "mla_projections": (trunk + module * there) * projections,
        "mla_products": 2 * H * (nope + pe + dv) * (trunk * (T + 1) / 2 + module * there * T / 2),
        "dense_ffn": dense * gated(int(cfg["intermediate_size"])),
        "router": sparse * 2 * d * int(cfg["n_routed_experts"]),
        "shared_experts": sparse * gated(int(cfg["moe_intermediate_size"])),
        "routed_experts": (trunk - dense + module) * assignments_per_token_layer * gated(int(cfg["moe_intermediate_size"])),
        "mtp_merge": module * there * 2 * 2 * d * d,
        "head": 2 * d * int(cfg["vocab_size"]) * (there + module * (T - 2) / T),
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len, assignments_per_token_layer).values())
