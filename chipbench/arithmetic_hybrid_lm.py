"""Operations and bytes the ``train_hybrid_lm`` cells' work requires, computed
from the configuration file's shapes (``config.json`` keys), held against
``chipbench/arithmetic``'s table of peaks.  Kept with the benchmark.

Fixed by the mathematics, not by the implementation.  **KDA** counts the
recurrence a step at a time: ``6 · d_k · d_v`` FLOPs a token a head forward
(decay the state, read it with ``k``, write the rank-one correction, read it
with ``q``: a multiply or a multiply-add over the ``[d_k, d_v]`` state each),
twice that backward; its bytes are q, k, v, the decay and beta read and o
written once (backward: the same read again with ``do``, five gradients
written).  A chunked kernel does more than that (the solve, the products
inside a chunk): the surplus reads as distance from the roofline.  **Latent
attention** counts the causal triangle, scores at ``qk_nope_head_dim +
qk_rope_head_dim`` and values at ``v_head_dim``, two products forward and
five backward, q, K, V read once.  The routed experts count by the
assignments the steps really computed.  Backward is twice forward; nothing
recomputed counts; the short convolutions, norms, gates and the router's
top-k are not matrix products and are left out.
"""

from __future__ import annotations

from typing import Any, Dict

from chipbench.arithmetic_moe_lm import row_tokens  # noqa: F401  (the cells' row length is this module's too)
from chipbench.weights_hybrid_lm import layer_kinds


def _kda(cfg):
    group = cfg["linear_attn_config"]
    return int(group["num_heads"]), int(group["head_dim"])


def _mla(cfg):
    return (
        int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]),
    )


def forward_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> Dict[str, float]:
    """Forward FLOPs a token, by part (a matrix product of ``m x k`` by
    ``k x n`` is ``2 m k n``)."""
    d = int(cfg["hidden_size"])
    kinds = layer_kinds(cfg)
    n_kda, n_mla = kinds.count("kda"), kinds.count("mla")
    dense = int(cfg["first_k_dense_replace"])
    sparse = len(kinds) - dense
    Hk, Dk = _kda(cfg)
    H, dqk, dv = _mla(cfg)
    rank, pe = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    gated = lambda width: 3 * 2 * d * width  # noqa: E731
    low_rank = 2 * d * Dk + 2 * Dk * Hk * Dk              # a gate's two products
    return {
        "kda_projections": n_kda * (3 * 2 * d * Hk * Dk + 2 * low_rank + 2 * d * Hk + 2 * Hk * Dk * d),
        "kda_recurrence": n_kda * Hk * 6 * Dk * Dk,
        "mla_projections": n_mla * (2 * d * H * dqk + 2 * d * (rank + pe) + 2 * rank * H * (dqk - pe + dv) + 2 * H * dv * d),
        "mla_products": n_mla * 2 * H * (dqk + dv) * (seq_len + 1) / 2,
        "dense_ffn": dense * gated(int(cfg["intermediate_size"])),
        "router": sparse * 2 * d * int(cfg["num_experts"]),
        "shared_experts": sparse * gated(int(cfg["moe_intermediate_size"])),
        "routed_experts": sparse * assignments_per_token_layer * gated(int(cfg["moe_intermediate_size"])),
        "head": 2 * d * int(cfg["vocab_size"]),
    }


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int, assignments_per_token_layer: float) -> float:
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len, assignments_per_token_layer).values())


def kda_flops(batch: int, cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """One KDA layer's recurrence."""
    Hk, Dk = _kda(cfg)
    one = batch * seq_len * Hk * 6 * Dk * Dk
    return {"fwd": one, "bwd": 2 * one}


def kda_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes that cross HBM once: q, k, v and o in the activations' dtype,
    the decay and beta in float32."""
    Hk, Dk = _kda(cfg)
    tokens = batch * seq_len
    read = tokens * Hk * (3 * Dk * itemsize + Dk * 4 + 4)
    out = tokens * Hk * Dk * itemsize
    return {"fwd": read + out, "bwd": 2 * read + out}


def mla_flops(batch: int, cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """One latent layer's attention kernels: scores and values forward; the
    scores again, three more products at the scores' width and two at the
    values' backward."""
    H, dqk, dv = _mla(cfg)
    pairs = 2 * batch * H * seq_len * (seq_len + 1) / 2
    return {"fwd": pairs * (dqk + dv), "bwd": pairs * (3 * dqk + 2 * dv)}


def mla_bytes(batch: int, cfg: Dict[str, Any], seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    H, dqk, dv = _mla(cfg)
    row = batch * seq_len * H * itemsize
    return {"fwd": row * (2 * dqk + 2 * dv), "bwd": row * (4 * dqk + 4 * dv)}
