"""Operations and bytes the work requires, computed from its shapes, and the
table of peaks they are held against.  Kept with the benchmark: a PR that
claims a gain cannot change how the gain is counted.

``train_flops_per_token`` is ``bench.py``'s, copied, with one change: the
attention products count at half, because the mask is causal and a kernel
that skips the masked blocks does not need them.  Recomputed operations do
not count.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: {sorted(table)}")
    return table[device_kind]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Matmul and attention FLOPs per trained token: forward, and twice
    that for the backward pass."""
    d, L, V = int(cfg["d_model"]), int(cfg["n_layer"]), int(cfg["vocab_size"])
    per_layer = (
        2 * d * 3 * d          # qkv projection
        + 2 * d * d            # output projection
        + 2 * 2 * d * 4 * d    # mlp up and down
        + 2 * 2 * seq_len * d // 2   # scores and values, causal: half of T x T
    )
    return 3.0 * (L * per_layer + 2 * d * V)   # + the tied output head


def flash_flops(batch: int, n_head: int, seq_len: int, head_dim: int) -> Dict[str, float]:
    """FLOPs one causal attention call needs, forward and backward.  The
    forward pass has two products (scores, values) over the lower triangle;
    the backward pass five: the scores again, dV, dP, dQ and dK."""
    tri = batch * n_head * seq_len * seq_len / 2
    one = 2 * tri * head_dim
    return {"fwd": 2 * one, "bwd": 5 * one}


def flash_bytes(batch: int, n_head: int, seq_len: int, head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """Bytes that have to cross HBM once: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = batch * n_head * seq_len * head_dim * itemsize
    return {"fwd": 4 * tensor, "bwd": 8 * tensor}


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    compute = flops / (peaks["bf16_tflops"] * 1e12)
    memory = nbytes / (peaks["hbm_gbps"] * 1e9)
    return {"seconds": max(compute, memory), "bound": "compute" if compute >= memory else "memory"}
