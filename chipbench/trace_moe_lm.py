"""What the ``train_moe_lm`` cells take from a device trace beyond
``trace_reduce.reduce_trace``: the time of the routed experts' part of the
expert layer (``adapcc_tpu/models/moe.routed_experts``).

An ``XLA Ops`` event's name is the whole HLO instruction and carries no scope
on this runtime (PERF.md §6, PR 24), so the operations are told by what only
they touch:

- the grouped products: XLA's own Mosaic kernels for ``ragged_dot``, named
  ``%ragged-dot…`` (the product and its metadata call), forward and backward;
- the sort of the assignments, the gathers out to the sorted rows and back,
  and the elementwise work on the rows: every operation that produces or
  reads an array with the assignment bound (``tokens x min(top_k, held)``) as
  a dimension, or ``[tokens, top_k, ...]``, or the stacked ``[held, ...]``
  expert weights of the layer's widths.

The optimizer's update of the stacked weights is the optimizer's, and is
told apart by reading no bound-sized array and producing float32
``[held, …]`` from float32 (it is left out here).
"""

from __future__ import annotations

import re
from typing import Any, Dict

from chipbench import trace_reduce


def expert_patterns(config: Dict[str, Any], tokens: int):
    held, k = int(config["num_experts_held"]), int(config["num_experts_per_tok"])
    bound = tokens * min(k, held)
    rows = re.compile(rf"\[(?:{bound},|{tokens},{k}[,\]]|{tokens * k}[,\]])")
    return re.compile(r"%ragged-dot"), rows


def part_of(name: str, kernel, rows):
    """Which part of the routed experts an operation is, or None."""
    return "grouped_products" if kernel.search(name) else "rows" if rows.search(name) else None


def is_expert_op(name: str, kernel, rows) -> bool:
    return part_of(name, kernel, rows) is not None


def expert_seconds(trace: Dict[str, Any], config: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """``{"grouped_products", "rows"}`` in seconds over the traced window,
    mean over the chips that ran something."""
    kernel, rows = expert_patterns(config, tokens)
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    out = {"grouped_products": 0.0, "rows": 0.0}
    parts: Dict[str, Any] = {}
    for evs in ops.values():
        for name, _, dur in evs:
            if name not in parts:
                parts[name] = part_of(name, kernel, rows)
            if parts[name]:
                out[parts[name]] += dur / 1e9 / len(ops)
    return out
