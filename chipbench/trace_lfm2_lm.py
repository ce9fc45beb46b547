"""What the ``train_lfm2_lm`` cells take from a device trace beyond
``trace_reduce.reduce_trace``: the time of the expert layers' operations
(``adapcc_tpu/models/moe.routed_experts``), **each event counted once**.

The operations are told as ``chipbench/trace_moe_lm`` tells them (XLA's
``ragged-dot`` kernels; whatever produces or reads an array with the
assignment bound, ``[tokens, top_k, ...]`` or the flattened assignments as a
dimension).  An ``XLA Ops`` line nests: a ``conditional`` (the layer's choice
between its short rows and the bound) is an event that spans its children's,
and both match.  Here only the leaves count: an event that another event of
the same chip starts inside is left out, so the sum can never pass the window.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List

from chipbench import trace_moe_lm, trace_reduce


def leaves(events: List[List[Any]]) -> List[List[Any]]:
    """The events no other event starts inside (``[name, start, duration]``)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    return [
        e for e, nxt in zip(ordered, ordered[1:] + [None])
        if nxt is None or nxt[1] >= e[1] + e[2]
    ]


def expert_seconds(trace: Dict[str, Any], config: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """``{"grouped_products", "rows"}`` in seconds over the traced window,
    leaf events only, mean over the chips that ran something."""
    kernel, rows = trace_moe_lm.expert_patterns(config, tokens)
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    out = {"grouped_products": 0.0, "rows": 0.0}
    parts: Dict[str, Any] = {}
    for evs in ops.values():
        for name, _, dur in leaves(evs):
            if name not in parts:
                parts[name] = trace_moe_lm.part_of(name, kernel, rows)
            if parts[name]:
                out[parts[name]] += dur / 1e9 / len(ops)
    return out


def label(name: str) -> str:
    """A line's name in the runner's list of where the time goes: XLA's
    grouped products under their own, else ``trace_reduce.stable_name``."""
    op = trace_reduce.parse_op(name)
    if op["name"].startswith("ragged-dot"):     # by signature they would pass for a flash kernel
        return f"ragged-dot {re.sub(r'{[^}]*}', '', op['type'])}"[:80]
    return trace_reduce.stable_name(name)
