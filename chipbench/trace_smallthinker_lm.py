"""What the ``train_smallthinker_lm`` cells take from a device trace beyond
``trace_reduce.reduce_trace`` and the flash kernels' seconds
(``trace_hybrid_lm.kernel_seconds``, by name): the time of the expert layers'
operations (``adapcc_tpu/models/moe.routed_experts``) and, apart from them, the
time of the routing made ahead of the attention
(``models/smallthinker.EarlyRouter``, the scope ``moe_route``), **each event
counted once**.

An ``XLA Ops`` event's name is the whole HLO instruction with its operands'
types and carries no scope on this runtime (no ``op_name``: my chip run, PR
49, 2,679 names of a traced step, none with metadata), so the operations are
told by what only they touch, as ``chipbench/trace_moe_lm`` tells the
experts' (XLA's ``ragged-dot`` kernels; an array with the assignment bound,
``[tokens, top_k, ...]`` or the flattened assignments as a dimension, produced
or read).  The routing's: an array ``[tokens, experts]`` or its transpose
produced or read: the float32 logits' product, the top-k's ``iota`` and
``sort`` over all 64 and the slices of its result, and in the backward pass
the chosen logits' cotangent (XLA hands it on as ``[experts, tokens]``), the
router's weight gradient and the product back to the stream.  Nothing else in
the step has that shape.  The routing is asked first.  **An upper bound by
one line**: the product back to the stream is one fusion with the stream's own
gradient sum behind it and counts whole (0.32 ms a layer, 1.13 in the first,
of the 2.7 ms a step read).
The softmax over the chosen six works on ``[tokens, top_k]`` alone, which is
the shape of the expert layer's weights and slots too: it counts with the
experts' rows (0.2 MB an array; under 0.01 ms a layer).  An ``XLA Ops`` line
nests (a ``conditional`` spans its children's events), so only the leaves
count (``trace_lfm2_lm.leaves``) and no sum can pass the window.  **What the
shapes do not tell inside an expert layer's ``conditional``** (the stacked
weights' casts and copies, the gathers and elementwise passes on the short
rows, 24,576 here: about a third of the layer's time) is told by where it
runs: a leaf inside an event that holds a grouped product is the expert rows'.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from chipbench import trace_hybrid_lm, trace_lfm2_lm, trace_moe_lm, trace_reduce

PARTS = ("route", "grouped_products", "rows")


def patterns(config: Dict[str, Any], tokens: int):
    """``(the routing's shapes, grouped products' kernel, the expert rows' shapes)``."""
    stated = {"num_experts_held": config["num_experts_held"], "num_experts_per_tok": config["moe_num_active_primary_experts"]}
    experts = int(config["moe_num_primary_experts"])
    routing = re.compile(rf"\[(?:{tokens},{experts}|{experts},{tokens})[,\]]")
    return (routing, *trace_moe_lm.expert_patterns(stated, tokens))


def part_of(name: str, routing, kernel, rows) -> Optional[str]:
    """``route``, ``grouped_products``, ``rows`` or None, by the arrays the instruction produces or reads."""
    return "route" if routing.search(name) else trace_moe_lm.part_of(name, kernel, rows)


def _expert_spans(evs: List[List[Any]], leaves: List[List[Any]], shapes) -> List[Tuple[int, int]]:
    """The merged intervals of the events that hold a grouped product: the
    expert layers' ``conditional``s, forward and backward."""
    held = sorted(e[1] for e in leaves if part_of(e[0], *shapes) == "grouped_products")
    leaf_ids = {id(e) for e in leaves}
    spans = []
    for e in evs:
        if id(e) not in leaf_ids:
            at = bisect.bisect_left(held, e[1])
            if at < len(held) and held[at] < e[1] + e[2]:
                spans.append((e[1], e[1] + e[2]))
    return trace_reduce.union(spans)


def part_seconds(trace: Dict[str, Any], config: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """Seconds of each of :data:`PARTS` over the traced window, leaf events
    only, mean over the chips that ran something.  A leaf that no shape tells
    and that runs inside an event holding a grouped product is the expert
    rows' too."""
    shapes = patterns(config, tokens)
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    out = {part: 0.0 for part in PARTS}
    for evs in ops.values():
        leaves = trace_lfm2_lm.leaves(evs)
        spans = _expert_spans(evs, leaves, shapes)
        starts = [s for s, _ in spans]
        for name, start, dur in leaves:
            part = part_of(name, *shapes)
            if part is None and spans:
                at = bisect.bisect_right(starts, start) - 1
                part = "rows" if at >= 0 and start < spans[at][1] else None
            if part:
                out[part] += dur / 1e9 / len(ops)
    return out


def labeller(config: Dict[str, Any], tokens: int) -> Callable[[str], str]:
    """A line's name in the runner's list of where the time goes: a flash
    kernel's own, the routing's and the expert rows' operations under their
    part, else ``trace_lfm2_lm.label``."""
    shapes = patterns(config, tokens)

    def label(name: str) -> str:
        kernel = trace_hybrid_lm.kernel_of(name)
        if kernel:
            return kernel
        part = part_of(name, *shapes)
        plain = trace_lfm2_lm.label(name)
        return f"{part}: {plain}"[:80] if part in ("route", "rows") else plain

    return label
