"""Expert layer (``models/smallthinker.EarlyRouter``, the scope ``moe_route``
ahead of the attention): device time of the routing's own operations, forward
and backward: the float32 logits at full precision (``[8192, 2560] x [2560,
64]``), the top-6 (an ``iota`` and a ``sort`` over all 64), the router's
gradient and the product back to the stream, told by the ``[tokens, 64]``
array or its transpose that they produce or read
(``chipbench/trace_smallthinker_lm``: an upper bound by the one fusion that
also sums the stream's gradient), each event counted once, over the traced
window.  No other cell prices its routing apart from its experts."""

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "smallthinker_route_s" not in trace:
        return None
    spent = sum(trace["smallthinker_route_s"].values())
    return 100.0 * spent / trace["window_s"] if spent else None
