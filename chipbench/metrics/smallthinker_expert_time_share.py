"""Expert layer (``models/moe.routed_experts`` at 16 of 64 ReGLU experts of
768, top-6): device time of the four layers' grouped products (XLA's
``ragged-dot`` kernels and what reads them), of the sort, gathers and
elementwise work on the bound-sized rows, and of every other operation that
runs inside the layers' ``conditional``s (the stacked weights' casts and
copies, the passes over the short rows, which no shape tells: they are told by
running inside an event that holds a grouped product;
``chipbench/trace_smallthinker_lm``), **each event counted once** (leaf events
only: a ``conditional`` and its children are not both summed), over the traced
window.  The routing made ahead of the attention is not in it: it has
``smallthinker_route_time_share``."""

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "smallthinker_expert_s" not in trace:
        return None
    spent = sum(trace["smallthinker_expert_s"].values())
    return 100.0 * spent / trace["window_s"] if spent else None
