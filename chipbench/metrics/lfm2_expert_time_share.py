"""Expert layer (``models/moe.routed_experts`` at 16 of 64 experts of 1,536,
top-4): device time of the four expert layers' grouped products (XLA's
``ragged-dot`` kernels) and of the sort, gathers and elementwise work on the
sorted rows, **each event counted once** (``chipbench/trace_lfm2_lm``: leaf
events only, so a ``conditional`` and its children are not both summed, as
``moe_expert_time_share`` sums them), over the traced window.  **A lower
bound**: beside ``ragged-dot`` the rows' work is told by arrays of the bound's
32,768 rows only, and every layer-step of the cell runs on the 16,384 short
rows, which is the vocabulary's size too: the sort, gathers and elementwise
passes over the short rows cannot be told from the head's by shape and are
not counted."""

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "lfm2_expert_s" not in trace:
        return None
    spent = sum(trace["lfm2_expert_s"].values())
    return 100.0 * spent / trace["window_s"] if spent else None
