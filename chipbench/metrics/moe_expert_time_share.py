"""Expert layer (``models/moe.routed_experts``): device time of the routed
experts' grouped products (XLA's ``ragged-dot`` kernels) and of the sort,
gathers and elementwise work on the sorted rows (told by the bound-sized
arrays they touch: ``chipbench/trace_moe_lm``) over the traced window."""

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "expert_s" not in trace:
        return None
    spent = sum(trace["expert_s"].values())
    return 100.0 * spent / trace["window_s"] if spent else None
