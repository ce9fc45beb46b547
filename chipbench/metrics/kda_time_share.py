"""Pallas kernels (``ops/kda.py``): summed device time of the KDA scan's two
kernels, forward and backward, over the traced window.  Left out: the
projections, the short convolutions, the gates and the output norm around
them, which XLA runs."""

from chipbench import trace_hybrid_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "hybrid_kernel_s" not in trace:
        return None
    spent = sum(trace["hybrid_kernel_s"][k] for k in trace_hybrid_lm.KDA_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
