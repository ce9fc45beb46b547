"""Pallas kernels (``ops/selective_scan.py``): summed device time of the
selective scan's two kernels, ``sscan_fwd`` and ``sscan_bwd`` by name (the
forward again where a block is recomputed), over the traced window.  Left
out: the projections, the convolution, the softplus and the gate around them,
which XLA runs."""

from chipbench import arithmetic_sambay_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "sambay_kernel_s" not in trace:
        return None
    spent = sum(trace["sambay_kernel_s"][k] for k in arithmetic_sambay_lm.SSCAN_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
