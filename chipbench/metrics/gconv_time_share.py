"""Pallas kernels (``ops/short_conv.gated_short_conv``): summed device time of
the gated convolution's two kernels, ``gated_conv_fwd`` and ``gated_conv_bwd``
by name (the forward again where a block is recomputed), over the traced
window.  Left out: the two projections around them, which XLA runs."""

from chipbench import arithmetic_lfm2_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "lfm2_kernel_s" not in trace:
        return None
    spent = sum(trace["lfm2_kernel_s"][k] for k in arithmetic_lfm2_lm.GCONV_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
