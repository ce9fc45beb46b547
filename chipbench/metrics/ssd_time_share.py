"""Pallas kernels (``ops/ssd.py``): summed device time of the state-space
scan's two kernels, forward and backward (the forward again where a block is
recomputed), over the traced window.  Left out: the projections, the
convolution, the gate and the norm around them, which XLA runs."""


from chipbench import arithmetic_ssm_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "ssm_kernel_s" not in trace:
        return None
    spent = sum(trace["ssm_kernel_s"][k] for k in arithmetic_ssm_lm.SSD_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
