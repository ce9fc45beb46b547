"""Pallas kernels (``ops/flash_attention.py`` at head size 64, four query
heads to a K/V head, in one layer in ten of ``models/granite_hybrid.py``):
summed device time of the three attention kernels, by name, over the traced
window.  Left out: the four projections around them."""

from chipbench import trace_reduce

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "ssm_kernel_s" not in trace:
        return None
    spent = sum(trace["ssm_kernel_s"][k] for k in trace_reduce.FLASH_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
