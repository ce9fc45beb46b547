"""Pallas kernels (``ops/flash_attention.py`` at head 64, 32 query heads on 8
K/V heads, T = 8,192, the one attention layer of ``models/lfm2_moe.py``):
summed device time of the three attention kernels, by name, over the traced
window.  Left out: the projections, the q/k norms and the rotation around them."""

from chipbench import trace_reduce

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "lfm2_kernel_s" not in trace:
        return None
    spent = sum(trace["lfm2_kernel_s"][k] for k in trace_reduce.FLASH_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
