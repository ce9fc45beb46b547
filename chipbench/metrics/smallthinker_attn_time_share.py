"""Pallas kernels (``ops/flash_attention.py`` at head 128, 28 query heads on 4
K/V heads, a group of seven, T = 8,192: the window-4,096 band on three layers
and the triangle on the fourth of ``models/smallthinker.py``): summed device
time of the three attention kernels, by name, over the traced window.  Left
out: the projections and the rotation around them."""

from chipbench import trace_reduce

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "smallthinker_kernel_s" not in trace:
        return None
    spent = sum(trace["smallthinker_kernel_s"][k] for k in trace_reduce.FLASH_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
