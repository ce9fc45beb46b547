"""Pallas kernels (``ops/flash_attention.py`` at a score width of 64 over
values of 128, 20 query heads on 10 K/V heads, two calls a layer in the
window, the full and the cross layer of ``models/phi4_flash.py``): summed
device time of the three attention kernels, by name, over the traced window.
Left out: the projections, ``lambda`` and the pair's norm around them."""

from chipbench import trace_reduce

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "sambay_kernel_s" not in trace:
        return None
    spent = sum(trace["sambay_kernel_s"][k] for k in trace_reduce.FLASH_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
