"""Pallas kernels (``ops/flash_attention.py`` with a window and grouped KV
heads): summed device time of the three flash kernels over the traced
window."""

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    spent = sum(trace["kernel_s"].values())
    return 100.0 * spent / trace["window_s"] if spent else None
