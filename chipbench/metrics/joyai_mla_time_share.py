"""Pallas kernels (``ops/flash_attention.py`` at a score width of 192 over
values of 128, in every block of ``models/joyai_flash.py``): summed device
time of the three attention kernels, by name, over the traced window.  Left
out: the projections down to and up from the latents, the norms between
them and the rotation."""

from chipbench import trace_hybrid_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or "mla_kernel_s" not in trace:
        return None
    spent = sum(trace["mla_kernel_s"][k] for k in trace_hybrid_lm.MLA_KERNELS)
    return 100.0 * spent / trace["window_s"] if spent else None
