"""Pallas kernels (``ops/flash_attention.py``): the least time the chip
could take for the attention the traced steps needed (causal FLOPs forward
and backward against q/k/v/o/do/dq/dk/dv bytes, ``chipbench/arithmetic``,
by the table of peaks) over the time the three kernels took.  At GPT-2's
head size of 64 and T=1,024 the FLOPs bind, not the bytes."""

from chipbench import arithmetic

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    shape = (
        int(mix["batch_per_chip"]), int(cfg["n_head"]), int(mix["seq_len"]),
        int(cfg["d_model"]) // int(cfg["n_head"]),
    )
    peaks = arithmetic.peaks_for(facts["device_kind"])
    flops, nbytes = arithmetic.flash_flops(*shape), arithmetic.flash_bytes(*shape)
    return sum(
        arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd")
    ) * int(cfg["n_layer"]) * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None:
        return None
    spent = sum(trace["kernel_s"].values())
    return 100.0 * least_seconds(facts) / spent if spent else None
