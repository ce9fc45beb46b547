"""Pallas kernels (``ops/ssd.py``): the least time the chip could take for the
recurrence the traced steps needed (``chipbench/arithmetic_ssm_lm``: 6 P N
FLOPs a token a head forward and twice that backward; x, the step sizes, B, C
and y across HBM once forward, read again with dy and four gradients written
backward; by the table of peaks: bytes bind forward, and backward the two
bounds meet within 0.2%) over the time the two kernels took.  The chunked
form's own surplus (the products inside a chunk, the backward kernel's
recomputation, a forward run again where a block is rematerialised) is not
required work and reads as distance from 100%."""

from chipbench import arithmetic, arithmetic_ssm_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_ssm_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    flops = arithmetic_ssm_lm.ssd_flops(batch, cfg, seq_len)
    nbytes = arithmetic_ssm_lm.ssd_bytes(batch, cfg, seq_len)
    one = sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    return one * arithmetic_ssm_lm.layer_kinds(cfg).count("mamba") * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "ssm_kernel_s" not in trace:
        return None
    spent = sum(trace["ssm_kernel_s"][k] for k in arithmetic_ssm_lm.SSD_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
