"""Pallas kernels (``ops/selective_scan.py``): the least time the chip could
take for the recurrences the traced steps needed (``chipbench/
arithmetic_sambay_lm``: ``6 d_in N`` FLOPs a token forward and twice that
backward; x, the step sizes, B and C read and y written once forward at the
activations' two bytes, read again with dy and four gradients written
backward; by the table of peaks: bytes bind) over the time the two kernels
took.  **It reads low by construction**: the recurrence is elementwise work
for the VPU and the table of peaks has no VPU number, so the bound is the time
to move the arrays, which no walk of 8,192 dependent steps reaches; it can
never read over 100.  It is the distance to memory speed.  A forward run
again where a block is rematerialised, the float32 step sizes and the saved
states are not required work."""

from chipbench import arithmetic, arithmetic_sambay_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_sambay_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    flops = arithmetic_sambay_lm.sscan_flops(batch, cfg, seq_len)
    nbytes = arithmetic_sambay_lm.sscan_bytes(batch, cfg, seq_len)
    one = sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    kinds = arithmetic_sambay_lm.layer_kinds(cfg)
    return one * (kinds.count("M") + kinds.count("M*")) * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "sambay_kernel_s" not in trace:
        return None
    spent = sum(trace["sambay_kernel_s"][k] for k in arithmetic_sambay_lm.SSCAN_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
