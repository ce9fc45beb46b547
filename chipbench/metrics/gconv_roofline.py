"""Pallas kernels (``ops/short_conv.gated_short_conv``): the least time the
chip could take for the gated convolutions the traced steps needed
(``chipbench/arithmetic_lfm2_lm``: ``B``, ``C``, ``x`` read and ``y`` written
once forward at the activations' two bytes, ``B``, ``C``, ``x``, ``dy`` read
and three gradients written backward; ``2 + 2 K`` FLOPs a channel and token
forward and ``4 + 4 K`` backward; by the table of peaks: bytes bind, 0.16 ms a
layer forward and 0.29 backward) over the time the two kernels took.  **It is
the distance to memory speed**: the work is elementwise, for the VPU, and the
table of peaks has no VPU number; the accepted convolution kernels run at
67-73% of the HBM's speed with the VPU binding (PERF.md section 7 item 28).
The share is of the table's HBM number (819 GB/s), not of what the memory
can be made to give: a three-array form of the backward, not in the tree,
was measured at 929 GB/s, and its share alone would read about 113 (PERF.md
section 7 item 34).  The kernels that run read 81; no ``min`` caps the reading, so a
kernel that passes the table's number shows as over 100 and the table, not
the reader, is then what has to be mended.  A forward run again where a block
is rematerialised is not required work."""

from chipbench import arithmetic, arithmetic_lfm2_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_lfm2_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    flops = arithmetic_lfm2_lm.gconv_flops(batch, cfg, seq_len)
    nbytes = arithmetic_lfm2_lm.gconv_bytes(batch, cfg, seq_len)
    one = sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    convs = sum(kind == "conv" for kind, _ in arithmetic_lfm2_lm.layer_plan(cfg))
    return one * convs * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "lfm2_kernel_s" not in trace:
        return None
    spent = sum(trace["lfm2_kernel_s"][k] for k in arithmetic_lfm2_lm.GCONV_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
