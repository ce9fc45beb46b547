"""Pallas kernels (``ops/kda.py``): the least time the chip could take for the
recurrence the traced steps needed (``chipbench/arithmetic_hybrid_lm``: 6 d_k
d_v FLOPs a token a head forward and twice that backward, q, k, v, decay,
beta and o across HBM once; by the table of peaks) over the time the two
kernels took.  The chunked form's own surplus (the solve, the products inside
a chunk, the recomputation in the backward kernel) is not required work and
reads as distance from 100%."""

from chipbench import arithmetic, arithmetic_hybrid_lm, trace_hybrid_lm
from chipbench.weights_hybrid_lm import layer_kinds

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_hybrid_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    flops = arithmetic_hybrid_lm.kda_flops(batch, cfg, seq_len)
    nbytes = arithmetic_hybrid_lm.kda_bytes(batch, cfg, seq_len)
    one = sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    return one * layer_kinds(cfg).count("kda") * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "hybrid_kernel_s" not in trace:
        return None
    spent = sum(trace["hybrid_kernel_s"][k] for k in trace_hybrid_lm.KDA_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
