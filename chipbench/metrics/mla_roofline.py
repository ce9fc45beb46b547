"""Pallas kernels (``ops/flash_attention.py`` at a score width of 192 over
values of 128): the least time the chip could take for the latent layer's
attention in the traced steps (the causal triangle, two products forward and
five backward at their own widths, q, K, V read once;
``chipbench/arithmetic_hybrid_lm``, by the table of peaks) over the time the
three kernels took.  The diagonal tiles' masked half and the recomputed
scores are not required work."""

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
    flops = arithmetic_hybrid_lm.mla_flops(batch, cfg, seq_len)
    nbytes = arithmetic_hybrid_lm.mla_bytes(batch, cfg, seq_len)
    one = sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    return one * layer_kinds(cfg).count("mla") * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "hybrid_kernel_s" not in trace:
        return None
    spent = sum(trace["hybrid_kernel_s"][k] for k in trace_hybrid_lm.MLA_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
