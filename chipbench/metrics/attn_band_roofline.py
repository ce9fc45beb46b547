"""Pallas kernels (``ops/flash_attention.py`` with a window and grouped KV
heads): the least time the chip could take for the attention the traced
steps needed (the band on sliding layers, the triangle on full ones, K/V
bytes at the KV heads' width; ``chipbench/arithmetic_moe_lm``, by the table
of peaks) over the time the three kernels took."""

from chipbench import arithmetic, arithmetic_moe_lm
from chipbench.weights_moe_lm import layer_kinds

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_moe_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    nbytes = arithmetic_moe_lm.attention_bytes(batch, cfg, seq_len)
    total = 0.0
    for kind in layer_kinds(cfg):
        flops = arithmetic_moe_lm.attention_flops(batch, cfg, seq_len, kind)
        total += sum(
            arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd")
        )
    return total * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "num_experts_held" not in facts["config"]:
        return None
    spent = sum(trace["kernel_s"].values())
    return 100.0 * least_seconds(facts) / spent if spent else None
