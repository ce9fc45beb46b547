"""Model step (``models/gpt2.py`` under XLA): tokens per second times the
FLOPs a token requires (``chipbench/arithmetic.train_flops_per_token``:
causal attention at half, backward at twice forward, nothing recomputed
counts) over chips times the chip's published bf16 peak."""

from chipbench import arithmetic

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    if facts["platform"] != "tpu":
        return None  # a utilization of a chip comes from a chip run only
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    need = arithmetic.train_flops_per_token(facts["config"], int(facts["mix"]["seq_len"]))
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
