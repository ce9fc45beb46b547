"""Model step (``models/trinity.py`` under XLA): tokens per second times the
FLOPs a token requires (``chipbench/arithmetic_moe_lm``: band and triangle
attention, the routed experts by the assignments the window's steps really
computed, backward at twice forward, nothing recomputed counts) over chips
times the chip's published bf16 peak."""

from chipbench import arithmetic, arithmetic_moe_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    moe = facts.get("moe")
    if facts["platform"] != "tpu" or not moe:
        return None  # a utilization of a chip comes from a chip run only
    mix = facts["mix"]
    tokens = int(mix["batch_per_chip"]) * facts["world"] * arithmetic_moe_lm.row_tokens(mix)
    need = arithmetic_moe_lm.train_flops_per_token(
        facts["config"], arithmetic_moe_lm.row_tokens(mix), moe["assignments_per_layer_step"] / tokens
    )
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
