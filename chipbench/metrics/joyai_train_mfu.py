"""Model step (``models/joyai_flash.py`` under XLA): tokens per second times
the FLOPs a token requires (``chipbench/arithmetic_mla_lm``: six a matrix
parameter the token meets, the six latent blocks' causal triangle at 192 /
128, the routed experts by the assignments the window's steps really
computed, and the multi-token-prediction module's merge, block and second
head product, which the objective requires; backward at twice forward,
nothing recomputed counts) over chips times the chip's published bf16 peak.
Left out: norms, the rotation and the router's top-k, which are no products."""

from chipbench import arithmetic, arithmetic_mla_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    counted = facts.get("mla_lm")
    if facts["platform"] != "tpu" or not counted:
        return None  # a utilization of a chip comes from a chip run only
    mix = facts["mix"]
    seq_len = arithmetic_mla_lm.row_tokens(mix)
    tokens = int(mix["batch_per_chip"]) * facts["world"] * seq_len
    need = arithmetic_mla_lm.train_flops_per_token(
        facts["config"], seq_len, counted["assignments_per_layer_step"] / tokens
    )
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
