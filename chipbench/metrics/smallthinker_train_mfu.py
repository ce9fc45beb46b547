"""Model step (``models/smallthinker.py`` under XLA): tokens per second times
the FLOPs a token requires (``chipbench/arithmetic_smallthinker_lm``: six a
matrix parameter the token meets: the router, the four attention projections,
the held experts' by the assignments the window's steps really computed; the
untied head's product on ``T - 1`` places; the attention products by the
mask's area, the band on the three windowed layers and the triangle on the
global one; backward at twice forward, nothing recomputed counts, so a
rematerialised step reads lower) over chips times the chip's published bf16
peak: the share of the whole step.  Left out: norms, the rotation, the router's
top-k and its softmax over the chosen, which are no matrix products."""

from chipbench import arithmetic, arithmetic_smallthinker_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    counted = facts.get("smallthinker_lm")
    if facts["platform"] != "tpu" or not counted:
        return None  # a utilization of a chip comes from a chip run only
    mix = facts["mix"]
    seq_len = arithmetic_smallthinker_lm.row_tokens(mix)
    tokens = int(mix["batch_per_chip"]) * facts["world"] * seq_len
    need = arithmetic_smallthinker_lm.train_flops_per_token(
        facts["config"], seq_len, counted["assignments_per_layer_step"] / tokens
    )
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
