"""Model step (``models/phi4_flash.py`` under XLA): tokens per second times
the FLOPs a token requires (``chipbench/arithmetic_sambay_lm``: six a matrix
parameter the token meets, the tied head's product on ``T - 1`` places among
them, a pair's two score maps and two products against the doubled value over
the band or the triangle, the Mamba-1 recurrence a step at a time at ``6 d_in
N``; backward at twice forward, nothing recomputed counts, so a
rematerialised step reads lower) over chips times the chip's published bf16
peak: the share of the whole step.  Left out: the convolution, norms, gates,
``lambda`` and the pair's norm, which are no products."""

from chipbench import arithmetic, arithmetic_sambay_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    if facts["platform"] != "tpu" or "sambay_lm" not in facts:
        return None  # a utilization of a chip comes from a chip run only
    need = arithmetic_sambay_lm.train_flops_per_token(facts["config"], arithmetic_sambay_lm.row_tokens(facts["mix"]))
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
