"""Model step (``models/granite_hybrid.py`` under XLA): tokens per second
times the FLOPs a token requires (``chipbench/arithmetic_ssm_lm``: six a
matrix parameter the token meets, the tied head's product among them, the
Mamba-2 recurrence a step at a time at ``6 P N`` a head, the attention layer's
causal triangle; backward at twice forward, nothing recomputed counts, so a
rematerialised step reads lower) over chips times the chip's published bf16
peak.  Left out: the convolution, norms, gates and scalings, which are no
products."""

from chipbench import arithmetic, arithmetic_ssm_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    if facts["platform"] != "tpu" or "ssm_lm" not in facts:
        return None  # a utilization of a chip comes from a chip run only
    need = arithmetic_ssm_lm.train_flops_per_token(facts["config"], arithmetic_ssm_lm.row_tokens(facts["mix"]))
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
