"""Model step (``models/kimi_linear.py`` under XLA): tokens per second times
the FLOPs a token requires (``chipbench/arithmetic_hybrid_lm``: six a matrix
parameter the token meets, the routed experts by the assignments the window's
steps really computed, the latent layer's causal triangle, the KDA recurrence
a step at a time; backward at twice forward, nothing recomputed counts) over
chips times the chip's published bf16 peak.  Left out: the short
convolutions, norms, gates and the router's top-k, which are no products."""

from chipbench import arithmetic, arithmetic_hybrid_lm

UNIT = "%"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    hybrid = facts.get("hybrid")
    if facts["platform"] != "tpu" or not hybrid:
        return None  # a utilization of a chip comes from a chip run only
    mix = facts["mix"]
    seq_len = arithmetic_hybrid_lm.row_tokens(mix)
    tokens = int(mix["batch_per_chip"]) * facts["world"] * seq_len
    need = arithmetic_hybrid_lm.train_flops_per_token(
        facts["config"], seq_len, hybrid["assignments_per_layer_step"] / tokens
    )
    peak = arithmetic.peaks_for(facts["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * facts["tokens_per_s"] * need / (facts["world"] * peak)
