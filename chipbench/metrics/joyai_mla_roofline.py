"""Pallas kernels (``ops/flash_attention.py`` at a score width of 192 over
values of 128): the least time the chip could take for the latent attention
of every block in the traced steps (the causal triangle, two products forward
and five backward at their own widths, q, K, V read once:
``chipbench/arithmetic_mla_lm``, by the table of peaks; FLOPs bind; the
multi-token-prediction module's block counted on the ``T - 1`` places it
exists on) over the time the three kernels took.  The diagonal tiles' masked
half and the recomputed scores are not required work."""

from chipbench import arithmetic, arithmetic_mla_lm, trace_hybrid_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_mla_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])

    def block(places):
        flops = arithmetic_mla_lm.mla_flops(batch, cfg, places)
        nbytes = arithmetic_mla_lm.mla_bytes(batch, cfg, places)
        return sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))

    one = int(cfg["num_hidden_layers"]) * block(seq_len) + int(cfg["num_nextn_predict_layers"]) * block(seq_len - 1)
    return one * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "mla_kernel_s" not in trace:
        return None
    spent = sum(trace["mla_kernel_s"][k] for k in trace_hybrid_lm.MLA_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
