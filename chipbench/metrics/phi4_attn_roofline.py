"""Pallas kernels (``ops/flash_attention.py`` at a score width of 64 over
values of 128): the least time the chip could take for the differential
products of every attention layer in the traced steps (a pair of heads: two
score maps at 64 and two products against 128 forward, each softmax's five
products backward at their own widths, over the band in the window layer and
the causal triangle in the full and the cross layer; q, K, V read once:
``chipbench/arithmetic_sambay_lm``, by the table of peaks; FLOPs bind, in the
band by half as much again as its bytes) over the time the three kernels took.  The
diagonal tiles' masked half, the band's visited-but-masked area
(``phi4_band_tile_waste``) and a forward run again under ``dots`` are not
required work."""

from chipbench import arithmetic, arithmetic_sambay_lm, trace_reduce

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_sambay_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    nbytes = arithmetic_sambay_lm.diff_attention_bytes(batch, cfg, seq_len)
    total = 0.0
    for kind in arithmetic_sambay_lm.layer_kinds(cfg):
        if kind in ("S", "F", "X"):
            flops = arithmetic_sambay_lm.diff_attention_flops(batch, cfg, seq_len, kind)
            total += sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    return total * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "sambay_kernel_s" not in trace:
        return None
    spent = sum(trace["sambay_kernel_s"][k] for k in trace_reduce.FLASH_KERNELS)
    return 100.0 * least_seconds(facts) / spent if spent else None
