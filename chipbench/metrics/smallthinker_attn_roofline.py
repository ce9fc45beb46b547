"""Pallas kernels (``ops/flash_attention.py`` with a window and a group of
seven query heads to a K/V head): the least time the chip could take for the
attention the traced steps needed (the window-4,096 band on the three windowed
layers, the triangle on the global one: 25.17 M and 33.56 M query-key pairs a
row of 8,192; two products forward and five backward at 28 heads of 128; K and
V bytes at the 4 K/V heads' width; ``chipbench/arithmetic_smallthinker_lm``, by
the table of peaks: the MXU binds on every layer) over the time the three
kernels took.  No new kernel came with this model, so this is its one
roofline."""

from chipbench import arithmetic, arithmetic_smallthinker_lm

UNIT = "%"
LAYER = "Pallas kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def least_seconds(facts):
    cfg, mix = facts["config"], facts["mix"]
    batch, seq_len = int(mix["batch_per_chip"]), arithmetic_smallthinker_lm.row_tokens(mix)
    peaks = arithmetic.peaks_for(facts["device_kind"])
    nbytes = arithmetic_smallthinker_lm.attention_bytes(batch, cfg, seq_len)
    total = 0.0
    for _, windowed in arithmetic_smallthinker_lm.layer_plan(cfg):
        flops = arithmetic_smallthinker_lm.attention_flops(batch, cfg, seq_len, windowed)
        total += sum(arithmetic.roofline_seconds(flops[p], nbytes[p], peaks)["seconds"] for p in ("fwd", "bwd"))
    return total * facts["steps"]


def read(facts):
    trace = facts["trace"]
    if trace is None or "smallthinker_kernel_s" not in trace:
        return None
    spent = sum(trace["smallthinker_kernel_s"].values())
    return 100.0 * least_seconds(facts) / spent if spent else None
