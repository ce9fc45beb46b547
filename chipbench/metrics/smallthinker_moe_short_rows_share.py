"""Expert layer (``models/moe.routed_experts`` at 16 of 64 experts of 768,
top-6): share of the traced window's layer-steps whose assignments fit the
layer's short rows (24,576 against the bound's 49,152; 12,288 on balance), so
that the layer ran over them: mean of the program's sample ``moe.rows_fit``
times 100.  A cell of another runner kind has its own reader; this one reads
nothing without the ``train_smallthinker_lm`` runner's facts."""

from chipbench import program_registry

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    if not facts.get("smallthinker_lm"):
        return None
    fit = program_registry.sample_mean("moe.rows_fit")
    return None if fit is None else 100.0 * fit
