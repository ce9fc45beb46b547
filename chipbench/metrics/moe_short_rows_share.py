"""Expert layer (``models/moe.routed_experts``): share of the traced window's
layer-steps whose assignments fit the layer's short rows, so that the layer
ran over them and not over the bound: mean of the program's sample
``moe.rows_fit`` (1.0 or 0.0 for each expert layer and step) times 100.  A
program that sizes its rows by the bound alone records no such sample, and
the line leaves the metric out."""

from chipbench import program_registry

UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    fit = program_registry.sample_mean("moe.rows_fit")
    return None if fit is None else 100.0 * fit
