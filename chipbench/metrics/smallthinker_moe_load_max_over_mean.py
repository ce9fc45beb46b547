"""Expert layer (``models/moe.py`` at 16 of 64 experts, top-6, a routing made
from the layer's un-normed input): assignments of the fullest held expert over
the mean of the held experts, mean over the traced window's steps and layers,
from the program's sample ``moe.load_max_over_mean`` (1.0 is a balanced
router; the grouped product's longest run sets the layer's tail)."""

from chipbench import program_registry

UNIT = "ratio"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    if not facts.get("smallthinker_lm"):
        return None
    return program_registry.sample_mean("moe.load_max_over_mean")
