"""Model step (``models/phi4_flash.py``): the gauge ``diffattn.band_waste``
the window layer sets when it is traced: the score-plane area its flash
kernels visit (whole tiles: ``flash_attention.visited_tiles`` times the tile)
over the area the 512-window's mask leaves (``sum_t min(t + 1, 512)``).  2.0
at 512-tiles: every query block visits its own tile and the one before, and
half of each is masked."""

from chipbench import program_registry

UNIT = "ratio"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    if "sambay_lm" not in facts:
        return None
    return program_registry.gauge("diffattn.band_waste")
