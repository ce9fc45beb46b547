"""Compile cache (``utils/compile_cache.py``, JAX's persistent cache):
backend compiles, cache reads included, inside the measured window, counted
from JAX's monitoring events.  Has to read 0."""

UNIT = "count"
LAYER = "compile cache"
MOVES = "train_step_p95_ms"
SOURCE = "program_counter"


def read(facts):
    return float(facts["window_compiles"])
