"""Compile cache (``utils/compile_cache.py``, ``DDPTrainer._first_call``):
backend seconds inside the step programs' first calls, as JAX's monitoring
reports them: XLA's compile where the persistent cache lacks the program,
the cache's read where it holds it; total of the program's timing
``step.build.load``.  ``step_cache_misses`` beside it says which it was."""

from chipbench import program_registry

UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    timing = program_registry._entry("timings", "step.build.load")
    return timing["total_s"] if timing else None
