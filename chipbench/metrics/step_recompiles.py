"""Compile cache (``utils/compile_cache.py``): calls of ``DDPTrainer.step``
that were no program's first and in which JAX traced, lowered or compiled
all the same, set-up's steps and the window's; the program's counter
``step.recompiles``.  Has to read 0: the program's twin of
``window_compiles``, which is the benchmark's own listener."""

from chipbench import program_registry

UNIT = "count"
LAYER = "compile cache"
MOVES = "train_step_p95_ms"
SOURCE = "program_counter"


def read(facts):
    return program_registry._entry("counters", "step.recompiles")
