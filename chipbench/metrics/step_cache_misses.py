"""Compile cache (``utils/compile_cache.py``, ``DDPTrainer._first_call``):
step programs whose first call held no hit of the persistent cache, so that
XLA compiled them; the program's counter ``step.build.cache_misses``.  0
says the ``setup_s`` beside it is a warm one."""

from chipbench import program_registry

UNIT = "count"
LAYER = "compile cache"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(facts):
    return program_registry._entry("counters", "step.build.cache_misses")
