"""Compile cache (``utils/compile_cache.py``, ``DDPTrainer._first_call``):
seconds of the step programs' first calls that were the program's own Python
and JAX's: the trace, the lowering with every Pallas kernel turned into
Mosaic text, the cache key's hash; total of the program's timing
``step.build.trace_lower`` (the span ``step.build`` less the backend seconds
inside it).  Paid on every start, warm or cold; a kernel PR moves it."""

from chipbench import program_registry

UNIT = "s"
LAYER = "compile cache"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    timing = program_registry._entry("timings", "step.build.trace_lower")
    return timing["total_s"] if timing else None
