"""Trainer (``ddp/trainer.py``, ``ddp/hook.py``): the trainer's own Python
around the compiled call (state check, program lookup, calibration, mask
negotiation, argument assembly before it; bank unpacking and bookkeeping
after it), mean per step, from the program's spans ``step.prepare`` and
``step.finish``."""

from chipbench import program_registry

UNIT = "ms"
LAYER = "trainer"
MOVES = "train_step_p95_ms"
SOURCE = "program_span"


def read(facts):
    parts = [program_registry.span_mean_ms(n) for n in ("step.prepare", "step.finish")]
    return None if None in parts else sum(parts)
