"""Trainer (``ddp/trainer.py``): host time inside the compiled call of
``DDPTrainer.step`` (pjit dispatch and the runtime's wait for output
buffers), mean per step, from the program's span ``step.enqueue``."""

from chipbench import program_registry

UNIT = "ms"
LAYER = "trainer"
MOVES = "train_step_p95_ms"
SOURCE = "program_span"


def read(facts):
    return program_registry.span_mean_ms("step.enqueue")
