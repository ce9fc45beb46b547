"""Input pipeline (``adapcc_tpu/data.py``): the producer thread's work on
one batch (materialising the host rows, ``jax.device_put``), mean per
batch, from the program's span ``data.h2d``."""

from chipbench import program_registry

UNIT = "ms"
LAYER = "input pipeline"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(facts):
    return program_registry.span_mean_ms("data.h2d")
