"""Input pipeline (``adapcc_tpu/data.py``): batches ready in the prefetch
queue when the consumer asks for one, mean over pulls, from the program's
sample ``data.queue_depth`` (the mix's ``prefetch`` is the most it can
read; near 0 the feed is about to be in the way)."""

from chipbench import program_registry

UNIT = "count"
LAYER = "input pipeline"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    return program_registry.sample_mean("data.queue_depth")
