"""Input pipeline (``adapcc_tpu/data.py``): host time a step waits in
``next(batches)``, mean per step, from the benchmark's span around it."""

UNIT = "ms"
LAYER = "input pipeline"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(facts):
    spans = facts["spans"].get("input_wait")
    return 1e3 * sum(spans) / len(spans) if spans else None
