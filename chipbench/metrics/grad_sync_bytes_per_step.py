"""Collective engine (``ddp/hook.py`` and the collectives it emits): MB of
gradient the hook hands to collectives each step, at the wire dtype's width,
from the program's gauge ``grad_sync.bytes``.  Nothing to read in a cell on
one chip."""

from chipbench import program_registry

UNIT = "MB"
LAYER = "collective engine"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    nbytes = program_registry.gauge("grad_sync.bytes")
    return None if nbytes is None or facts["world"] < 2 else nbytes / 1e6
