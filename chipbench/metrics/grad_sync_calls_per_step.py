"""Collective engine (``ddp/hook.py`` and the collectives it emits):
collective calls the hook emits each step (one per leaf on the ``psum``
path, one per bucket on the bucketed paths), from the program's gauge
``grad_sync.calls``.  Nothing to read in a cell on one chip."""

from chipbench import program_registry

UNIT = "count"
LAYER = "collective engine"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    calls = program_registry.gauge("grad_sync.calls")
    return None if calls is None or facts["world"] < 2 else calls
