"""Collective engine (``comm/engine.py`` and the XLA collectives the hook
emits): time per step in which a collective runs on a chip and no other
operation does, mean over chips, from the device trace.  Nothing to read in
a cell on one chip."""

UNIT = "ms"
LAYER = "collective engine"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(facts):
    trace = facts["trace"]
    if trace is None or facts["world"] < 2 or not facts["steps"]:
        return None
    return 1e3 * trace["exposed_collective_s"] / facts["steps"]
