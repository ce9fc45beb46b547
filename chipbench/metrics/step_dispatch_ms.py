"""Trainer (``ddp/trainer.py``, ``ddp/hook.py``): host time inside
``trainer.step(...)`` (negotiate + enqueue), mean per step, from the
benchmark's span around it."""

UNIT = "ms"
LAYER = "trainer"
MOVES = "train_step_p95_ms"
SOURCE = "host_clock"


def read(facts):
    spans = facts["spans"].get("step_dispatch")
    return 1e3 * sum(spans) / len(spans) if spans else None
