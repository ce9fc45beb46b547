"""Model step (``models/granite_hybrid.py``): the sample ``ssd.decay_floor``
the program records from what each step of the traced window handed out
beside its loss: the smallest decay ``exp(sum of dt A)`` any chunk of any
Mamba-2 layer's scan laid on the state it was handed, float32; mean over the
window's steps.  How near a chunk comes to forgetting everything, which is
what bounds the chunk of a kernel that splits the exponential."""

from chipbench import program_registry

UNIT = "ratio"
LAYER = "model step"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(facts):
    if "ssm_lm" not in facts:
        return None
    return program_registry.sample_mean("ssd.decay_floor")
