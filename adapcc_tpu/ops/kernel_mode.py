"""Kernel or interpreter: the one place a Pallas call site decides.

Every Pallas kernel in the repo (flash attention, the ICI ring family) can
run through Mosaic on a TPU or through the Pallas interpreter on the CPU
pod the tests use.  A call site that was not told which (``interpret=None``)
asks here, and the answer is *recorded* per site: the interpreter silently
inlined where a kernel was expected is exactly the failure a chip run must
be able to rule out.  ``chip_smoke.py`` asserts every recorded decision is
``False`` on the chip; the engine stamps the decision on each ring dispatch
in its :class:`~adapcc_tpu.utils.observability.CollectiveTrace`.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax

_DECISIONS: Dict[str, bool] = {}


def resolve_interpret(interpret, site: str):
    """``interpret`` as the caller pinned it (a bool or Pallas interpret
    params), else the interpreter exactly when the default backend is not a
    TPU.  The decision is recorded under ``site``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _DECISIONS[site] = bool(interpret)
    return interpret


def interpret_decisions() -> Dict[str, bool]:
    """Site → whether its last Pallas call ran the interpreter."""
    return dict(_DECISIONS)
