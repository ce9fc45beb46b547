"""What the ``train_hybrid_lm`` cells take from a device trace beyond
``trace_reduce.reduce_trace``: the time of the KDA scan's two kernels
(``adapcc_tpu/ops/kda.py``: ``kda_fwd``, ``kda_bwd``) and of the latent
layer's three attention kernels (``ops/flash_attention.py``).

A Mosaic kernel is an ``XLA Ops`` event whose HLO instruction is a
``tpu_custom_call``.  It is told by its name where the instruction carries
the kernel's (``%kda_fwd.4``), else by its signature: the KDA forward takes
five arrays (q, k, beta k, v, the summed decay), its backward seven, the
flash kernels three and six (``trace_reduce.flash_kernel``).  XLA's own
``ragged-dot`` kernels, the expert layer's, are custom calls too and are
left out by name.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from chipbench import trace_reduce

KDA_KERNELS = ("kda_fwd", "kda_bwd")
MLA_KERNELS = trace_reduce.FLASH_KERNELS
_BY_OPERANDS = {5: "kda_fwd", 7: "kda_bwd"}


def kernel_of(name: str) -> Optional[str]:
    """Which of the five kernels an operation is, or None."""
    if trace_reduce.MOSAIC not in name:
        return None
    op = trace_reduce.parse_op(name)
    if op["op"] != "custom-call" or op["name"].startswith("ragged-dot"):
        return None
    for kernel in sorted(KDA_KERNELS + MLA_KERNELS, key=len, reverse=True):
        if op["name"].startswith(kernel):
            return kernel
    operands = op["rest"].split("), custom_call_target", 1)[0].count(" %")
    return _BY_OPERANDS.get(operands) or trace_reduce.flash_kernel(name)


def kernel_seconds(trace: Dict[str, Any]) -> Dict[str, float]:
    """Summed device time of each kernel over the traced window, mean over
    the chips that ran something."""
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    out = {k: 0.0 for k in KDA_KERNELS + MLA_KERNELS}
    which: Dict[str, Optional[str]] = {}
    for evs in ops.values():
        for name, _, dur in evs:
            if name not in which:
                which[name] = kernel_of(name)
            if which[name]:
                out[which[name]] += dur / 1e9 / len(ops)
    return out


def top_operations(trace: Dict[str, Any], count: int) -> List[Tuple[str, float]]:
    """The ``count`` operations with the most summed device time (seconds,
    mean over the chips), by ``trace_reduce.stable_name`` with the five
    kernels under their own names: where the step's time goes past the ten
    lines a result's ``breakdown`` keeps.  A ``conditional`` holds its
    children, which are listed too."""
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    by_name: Dict[str, float] = {}
    label: Dict[str, str] = {}
    for evs in ops.values():
        for name, _, dur in evs:
            if name not in label:
                op = trace_reduce.parse_op(name)
                if op["name"].startswith("ragged-dot"):     # by signature they would pass for a flash kernel
                    label[name] = f"ragged-dot {re.sub(r'{[^}]*}', '', op['type'])}"[:80]
                else:
                    label[name] = kernel_of(name) or trace_reduce.stable_name(name)
            by_name[label[name]] = by_name.get(label[name], 0.0) + dur / 1e9 / len(ops)
    return sorted(by_name.items(), key=lambda kv: -kv[1])[:count]
