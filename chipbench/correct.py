"""The comparison that decides ``correct`` for a training cell.

The program's first steps (the window's own compiled step, state and feed)
against the plain reference's on the same rows: each step's loss, the norm
of the first gradient as the optimizer gets it, and the norm of the
parameters' change after the last step.  The two norms go leaf by leaf and
the worst leaf counts: the gap between the program's norm and the
reference's, against the reference's norm of that leaf or of the median
leaf, whichever is larger (some gradients are all but zero).

Every number has a limit of its own, stated in the configuration file with
the readings it was set from (PERF.md, section 2).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

LIMIT_KEYS = ("loss_gap", "grad_norm_gap", "update_norm_gap")


def worst_leaf_gap(program: Sequence[float], reference: Sequence[float]) -> float:
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if program.shape != reference.shape:
        raise ValueError(f"leaf counts differ: {program.shape} vs {reference.shape}")
    floor = float(np.median(reference))
    gap = np.abs(program - reference) / np.maximum(np.maximum(reference, floor), 1e-30)
    return float(np.max(np.nan_to_num(gap, nan=np.inf)))


def compare(program: Dict[str, Any], reference: Dict[str, Any], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """One row for each number compared: its name, value, limit and verdict.
    ``program`` and ``reference`` hold ``losses``, ``grad_norms`` and
    ``update_norms`` as :func:`chipbench.reference.gpt2_ref.train_steps`
    returns them."""
    missing = [k for k in LIMIT_KEYS if k not in limits]
    if missing:
        raise KeyError(f"the configuration states no limit for {missing}")
    rows = []
    p_loss = np.asarray(program["losses"], np.float64)
    r_loss = np.asarray(reference["losses"], np.float64)
    if p_loss.shape != r_loss.shape:
        raise ValueError(f"step counts differ: {p_loss.shape} vs {r_loss.shape}")
    for i, (p, r) in enumerate(zip(p_loss, r_loss), start=1):
        gap = abs(p - r) / abs(r) if np.isfinite(p) and np.isfinite(r) else float("inf")
        rows.append({"name": f"loss_gap.step{i}", "value": float(gap), "limit": limits["loss_gap"]})
    rows.append({
        "name": "grad_norm_gap",
        "value": worst_leaf_gap(program["grad_norms"], reference["grad_norms"]),
        "limit": limits["grad_norm_gap"],
    })
    rows.append({
        "name": "update_norm_gap",
        "value": worst_leaf_gap(program["update_norms"], reference["update_norms"]),
        "limit": limits["update_norm_gap"],
    })
    for row in rows:
        row["ok"] = bool(row["value"] <= row["limit"])
    return rows


def verdict(rows: List[Dict[str, Any]]) -> bool:
    return all(r["ok"] for r in rows)


def show(rows: List[Dict[str, Any]], say) -> None:
    for r in rows:
        say(f"correct: {r['name']} = {r['value']:.6g}  limit {r['limit']:.6g}  {'ok' if r['ok'] else 'FAILED'}")
