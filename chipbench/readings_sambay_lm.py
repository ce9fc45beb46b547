#!/usr/bin/env python3
"""The readings a ``train_sambay_lm`` cell's ``correct`` limits are set from,
in one process (``chipbench/readings_ssm_lm.py`` for another runner kind).

    python3 chipbench/readings_sambay_lm.py --workload <cell> --seeds 1,2,3 \\
        [--controls float8,no_lambda,...] [--control-seeds 2]

For each seed: the program's first steps (the runner's own build, step and
feed, at the cell's own sizes) against the plain reference, and each control
in the program's place: the reference computed in a lower precision
(``float8``, ``bfloat16``) or with a piece of the mathematics changed
(``phi4_flash_ref.FAULTS``: ``no_lambda``, ``norm_before_diff``,
``memory_after_gate``, ``no_skip``, ``window_off``, ``kv_own``), each of which
has to come out as not correct.  One JSON line a seed: every number compared
(the loss's gap is the widest over the three steps; the leaf with the widest
gradient gap by name), the two readings the comparison leaves out and why it
may (``lambda_grad_gap``: the ``lambda`` vectors' first gradient by
``grad_norm_gap``'s measure; ``key_bias_grad``: the largest first gradient of
a key's bias as a share of the reference's median leaf's, and
``reference_key_bias_grad`` the reference's own, which
``train_sambay_lm.NOUGHT`` has to stand well above), and under
``verdict`` what the runner's comparison says of the program and of each
control by the configuration file's own limits (the names that failed; none
for the program, one at least for a control).  Then the largest each number
read over the sound runs and the smallest over each control.

The work goes program by compiled program, so that each compiles once however
many seeds are read and only one is loaded at a time: the timed path's step
for every seed; then the reference for every seed and, as the same program
given other numbers, the controls that change a piece; then each lower
precision.  The lines are printed at the end, in the seeds' order.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument(
        "--controls", default="float8,no_lambda,norm_before_diff,memory_after_gate,no_skip,window_off,kv_own"
    )
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the controls on the first N seeds only")
    ap.add_argument("--raw", default=None,
                    help="also write every side's losses and leaf norms, by seed and control, to this JSON file")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import run
    from chipbench.reference import phi4_flash_ref
    from chipbench.runners import train, train_sambay_lm

    _, cell, config, mix = run.load_cell(root, root / "chipbench", args.workload)
    if require_chip:
        run.require_tpu(int(cell["chips"]))
        run.enable_compile_cache(root)
    world = int(cell["chips"])
    controls = [c for c in args.controls.split(",") if c]
    unknown = set(controls) - set(train_sambay_lm.CONTROLS)
    if unknown:
        raise SystemExit(f"readings: controls {sorted(unknown)} not in {train_sambay_lm.CONTROLS}")
    unlimited = {k: float("inf") for k in config["limits"]}

    names = train_sambay_lm.leaf_names(config)
    leaves = train_sambay_lm.lambda_leaves(config)
    key_biases = [i for i, name in enumerate(names) if name.endswith("['qkv_proj']['bias'][1]")]

    def gaps(numbers, reference):
        rows = train_sambay_lm.compare({**numbers, "lambda_leaves": leaves}, reference, unlimited)
        out = {"loss_gap": max(r["value"] for r in rows if r["name"].startswith("loss_"))}
        out.update({r["name"]: r["value"] for r in rows if not r["name"].startswith("loss_")})
        got, want = (np.asarray(side["grad_norms"], np.float64) for side in (numbers, reference))
        out["lambda_grad_gap"] = float(np.max(np.abs(got - want)[leaves] / np.maximum(want[leaves], np.median(want))))
        out["key_bias_grad"] = float(got[key_biases].max() / np.median(want))
        return out

    def failed_limits(numbers, reference):
        rows = train_sambay_lm.compare({**numbers, "lambda_leaves": leaves}, reference, config["limits"])
        return [r["name"] for r in rows if not r["ok"]]

    def worst_leaf(numbers, reference):
        """By name, among the leaves ``grad_norm_gap`` is taken over."""
        got, want = (np.delete(np.asarray(side["grad_norms"], np.float64), leaves) for side in (numbers, reference))
        return np.delete(names, leaves)[int(np.argmax(np.abs(got - want) / np.maximum(want, np.median(want))))]

    raw = {}

    def keep(seed, what, numbers):
        raw.setdefault(str(seed), {})[what] = {
            k: np.asarray(numbers[k], np.float64).tolist() for k in ("losses", "grad_norms", "update_norms")
        }

    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = seeds if args.control_seeds is None else seeds[:args.control_seeds]

    def release() -> None:
        """One loaded step at a time: a loaded program keeps its temporaries reserved."""
        gc.collect()
        phi4_flash_ref._compiled_step.cache_clear()
        jax.clear_caches()

    # the program's side of every seed first, through one trainer: its step compiles once
    trainer, mesh = train_sambay_lm.build(config, world)
    checked, programs = {}, {}
    for seed in seeds:
        rows = train_sambay_lm.packed_rows(mix, config["vocab_size"], seed)
        batches = train.epochs_of_batches(rows, int(mix["batch_per_chip"]) * world, mesh, seed, 2)
        state = train_sambay_lm.fresh_state(trainer, mesh, config, seed)
        jax.block_until_ready(state)
        try:
            state, checked[seed], programs[seed] = train_sambay_lm.drive_first_steps(
                train_sambay_lm.NoRecording(trainer), state, batches, config, seed
            )
        finally:
            batches.close()
        del state
    del trainer
    release()

    # then the reference, and the controls that are the same compiled program given other numbers (the faults);
    # then each lower precision, a program of its own
    lines, references = {}, {}
    for seed in seeds:
        reference = references[seed] = train_sambay_lm.reference_numbers(config, checked[seed], seed)
        program = programs[seed]
        keep(seed, "program", program)
        keep(seed, "reference", reference)
        lines[seed] = {
            "seed": seed, "losses": program["losses"], "program": gaps(program, reference),
            "worst_leaf": worst_leaf(program, reference),
            "reference_key_bias_grad": float(
                np.max(reference["grad_norms"][key_biases]) / np.median(reference["grad_norms"])
            ),
            "verdict": {"program": failed_limits(program, reference)},
        }
    by_program = sorted(controls, key=lambda c: c not in phi4_flash_ref.FAULTS)      # the faults first
    for c in by_program:
        if c not in phi4_flash_ref.FAULTS:
            release()
        for seed in control_seeds:
            control = train_sambay_lm.reference_numbers(config, checked[seed], seed, c)
            keep(seed, c, control)
            lines[seed][c] = gaps(control, references[seed])
            lines[seed]["verdict"][c] = failed_limits(control, references[seed])
    sound = [lines[seed]["program"] for seed in seeds]
    failed = {c: [lines[seed][c] for seed in control_seeds] for c in controls}
    for seed in seeds:
        print(json.dumps(lines[seed]), flush=True)
    summary = {"sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}}
    summary["reference_key_bias_grad_largest"] = max(lines[seed]["reference_key_bias_grad"] for seed in seeds)
    for c in controls:
        if failed[c]:
            summary[f"{c}_smallest"] = {k: min(r[k] for r in failed[c]) for k in failed[c][0]}
    print(json.dumps(summary), flush=True)
    if args.raw:
        Path(args.raw).parent.mkdir(parents=True, exist_ok=True)
        Path(args.raw).write_text(json.dumps({"leaves": names, "lambda_leaves": leaves, "by_seed": raw}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
