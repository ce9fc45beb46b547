#!/usr/bin/env python3
"""The readings a ``train_smallthinker_lm`` cell's ``correct`` limits are set from, in
one process (``chipbench/readings_lfm2_lm.py`` for another runner kind).

    python3 chipbench/readings_smallthinker_lm.py --workload <cell> --seeds 1,2,3 \\
        [--controls float8,window_off,...] [--control-seeds 2] [--raw <file>]

For each seed: the program's first steps (the runner's own build, step and
feed, at the cell's own sizes) against the plain reference, and each control
in the program's place: the reference computed in a lower precision
(``float8``, ``bfloat16``) or with a piece of the mathematics changed
(``smallthinker_ref.FAULTS``), each of which has to come out as not correct.  One
JSON line a seed: every number compared (the loss's gap is the widest over
the three steps; the leaves with the widest gradient and update gaps by
name), and under ``verdict`` what the runner's comparison says of the program
and of each control by the configuration file's own limits (the names that
failed; none for the program, one at least for a control).  Then the largest
each number read over the sound runs and the smallest over each control.

The work goes program by compiled program, so that each compiles once however
many seeds are read and only one is loaded at a time: the timed path's step
for every seed; then the reference for every seed and, as the same program
given other numbers, the controls that change a piece; then each lower
precision.  The lines are printed at the end, in the seeds' order; ``--raw``
is written anew after every stage, so that a call cut short keeps what it read.
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
    ap.add_argument("--controls", default=None, help="comma-separated; default: float8 and every changed piece")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the controls on the first N seeds only")
    ap.add_argument("--raw", default=None,
                    help="also write the lines and every side's losses and leaf norms, by seed and control, to this JSON file")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import correct, run, weights_smallthinker_lm
    from chipbench.reference import smallthinker_ref
    from chipbench.runners import train, train_smallthinker_lm

    _, cell, config, mix = run.load_cell(root, root / "chipbench", args.workload)
    if require_chip:
        run.require_tpu(int(cell["chips"]))
        run.enable_compile_cache(root)
    world = int(cell["chips"])
    controls = ["float8", *(f for f in smallthinker_ref.FAULTS if f)] if args.controls is None else [c for c in args.controls.split(",") if c]
    unknown = set(controls) - set(train_smallthinker_lm.CONTROLS)
    if unknown:
        raise SystemExit(f"readings: controls {sorted(unknown)} not in {train_smallthinker_lm.CONTROLS}")
    unlimited = {k: float("inf") for k in config["limits"]}
    names = weights_smallthinker_lm.leaf_names(config)

    def gaps(numbers, reference):
        rows = correct.compare(numbers, reference, unlimited)
        out = {"loss_gap": max(r["value"] for r in rows if r["name"].startswith("loss_"))}
        out.update({r["name"]: r["value"] for r in rows if not r["name"].startswith("loss_")})
        return out

    def failed_limits(numbers, reference):
        return [r["name"] for r in correct.compare(numbers, reference, config["limits"]) if not r["ok"]]

    def worst_leaf(numbers, reference, key):
        want = np.asarray(reference[key], np.float64)
        gap = np.abs(np.asarray(numbers[key], np.float64) - want) / np.maximum(want, np.median(want))
        return names[int(np.argmax(gap))]

    raw, lines = {}, {}

    def keep(seed, what, numbers):
        raw.setdefault(str(seed), {})[what] = {
            k: np.asarray(numbers[k], np.float64).tolist() for k in ("losses", "grad_norms", "update_norms")
        }
        if args.raw:
            Path(args.raw).parent.mkdir(parents=True, exist_ok=True)
            Path(args.raw).write_text(json.dumps({"leaves": names, "lines": lines, "by_seed": raw}))

    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = seeds if args.control_seeds is None else seeds[:args.control_seeds]

    def release() -> None:
        """One loaded step at a time: a loaded program keeps its temporaries reserved."""
        gc.collect()
        smallthinker_ref._compiled_step.cache_clear()
        jax.clear_caches()

    # the program's side of every seed first, through one trainer: its step compiles once
    trainer, mesh = train_smallthinker_lm.build(config, world)
    checked, programs = {}, {}
    for seed in seeds:
        rows = train_smallthinker_lm.packed_rows(mix, config["vocab_size"], seed)
        batches = train.epochs_of_batches(rows, int(mix["batch_per_chip"]) * world, mesh, seed, 2)
        state = train_smallthinker_lm.fresh_state(trainer, mesh, config, seed)
        jax.block_until_ready(state)
        try:
            state, checked[seed], programs[seed] = train_smallthinker_lm.drive_first_steps(
                train_smallthinker_lm.Recording(trainer), state, batches, config, seed
            )
        finally:
            batches.close()
        del state
        keep(seed, "program", programs[seed])
    del trainer
    release()

    # then the reference, and the controls that are the same compiled program given other numbers (the faults);
    # then each lower precision, a program of its own
    references = {}
    for seed in seeds:
        reference = references[seed] = train_smallthinker_lm.reference_numbers(config, checked[seed], seed)
        program = programs[seed]
        lines[seed] = {
            "seed": seed, "losses": program["losses"], "program": gaps(program, reference),
            "worst_leaf": worst_leaf(program, reference, "grad_norms"),
            "worst_update_leaf": worst_leaf(program, reference, "update_norms"),
            "verdict": {"program": failed_limits(program, reference)},
        }
        keep(seed, "reference", reference)
    by_program = sorted(controls, key=lambda c: c not in smallthinker_ref.FAULTS)      # the faults first
    for c in by_program:
        if c not in smallthinker_ref.FAULTS:
            release()
        for seed in control_seeds:
            control = train_smallthinker_lm.reference_numbers(config, checked[seed], seed, c)
            lines[seed][c] = gaps(control, references[seed])
            lines[seed]["verdict"][c] = failed_limits(control, references[seed])
            keep(seed, c, control)
    sound = [lines[seed]["program"] for seed in seeds]
    failed = {c: [lines[seed][c] for seed in control_seeds] for c in controls}
    for seed in seeds:
        print(json.dumps(lines[seed]), flush=True)
    summary = {"sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}}
    for c in controls:
        if failed[c]:
            summary[f"{c}_smallest"] = {k: min(r[k] for r in failed[c]) for k in failed[c][0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
