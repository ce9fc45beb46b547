#!/usr/bin/env python3
"""The readings a ``train_hybrid_lm`` cell's ``correct`` limits are set from,
in one process (``chipbench/readings_moe_lm.py`` for the other runner kind).

    python3 chipbench/readings_hybrid_lm.py --workload <cell> --seeds 1,2,3 [--controls float8] [--control-seeds 2]

For each seed: the program's first steps (the runner's own build, step and
feed, at the cell's own sizes) against the plain reference, and the
reference computed in each lower precision in the program's place (the
control, which has to come out as not correct).  One JSON line a seed:
every number compared, and under ``verdict`` what ``correct.compare`` says
of the program and of each control by the configuration file's own limits
(the names that failed; none for the program, one at least for a control).
Then the largest each number read over the sound runs and the smallest over
each control.
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


def failed_limits(numbers, reference, limits) -> list:
    """The rows of ``correct.compare`` that are over their limit, by name."""
    from chipbench import correct

    return [r["name"] for r in correct.compare(numbers, reference, limits) if not r["ok"]]


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="float8")
    ap.add_argument("--control-seeds", type=int, default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import run
    from chipbench.readings import gaps
    from chipbench.runners import train, train_hybrid_lm

    _, cell, config, mix = run.load_cell(root, root / "chipbench", args.workload)
    if require_chip:
        run.require_tpu(int(cell["chips"]))
        run.enable_compile_cache(root)
    world = int(cell["chips"])
    controls = [c for c in args.controls.split(",") if c]
    sound, failed = [], {c: [] for c in controls}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows = train_hybrid_lm.packed_rows(mix, config["vocab_size"], seed)
        trainer, mesh = train_hybrid_lm.build(config, world)
        batches = train.epochs_of_batches(rows, int(mix["batch_per_chip"]) * world, mesh, seed, 2)
        state = train_hybrid_lm.fresh_state(trainer, mesh, config, seed)
        jax.block_until_ready(state)
        try:
            state, checked, program = train_hybrid_lm.drive_first_steps(trainer, state, batches, config, seed)
        finally:
            batches.close()
        del state, trainer
        gc.collect()
        jax.clear_caches()
        reference = train_hybrid_lm.reference_numbers(config, checked, seed)
        line = {"seed": seed, "losses": program["losses"], "program": gaps(program, reference)}
        line["verdict"] = {"program": failed_limits(program, reference, config["limits"])}
        sound.append(line["program"])
        for c in controls if args.control_seeds is None or n < args.control_seeds else []:
            control = train_hybrid_lm.reference_numbers(config, checked, seed, c)
            line[c] = gaps(control, reference)
            line["verdict"][c] = failed_limits(control, reference, config["limits"])
            failed[c].append(line[c])
        print(json.dumps(line), flush=True)
    summary = {"sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}}
    for c in controls:
        if failed[c]:
            summary[f"{c}_smallest"] = {k: min(r[k] for r in failed[c]) for k in failed[c][0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
