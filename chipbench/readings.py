#!/usr/bin/env python3
"""The readings a cell's ``correct`` limits are set from, in one process.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 [--controls float8,bfloat16]

For each seed: the program's first steps (the runner's own build, step and
feed, at the cell's own sizes) against the plain reference, and the
reference computed in each lower precision in the program's place (the
control, which has to come out as not correct).  Prints one JSON line per
seed with every number compared, and a summary: the largest each number
read over the sound runs and the smallest over each control.  Limits go
above the first and below the second (PERF.md, section 2).
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


def gaps(program, reference):
    from chipbench import correct

    rows = correct.compare(program, reference, {k: float("inf") for k in correct.LIMIT_KEYS})
    out = {"loss_gap": max(r["value"] for r in rows if r["name"].startswith("loss_gap"))}
    out.update({r["name"]: r["value"] for r in rows if not r["name"].startswith("loss_gap")})
    return out


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="float8")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the controls on the first N seeds only (they need no program)")
    args = ap.parse_args(argv)

    import jax

    from chipbench import run
    from chipbench.runners import train
    from chipbench.traffic import generator

    _, cell, config, mix = run.load_cell(root, root / "chipbench", args.workload)
    if require_chip:
        run.require_tpu(int(cell["chips"]))
    run.enable_compile_cache(root)
    world = int(cell["chips"])
    trainer, mesh = train.build(config, world)
    controls = [c for c in args.controls.split(",") if c]
    per_step = int(mix["batch_per_chip"]) * world
    ref_fn = train.reference_fn(config, per_step)
    control_fn = {c: train.reference_fn(config, per_step, precision=c) for c in controls}
    sound, failed = [], {c: [] for c in controls}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows = generator.make_rows(mix, config["vocab_size"], seed)
        batches = train.epochs_of_batches(rows, int(mix["batch_per_chip"]) * world, mesh, seed, 2)
        state = train.fresh_state(trainer, mesh, config, seed)
        jax.block_until_ready(state)
        try:
            state, checked, program = train.drive_first_steps(trainer, state, batches, config, seed)
        finally:
            batches.close()
        del state
        gc.collect()
        reference = train.reference_numbers(config, checked, seed, ref_fn)
        line = {"seed": seed, "losses": program["losses"], "program": gaps(program, reference)}
        sound.append(line["program"])
        for c in controls if args.control_seeds is None or n < args.control_seeds else []:
            line[c] = gaps(train.reference_numbers(config, checked, seed, control_fn[c]), reference)
            failed[c].append(line[c])
        print(json.dumps(line), flush=True)
    summary = {"sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}}
    for c in controls:
        summary[f"{c}_smallest"] = {k: min(r[k] for r in failed[c]) for k in failed[c][0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
