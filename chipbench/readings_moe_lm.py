#!/usr/bin/env python3
"""The readings a ``train_moe_lm`` cell's ``correct`` limits are set from,
in one process (``chipbench/readings.py`` for the other runner kind).

    python3 chipbench/readings_moe_lm.py --workload <cell> --seeds 1,2,3 [--controls float8] [--control-seeds 4]

For each seed: the program's first steps (the runner's own build, step and
feed, at the cell's own sizes) against the plain reference, and the
reference computed in each lower precision in the program's place (the
control, which has to come out as not correct).  Also, for the first row of
the first step, how many of each expert layer's ``tokens x top_k`` choices
differ between the reference in bfloat16 and in float32.  One JSON line a
seed, then the largest each number read over the sound runs and the smallest
over each control.
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


def flipped(a, b) -> list:
    """Choices of ``a [layers, T, k]`` not among ``b``'s, a layer."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return [int(np.sum(~(x[:, :, None] == y[:, None, :]).any(axis=-1))) for x, y in zip(a, b)]


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="float8")
    ap.add_argument("--control-seeds", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import run, weights_moe_lm
    from chipbench.readings import gaps
    from chipbench.reference import trinity_ref
    from chipbench.runners import train, train_moe_lm

    _, cell, config, mix = run.load_cell(root, root / "chipbench", args.workload)
    if require_chip:
        run.require_tpu(int(cell["chips"]))
        run.enable_compile_cache(root)
    world = int(cell["chips"])
    controls = [c for c in args.controls.split(",") if c]
    sound, failed = [], {c: [] for c in controls}
    ids = jax.jit(lambda p, t, precision: trinity_ref.routing_ids(p, t, config, precision), static_argnums=2)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rows = train_moe_lm.packed_rows(mix, config["vocab_size"], seed)
        trainer, mesh = train_moe_lm.build(config, world)
        batches = train.epochs_of_batches(rows, int(mix["batch_per_chip"]) * world, mesh, seed, 2)
        state = train_moe_lm.fresh_state(trainer, mesh, config, seed)
        jax.block_until_ready(state)
        try:
            state, checked, program = train_moe_lm.drive_first_steps(trainer, state, batches, config, seed)
        finally:
            batches.close()
        del state, trainer
        gc.collect()
        jax.clear_caches()
        reference = train_moe_lm.reference_numbers(config, checked, seed)
        line = {"seed": seed, "losses": program["losses"], "program": gaps(program, reference)}
        sound.append(line["program"])
        for c in controls if args.control_seeds is None or n < args.control_seeds else []:
            line[c] = gaps(train_moe_lm.reference_numbers(config, checked, seed, c), reference)
            failed[c].append(line[c])
        params, row = weights_moe_lm.make_params(seed, config), jnp.asarray(checked[0, 0])
        line["choices_flipped_bf16_vs_f32"] = flipped(ids(params, row, "bfloat16"), ids(params, row, "float32"))
        del params
        print(json.dumps(line), flush=True)
    summary = {"sound_largest": {k: max(r[k] for r in sound) for k in sound[0]}}
    for c in controls:
        if failed[c]:
            summary[f"{c}_smallest"] = {k: min(r[k] for r in failed[c]) for k in failed[c][0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
