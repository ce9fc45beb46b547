"""ZeRO-1 data-plane A/B: XLA psum_scatter/all_gather vs the Pallas ring.

One JSON line per variant: steady-state step ms of the full
``zero1_train_step`` program (grad → reduce-scatter → sharded adam →
all-gather) on an MLP sized by ``--params`` (default ~8M), after
``--warmup`` untimed steps (the first compiles).

At world=1 (one real chip) both collectives are degenerate, so the A/B
measures the ring path's *plumbing* cost (tile-aligned padding + the
chunk-order roll) — the honest single-chip statement; the ring's bandwidth
case needs a real pod and is pinned functionally by the interpret-mode
parity tests (tests/test_fsdp.py).

Usage::

    python -m benchmarks.zero1_ab --steps 20 --json
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> int:

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer, zero1_train_step

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--params", type=int, default=8 << 20,
                    help="approx parameter count (two square layers)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--world", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    world = args.world or len(jax.devices())
    mesh = build_world_mesh(world)
    d = int(np.sqrt(args.params / 2))
    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(d, d)) * 0.02, jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(d, d)) * 0.02, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(args.batch * world, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(args.batch * world, d)), jnp.float32)

    def loss_fn(p, batch):
        bx, by = batch
        h = jnp.tanh(bx @ p["w1"])
        return jnp.mean((h @ p["w2"] - by) ** 2)

    platform = jax.devices()[0].platform
    rows = []
    for ring in (False, True):
        opt = Zero1Optimizer(optax.adam(1e-3), mesh, ring=ring)
        master, opt_state = opt.init(params)
        step = zero1_train_step(loss_fn, opt, mesh)
        p = jax.tree_util.tree_map(jnp.array, params)
        for _ in range(max(args.warmup, 1)):  # the first step compiles
            p, master, opt_state, losses = step(p, master, opt_state, (x, y))
            jax.block_until_ready(losses)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            p, master, opt_state, losses = step(p, master, opt_state, (x, y))
            jax.device_get(losses)  # forced sync closes the window
        per_step = (time.perf_counter() - t0) / args.steps
        rows.append({
            "metric": "zero1_step_ms",
            "data_plane": "pallas_ring" if ring else "xla",
            "world": world,
            "platform": platform,
            "params": 2 * d * d,
            "step_ms": round(per_step * 1e3, 3),
        })

    for r in rows:
        print(json.dumps(r) if args.json else r, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
