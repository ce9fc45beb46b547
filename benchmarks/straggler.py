"""Straggler-regime end-to-end benchmark: the experiment that justifies the
adaptive sync plane.

The reference ships evidence of the straggler *problem* (wait-time CSVs from
``units-test/get_wait_time.py``'s ``heter_alpha`` skew emulation,
units-test/wait_time_heter_bc128.csv) and the rent-or-buy policy that
monetizes it (proto/rpc_server.py:69-96) — but never a committed run showing
the adaptive path beating full-wait BSP.  This benchmark closes that loop on
the virtual pod, with the REAL machinery end to end: per-rank worker threads
sleep their emulated backward time and negotiate each step through
:class:`CoordinatorLogic` (actual rent-or-buy freeze, wall-clock rent), and
the frozen active list drives the REAL compiled
:class:`~adapcc_tpu.ddp.DDPTrainer` step with a runtime mask.

Three sync modes over identical skew and data:

* ``full_wait``   — plain BSP DDP: every step waits for the slowest rank
                    (static full-world program, the psum fastpath).
* ``rentbuy_bsp`` — coordinator rent-or-buy freeze + BSP relay skip: the
                    leader stops waiting when renting costs more than buying;
                    the straggler's gradients for that step are dropped
                    (reference is_bsp=True, commu.py:107).
* ``rentbuy_async`` — same freeze, async relay bank: the straggler banks its
                    gradients in the carried deferred buffer and contributes
                    the accumulated sum at its next active step
                    (commu.py:160-170,427-431).

Skew pattern (``--pattern``): ``persistent`` marks ``--slow-rank`` slow on
every step; ``bursty`` (default) on 1 of every 4 steps, leaving enough fast
steps for the rank's pipeline lag to drain so it rejoins — intermittent
stragglers are where the async bank differs from BSP drop (a permanently
excluded rank's bank never lands, and the reference's replay has the same
property: a relay that never rejoins never replays).

Reported per mode: steps/s, per-step wait stats (dispatch start minus
previous-step result, the analog of the reference's wait-time CSV columns),
active-count totals, landed-gradient fraction (what share of per-rank batch
shards made it into an update — the convergence-relevant quantity), and the
final full-data eval loss.

Usage (virtual 8-CPU pod or real hardware)::

    python -m benchmarks.straggler --world 8 --steps 40
"""

from __future__ import annotations

import argparse
import json
import statistics
import threading
import time
from typing import Dict, List, Optional, Sequence

MODES = ("full_wait", "rentbuy_bsp", "rentbuy_async")


def _slow_steps(pattern: str, steps: int) -> List[bool]:
    if pattern == "persistent":
        return [True] * steps
    if pattern == "bursty":
        # slow 1 of every 4 steps.  The straggler's pipeline lag after one
        # slow step is (alpha-1)*base - rent_window; each fast step shrinks
        # it by the fast ranks' rent window, so with the default cost
        # constants it catches back up on the 3rd fast step, rejoins the
        # active set, and its banked gradients land — the regime where the
        # async bank beats BSP drop.  (2-of-3 slow at alpha 6 accrues lag
        # faster than it can recover: effectively persistent.)
        return [s % 4 == 0 for s in range(steps)]
    raise ValueError(f"unknown --pattern {pattern!r}")


def run_mode(
    mode: str,
    *,
    trainer,
    state,
    batches: Sequence,
    world: int,
    base_s: float,
    alpha: float,
    slow_rank: int,
    slow: Sequence[bool],
    logic_factory,
) -> Dict:
    """Run ``len(batches)`` steps of ``mode``; returns the metrics dict.

    Worker thread ``r`` emulates rank r's backward pass for step ``s`` by
    sleeping its compute delay after the step ``s-1`` result lands, then
    negotiating (or barriering).  The dispatcher thread launches the real
    compiled train step the moment the step's active set is decided.
    """
    import jax
    import numpy as np

    steps = len(batches)
    delays = [
        [
            base_s * (alpha if (r == slow_rank and slow[s]) else 1.0)
            for r in range(world)
        ]
        for s in range(steps)
    ]
    result_done = [threading.Event() for _ in range(steps)]
    frozen_ready = [threading.Event() for _ in range(steps)]
    frozen_lists: List[Optional[List[int]]] = [None] * steps
    arrivals = [0] * steps
    lock = threading.Lock()
    logic = logic_factory() if mode != "full_wait" else None

    def worker(rank: int) -> None:
        for s in range(steps):
            if s:
                result_done[s - 1].wait()
            time.sleep(delays[s][rank])
            if logic is None:
                with lock:
                    arrivals[s] += 1
                    if arrivals[s] == world:
                        frozen_lists[s] = list(range(world))
                        frozen_ready[s].set()
            else:
                active = logic.hook_arrive(s, rank)
                with lock:
                    if frozen_lists[s] is None:
                        frozen_lists[s] = active
                        frozen_ready[s].set()

    threads = [
        threading.Thread(target=worker, args=(r,), daemon=True)
        for r in range(world)
    ]
    t_start = time.monotonic()
    last_result = t_start
    waits: List[float] = []
    active_counts: List[int] = []
    excluded_shards = 0
    # per-rank shards banked since the rank's last active step: they land in
    # full at the next active step (sync_deferred folds the accumulated sum
    # into the masked average); whatever is still pending at the end is lost
    banked_pending = [0] * world
    for t in threads:
        t.start()
    for s in range(steps):
        frozen_ready[s].wait()
        waits.append(time.monotonic() - last_result)
        active = sorted(frozen_lists[s])
        active_counts.append(len(active))
        excluded_shards += world - len(active)
        for r in range(world):
            if r in active:
                banked_pending[r] = 0
            else:
                banked_pending[r] += 1
        if mode == "full_wait":
            state, _ = trainer.step(state, batches[s])
        else:
            mask = np.zeros((world,), dtype=bool)
            mask[active] = True
            state, _ = trainer.step(state, batches[s], active_mask=mask)
        jax.block_until_ready(state.params)
        last_result = time.monotonic()
        result_done[s].set()
    wall = time.monotonic() - t_start
    for t in threads:
        t.join()

    # landed-gradient fraction: how much of the presented data contributed
    # to an update.  BSP drop loses excluded shards outright; the async bank
    # recovers every banked shard whose rank rejoined, losing only the
    # still-pending tail.
    total_shards = steps * world
    if mode == "rentbuy_async":
        unlanded_tail = sum(banked_pending)
        landed = (total_shards - unlanded_tail) / total_shards
    else:
        landed = (total_shards - excluded_shards) / total_shards

    return {
        "mode": mode,
        "steps": steps,
        "wall_s": round(wall, 4),
        "steps_per_s": round(steps / wall, 3),
        "wait_mean_ms": round(1e3 * statistics.fmean(waits), 2),
        "wait_p95_ms": round(1e3 * sorted(waits)[max(0, int(0.95 * steps) - 1)], 2),
        "active_mean": round(statistics.fmean(active_counts), 3),
        "active_counts": active_counts,
        "excluded_rank_steps": excluded_shards,
        "landed_fraction": round(landed, 4),
        "state": state,
    }


def main(argv: Optional[Sequence[str]] = None) -> List[Dict]:

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--base-ms", type=float, default=15.0,
                    help="emulated per-rank backward time")
    ap.add_argument("--alpha", type=float, default=6.0,
                    help="straggler slowdown factor (reference heter_alpha)")
    ap.add_argument("--slow-rank", type=int, default=0)
    ap.add_argument("--pattern", choices=("persistent", "bursty"),
                    default="bursty")
    ap.add_argument("--out", type=str, default=None,
                    help="append one JSON line per mode to this file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync-mode", choices=("auto", "psum", "schedule"),
                    default="auto",
                    help="gradient-sync data plane; schedule = bucketed "
                    "strategy-tree allreduce (merged rounds on multi-tree)")
    ap.add_argument("--trans", type=int, default=1,
                    help="ring-strategy parallel trees (>1 engages the "
                    "merged-round executor on the schedule path)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.coordinator.logic import CoordinatorLogic
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.mlp import MLP
    from adapcc_tpu.strategy.ir import Strategy

    world, steps = args.world, args.steps
    mesh = build_world_mesh(world)
    slow = _slow_steps(args.pattern, steps)

    # fixed synthetic regression task; fresh batch per step (plain SGD)
    rng = np.random.default_rng(args.seed)
    d_in, d_out, per_rank = 16, 4, 8
    w_true = rng.normal(size=(d_in, d_out))
    model = MLP(features=(32, d_out))

    def make_batch():
        x = rng.normal(size=(world * per_rank, d_in)).astype(np.float32)
        y = np.tanh(x @ w_true).astype(np.float32)
        return jnp.asarray(x), jnp.asarray(y)

    batches = [make_batch() for _ in range(steps)]
    x_eval = jnp.concatenate([b[0] for b in batches[:8]])
    y_eval = jnp.concatenate([b[1] for b in batches[:8]])

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((model.apply(params, x) - y) ** 2)

    params0 = model.init(jax.random.PRNGKey(args.seed), batches[0][0][:1])
    eval_loss = jax.jit(lambda p: loss_fn(p, (x_eval, y_eval)))

    def logic_factory():
        return CoordinatorLogic(world)

    records = []
    for mode in MODES:
        trainer = DDPTrainer(
            loss_fn,
            optax.sgd(0.05),
            mesh,
            Strategy.ring(world, args.trans),
            dynamic_mask=(mode != "full_wait"),
            bsp=(mode != "rentbuy_async"),
            sync_mode=args.sync_mode,
            use_xla_fastpath=(args.sync_mode != "schedule"),
        )
        state = trainer.init_state(jax.tree_util.tree_map(jnp.array, params0))
        # compile outside the measured window (full-world warmup plus, for
        # masked modes, one partial-mask step — masking is a runtime input,
        # so both share one program; the warmup state is discarded)
        warm = trainer.init_state(jax.tree_util.tree_map(jnp.array, params0))
        if mode == "full_wait":
            trainer.step(warm, batches[0])
        else:
            m = np.ones((world,), dtype=bool)
            trainer.step(warm, batches[0], active_mask=m)
        trainer.reset()  # drop warmup step count + any warmup bank
        rec = run_mode(
            mode,
            trainer=trainer,
            state=state,
            batches=batches,
            world=world,
            base_s=args.base_ms / 1e3,
            alpha=args.alpha,
            slow_rank=args.slow_rank,
            slow=slow,
            logic_factory=logic_factory,
        )
        state = rec.pop("state")
        rec["final_eval_loss"] = round(float(eval_loss(state.params)), 6)
        rec.update(
            world=world, base_ms=args.base_ms, alpha=args.alpha,
            pattern=args.pattern, slow_rank=args.slow_rank,
            sync_mode=args.sync_mode, trans=args.trans,
            backend=jax.devices()[0].platform,
        )
        records.append(rec)
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    a, b, c = records
    summary = {
        "pattern": args.pattern,
        "speedup_rentbuy_bsp": round(b["steps_per_s"] / a["steps_per_s"], 3),
        "speedup_rentbuy_async": round(c["steps_per_s"] / a["steps_per_s"], 3),
        # the wait component alone: on tiny emulation models the async bank's
        # device-side O(params) overhead is visible in wall time; on real
        # models backward is O(params × batch) and the bank cost vanishes,
        # so the wait ratio is the transferable number
        "wait_speedup_bsp": round(a["wait_mean_ms"] / b["wait_mean_ms"], 3),
        "wait_speedup_async": round(a["wait_mean_ms"] / c["wait_mean_ms"], 3),
        "landed_bsp": b["landed_fraction"],
        "landed_async": c["landed_fraction"],
        "loss_full_wait": a["final_eval_loss"],
        "loss_rentbuy_bsp": b["final_eval_loss"],
        "loss_rentbuy_async": c["final_eval_loss"],
    }
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps({"summary": summary}) + "\n")
    return records


if __name__ == "__main__":
    main()
