"""Pod-scale synthesizer benchmark: milp vs partrees vs ring vs the
hierarchical sketch policy, world 32 → 4096.

The reference ships strategy fixtures up to 24 GPUs (`strategy/`, 17 files)
and its Gurobi study compares solver vs heuristic makespans
(gurobi/solver.py:190-208).  This sweep reproduces that comparison at pod
scale on synthetic two-level topologies, putting all three synthesis
policies on one modeled scale:

- **policy wall time** — synthesis latency with the solver's own runtime
  budget (`ROUTING_MILP_TIME_LIMIT_S`) in force, i.e. what topology
  reconstruction would actually stall;
- **modeled makespan** — the routing MILP's pipeline-aware bottleneck
  objective evaluated on every policy's output
  (:func:`adapcc_tpu.strategy.solver.modeled_makespan`);
- **lowering** — rounds per tree through ``reduce_rounds`` /
  ``broadcast_rounds``; at >= ``Tree.NATIVE_LOWERING_THRESHOLD`` (64) ranks
  this exercises the native C++ lowering engine when ``libadapcc_rt.so`` is
  built (strategy/ir.py:162);
- optional ``--exec``: relative busbw of each policy's allreduce executed on
  a virtual CPU pod of the same world size (NOT a hardware number — an
  ordering regression pin, like busbw_virtual8).

The degraded-link topologies are where the policies genuinely diverge: one
host pair's DCN bandwidth is cut to a fraction, so bandwidth-aware synthesis
(milp / partrees BDP sort) should beat the oblivious ring on the modeled
makespan.

The ``hier`` policy rows (docs/HIERARCHY.md) are the pod-cluster
extension: matrix-free per-level solves whose wall time stays inside
``MILP_SYNTH_BUDGET_S`` all the way to world=4096, recorded next to the
flat policies' blowout — every row stamps ``synth_budget_s`` /
``within_synth_budget`` so the scaling curve is pinned, not eyeballed.

Usage::

    python -m benchmarks.synthesis_scale --worlds 32,64 --json
    XLA_FLAGS=--xla_force_host_platform_device_count=32 JAX_PLATFORMS=cpu \
        python -m benchmarks.synthesis_scale --worlds 32 --exec --json
"""

from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional, Sequence, Tuple

from adapcc_tpu.primitives import ALLREDUCE

#: intra-host (ICI) / healthy inter-host (DCN) link model, in GB/s and s —
#: the same two-tier shape the reference's cluster profiles have
#: (strategy/cluster_*.xml: NVLink vs 100GbE)
ICI_BW, ICI_LAT = 400.0, 1e-6
DCN_BW, DCN_LAT = 25.0, 5e-5

#: largest world the dense-matrix (flat) policies run at in the default
#: sweep: the flat MILP measures ~5.9 s at 1024 (already 6x the budget —
#: the row records the blowout) and minutes at 4096; the hierarchical
#: sketch policy carries the curve beyond this, matrix-free
MATRIX_POLICY_MAX_WORLD = 1024

#: replay-scaling world grid (--replay-scale): the vectorized engine's
#: scaling curve, recorded next to the synthesis curve it unblocks
REPLAY_WORLDS = (1024, 4096, 16384, 65536, 131072)

#: replay wall-clock budgets the scaling rows pin, mirroring
#: ``synth_budget_s``: a world<=16384 strategy must replay in < 2 s (the
#: controller's re-rank window) and even 131072 in < 30 s
REPLAY_BUDGET_S = 2.0
REPLAY_BUDGET_LARGE_S = 30.0
REPLAY_BUDGET_MAX_WORLD = 16384


def replay_budget_s(world: int) -> float:
    """The wall-clock budget a ``world``-rank replay is pinned against."""
    return REPLAY_BUDGET_S if world <= REPLAY_BUDGET_MAX_WORLD else REPLAY_BUDGET_LARGE_S


def bench_replay(
    world: int,
    transmission_size: int = 64 << 20,
    collective: str = "allreduce",
) -> dict:
    """Replay-scaling row: build + cold replay + warm re-price wall times
    for a ``world``-rank binary strategy on a uniform synthetic topology,
    stamped ``replay_budget_s`` / ``within_replay_budget_s`` (the replay
    twin of ``synth_budget_s`` / ``within_synth_budget``).

    The cold replay includes column lowering; the re-price row shows what
    the adaptation loop actually pays once the structure cache is warm
    (docs/SIMULATION.md §7).  Wall times are measured, so these rows are
    NOT byte-identical across runs — the deterministic priced grid lives
    in ``sim_collectives --scale-sweep``.
    """
    from adapcc_tpu.sim.cost_model import (
        LinkCostModel, collective_lower_bound, optimality_gap,
    )
    from adapcc_tpu.sim.replay import simulate_strategy
    from adapcc_tpu.sim.vector import clear_lowering_cache, resolve_sim_engine
    from adapcc_tpu.strategy.ir import Strategy

    model = LinkCostModel.uniform(world)
    t0 = time.perf_counter()
    strategy = Strategy.binary(world, 2)
    build_s = time.perf_counter() - t0

    clear_lowering_cache()  # the cold number must include column lowering
    t0 = time.perf_counter()
    timeline = simulate_strategy(
        strategy, model, transmission_size, collective, keep_transfers=False
    )
    replay_s = time.perf_counter() - t0

    t0 = time.perf_counter()  # warm: cached columns, pricing only
    simulate_strategy(
        strategy, model, transmission_size, collective, keep_transfers=False
    )
    reprice_s = time.perf_counter() - t0

    lb = collective_lower_bound(model, transmission_size, collective, world)
    budget = replay_budget_s(world)
    return {
        "world": world,
        "policy": "replay",
        "strategy": "binary",
        "engine": resolve_sim_engine(None, world),
        "size_bytes": int(transmission_size),
        "build_ms": round(build_s * 1e3, 2),
        "replay_ms": round(replay_s * 1e3, 2),
        "reprice_ms": round(reprice_s * 1e3, 2),
        "pred_time_us": round(timeline.seconds * 1e6, 3),
        "lower_bound_us": round(lb * 1e6, 3),
        "optimality_gap": round(optimality_gap(timeline.seconds, lb), 6),
        "replay_budget_s": budget,
        "within_replay_budget_s": replay_s <= budget,
    }


def synthetic_ip_table(num_hosts: int, per_host: int) -> List[str]:
    """The matrix-free half of :func:`synthetic_topology` — all the
    hierarchical sketch policy needs, so pod-cluster worlds never pay the
    world² matrix build just to benchmark an O(pod)+O(hosts) solve."""
    return [f"10.8.{h}.1" for h in range(num_hosts) for _ in range(per_host)]


def synthetic_topology(
    num_hosts: int, per_host: int, degraded_pair: Optional[Tuple[int, int]] = (0, 1),
    degrade_factor: float = 0.25,
):
    """(ip_table, bandwidth_graph, latency_graph) for a two-level pod.

    ``degraded_pair`` cuts one host pair's DCN bandwidth by
    ``degrade_factor`` — the adaptive-routing case the synthesizers exist
    for (reference README: "adapts to dynamic network conditions").
    Vectorized: the pod-scale worlds the default grid now reaches would
    spend longer building matrices in a Python loop than synthesizing.
    """
    import numpy as np

    world = num_hosts * per_host
    ip_table = synthetic_ip_table(num_hosts, per_host)
    host_of = np.arange(world) // per_host
    same = host_of[:, None] == host_of[None, :]
    bw = np.where(same, ICI_BW, DCN_BW)
    lat = np.where(same, ICI_LAT, DCN_LAT)
    if degraded_pair is not None:
        a, b = degraded_pair
        pair = (
            (host_of[:, None] == a) & (host_of[None, :] == b)
        ) | (
            (host_of[:, None] == b) & (host_of[None, :] == a)
        )
        bw = np.where(pair, DCN_BW * degrade_factor, bw)
        lat = np.where(pair, DCN_LAT * 4, lat)
    np.fill_diagonal(bw, 0.0)
    np.fill_diagonal(lat, 0.0)
    return ip_table, bw.tolist(), lat.tolist()


def crosshost_makespan(
    strategy,
    bw: Sequence[Sequence[float]],
    lat: Sequence[Sequence[float]],
    transmission_size: int,
) -> float:
    """Policy-agnostic bottleneck-edge time in SECONDS: max over every tree
    edge of ``lat + share·size/(bw·1e9)`` (bw in GB/s, the profiler's
    convention).  Unlike :func:`modeled_makespan` — which projects to
    inter-master edges and so scores a master-chain ring as zero — this
    walks ALL edges, making ring vs tree strategies comparable."""
    import numpy as np

    b = np.asarray(bw, float)
    l = np.asarray(lat, float)
    worst = 0.0
    for tree, share in zip(strategy.trees, strategy.tree_shares()):
        if share <= 0.0:
            continue
        for p, cs in tree.children.items():
            for c in cs:
                t = l[p][c] + share * transmission_size / (max(b[p][c], 1e-9) * 1e9)
                worst = max(worst, float(t))
    return worst


def bench_policy(
    policy: str,
    ip_table: Sequence[str],
    bw: Sequence[Sequence[float]],
    lat: Sequence[Sequence[float]],
    parallel_degree: int = 2,
    transmission_size: int = 4 << 20,
) -> dict:
    """Synthesize + score one policy; returns one artifact row.

    Every row carries ``synth_budget_s`` / ``within_synth_budget`` (the
    reconstruction budget the pruned MILP earned at 64 ranks, PR 2), so
    the pod-scale curve is pinned per policy rather than eyeballed.  The
    ``hier`` policy (docs/HIERARCHY.md) needs no profile matrices — pass
    ``bw=lat=None`` and the row prices off the sketch's class
    coefficients; matrix policies reject None loudly.
    """
    from adapcc_tpu import native
    from adapcc_tpu.strategy.solver import MILP_SYNTH_BUDGET_S, modeled_makespan
    from adapcc_tpu.strategy.synthesizer import Synthesizer, _infer_local_rank0s

    world = len(ip_table)
    masters = _infer_local_rank0s(ip_table)
    have_matrices = bw is not None and lat is not None
    if policy != "hier" and not have_matrices:
        raise ValueError(
            f"policy {policy!r} synthesizes from profile matrices; only "
            "'hier' runs matrix-free (the sketch's class coefficients)"
        )
    t0 = time.perf_counter()
    strategy = Synthesizer(None, ip_table, policy).synthesize(
        ALLREDUCE, parallel_degree, transmission_size, bw, lat
    )
    synth_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    rounds = sum(
        len(t.reduce_rounds()) + len(t.broadcast_rounds()) for t in strategy.trees
    )
    lower_s = time.perf_counter() - t0
    row = {
        "world": world,
        "hosts": len(masters),
        "policy": policy,
        "synthesis": strategy.synthesis,
        "num_trees": len(strategy.trees),
        "synth_ms": round(synth_s * 1e3, 2),
        "lowering_ms": round(lower_s * 1e3, 2),
        "rounds": rounds,
        "native_lowering": bool(
            native.available()
            and world >= type(strategy.trees[0]).NATIVE_LOWERING_THRESHOLD
        ),
        "synth_budget_s": MILP_SYNTH_BUDGET_S,
        "within_synth_budget": synth_s <= MILP_SYNTH_BUDGET_S,
    }
    if have_matrices:
        # raw model units (reference gurobi objective) — inter-master edges
        # only, comparable between milp and partrees
        row["modeled_makespan"] = float(
            modeled_makespan(
                strategy, masters, ALLREDUCE, transmission_size, bw, lat
            )
        )
        # seconds → ms, every edge scored — comparable across ALL policies
        row["crosshost_makespan_ms"] = round(
            crosshost_makespan(strategy, bw, lat, transmission_size) * 1e3, 4
        )
    if policy == "hier":
        from adapcc_tpu.strategy.hierarchy import plan_of

        plan = plan_of(strategy)
        row.update({
            "hier_pods": plan.sketch.num_pods,
            "hier_pod_size": plan.sketch.pod_size,
            "pod_algo": plan.pod_algo,
            "leader_algo": plan.leader_algo,
            "ici_solve_ms": round(plan.ici_solve.solve_s * 1e3, 4),
            "dcn_solve_ms": round(plan.dcn_solve.solve_s * 1e3, 4),
            "pred_two_level_us": round(plan.predicted_s * 1e6, 3),
            "pred_flat_us": round(plan.flat_pred_s * 1e6, 3),
            "chosen_vs_flat": plan.chosen_vs_flat,
        })
    return row


def exec_relative_busbw(
    policy: str,
    ip_table: Sequence[str],
    bw,
    lat,
    elems: int = 16384,
    iters: int = 3,
) -> dict:
    """Execute the policy's allreduce on a virtual pod of the same world
    size; returns a timing row (ordering evidence only, not hardware)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    world = len(ip_table)
    if len(jax.devices()) < world:
        raise RuntimeError(
            f"--exec needs {world} devices "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count={world})"
        )
    strategy = Synthesizer(None, ip_table, policy).synthesize(
        ALLREDUCE, 2, 4 << 20, bw, lat
    )
    mesh = build_world_mesh(world)
    eng = CollectiveEngine(mesh, strategy, use_xla_fastpath=False)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(world, elems)), jnp.float32
    )
    active = list(range(world))
    jax.block_until_ready(eng.all_reduce(x, active_gpus=active))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(eng.all_reduce(x, active_gpus=active))
    per_op = (time.perf_counter() - t0) / iters
    return {
        "world": world,
        "policy": policy,
        "exec_virtual_ms": round(per_op * 1e3, 2),
        "elems": elems,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worlds", default="32,64,256,1024,4096",
                    help="comma list of world sizes (8 ranks per host)")
    ap.add_argument("--per-host", type=int, default=8)
    ap.add_argument("--policies", default="par-trees,milp,ring,hier")
    ap.add_argument("--degrade", type=float, default=0.25,
                    help="bandwidth factor for the degraded host pair (1.0 = healthy)")
    ap.add_argument("--exec", action="store_true", dest="exec_",
                    help="also execute each policy's allreduce on a virtual pod")
    ap.add_argument("--replay-scale", action="store_true",
                    help="also emit replay-scaling rows (--replay-worlds x "
                    "replay wall-ms on the vectorized engine, budget-stamped)")
    ap.add_argument("--replay-worlds",
                    default=",".join(str(w) for w in REPLAY_WORLDS),
                    help="replay-scaling world grid")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    rows: List[dict] = []
    if args.replay_scale:
        for world in (int(w) for w in args.replay_worlds.split(",") if w):
            rows.append(bench_replay(world))
    for world in (int(w) for w in args.worlds.split(",") if w):
        if world % args.per_host:
            raise SystemExit(f"world {world} must divide per-host {args.per_host}")
        hosts = world // args.per_host
        degraded = (0, 1) if args.degrade < 1.0 and hosts >= 2 else None
        policies = [p for p in args.policies.split(",") if p]
        # matrix policies stop at MATRIX_POLICY_MAX_WORLD: beyond it the
        # flat synthesis (and the world² matrix build feeding it) is
        # minutes of wall time — the sketch policy exists exactly because
        # that does not scale.  Explicit skip rows keep the curve honest.
        need_matrices = any(p != "hier" for p in policies)
        if need_matrices and world <= MATRIX_POLICY_MAX_WORLD:
            ip_table, bw, lat = synthetic_topology(
                hosts, args.per_host, degraded_pair=degraded,
                degrade_factor=args.degrade,
            )
        else:
            ip_table, bw, lat = synthetic_ip_table(hosts, args.per_host), None, None
        for policy in policies:
            if policy != "hier" and bw is None:
                rows.append({
                    "world": world, "hosts": hosts, "policy": policy,
                    "skipped": (
                        f"world {world} > {MATRIX_POLICY_MAX_WORLD}: flat "
                        "synthesis over dense profile matrices exceeds the "
                        "budget by orders of magnitude at this scale "
                        "(the hier rows carry the curve)"
                    ),
                })
                continue
            if policy == "hier" and hosts < 2:
                rows.append({
                    "world": world, "hosts": hosts, "policy": policy,
                    "skipped": "single host: no hierarchy to sketch",
                })
                continue
            row = bench_policy(policy, ip_table, bw, lat)
            row["degrade_factor"] = args.degrade if degraded else 1.0
            rows.append(row)
            if args.exec_:
                rows.append(exec_relative_busbw(policy, ip_table, bw, lat))

    for r in rows:
        if args.json:
            print(json.dumps(r))
        else:
            print(r)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
