"""Collective busbw/algbw sweep — the nccl-tests analog for the TPU engine.

Measures every primitive the engine exposes across a message-size sweep and
reports nccl-tests-style numbers (nccl-perf/benchmark/PERFORMANCE.md):

    algbw = bytes_moved / time
    busbw = algbw × correction_factor

with the standard per-collective correction factors — AllReduce ``2(n-1)/n``,
ReduceScatter/AllGather/AllToAll ``(n-1)/n``, Broadcast/Reduce ``1`` — so
numbers are directly comparable to the reference's NCCL baselines
(nccl-perf/tree/report_allreduce.txt) and to any nccl-tests run.

Three allreduce implementations are swept side by side:

* ``xla`` — the ``lax.psum`` fast path (XLA's own ICI schedule),
* ``strategy`` — the synthesized masked-ppermute tree schedule,
* ``pallas_ring`` — the hand-written Pallas ring kernel.

Bytes accounting per collective (``b`` = per-rank payload bytes =
elements × dtype itemsize, ``w`` = world): allreduce/broadcast/reduce move
``b`` bytes per rank; all_gather's and all_to_all's payload is the full
``b·w`` exchanged volume; reduce_scatter's is its ``b`` input per rank.
``--dtype`` sets the payload element type (default float32, the
nccl-tests convention).

Usage (real TPU or the virtual CPU pod)::

    python -m benchmarks.collectives --world 8 --sizes 4K,1M,16M --iters 20
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

#: busbw = algbw × factor(world); nccl-perf/benchmark/PERFORMANCE.md:1-140
BUS_FACTORS: Dict[str, Callable[[int], float]] = {
    "allreduce": lambda w: 2 * (w - 1) / w,
    "reduce_scatter": lambda w: (w - 1) / w,
    "all_gather": lambda w: (w - 1) / w,
    "all_to_all": lambda w: (w - 1) / w,
    "broadcast": lambda w: 1.0,
    "reduce": lambda w: 1.0,
}


@dataclasses.dataclass
class BenchResult:
    collective: str
    impl: str
    size_bytes: int  # bytes moved (see module docstring accounting)
    world: int
    time_us: float  # median per-op wall time
    algbw_gbps: float
    busbw_gbps: float
    dtype: str = "float32"
    #: strategy shape behind "strategy"-impl rows, e.g. "ring x8 (merged)";
    #: "" for strategy-independent impls (xla, pallas_ring)
    strategy: str = ""

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def parse_size(text: str) -> int:
    """``"4K" → 4096``; accepts K/M/G suffixes (powers of 1024) or raw ints."""
    text = text.strip().upper()
    mult = 1
    if text and text[-1] in "KMG":
        mult = {"K": 1024, "M": 1024**2, "G": 1024**3}[text[-1]]
        text = text[:-1]
    return int(float(text) * mult)


def _format_size(nbytes: int) -> str:
    for unit, div in (("G", 1024**3), ("M", 1024**2), ("K", 1024)):
        if nbytes >= div and nbytes % div == 0:
            return f"{nbytes // div}{unit}"
    return str(nbytes)


def _time_op(fn: Callable[[], jnp.ndarray], iters: int, warmup: int) -> float:
    """Median wall-clock seconds per op, after ``warmup`` compile/cache calls."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return statistics.median(samples)


def _make_ops(engine, elems: int, dtype=jnp.float32) -> Dict[str, tuple]:
    """(callable, bytes_moved) per (collective, impl) for one message size.

    On a two-level mesh the engine routes reduce/broadcast through the
    hierarchical schedule regardless of ``active_gpus`` (no XLA fastpath
    there), so emitting both an "xla" and a "strategy" row would time the
    SAME compiled function twice and present the copy as a baseline — only
    the genuinely distinct surfaces are swept.
    """
    world = engine.world_size
    itemsize = jnp.dtype(dtype).itemsize
    rng = np.random.default_rng(elems)
    # pre-place the payload with the engine's sharding: the timed region must
    # cover the collective alone, not a per-call reshard of the input
    sharding = NamedSharding(engine.mesh, P(engine.axis_name))
    if jnp.issubdtype(dtype, jnp.integer):
        host = rng.integers(-8, 8, size=(world, elems))
    else:
        host = rng.normal(size=(world, elems))
    flat = jax.device_put(jnp.asarray(host, dtype), sharding)
    per_rank = elems * itemsize
    total = per_rank * world

    two_level = getattr(engine, "two_level", False)
    # gather/scatter route hierarchically on a (dcn, ici) mesh — label the
    # rows with the impl that actually runs, not the flat default
    gs_impl = "two_level" if two_level else "xla"
    composed = False
    if two_level:
        from adapcc_tpu.strategy.hierarchy import plan_of

        plan = plan_of(engine.strategy)
        composed = plan is not None and plan.pod_algo == "rs-ag"
    ops: Dict[str, tuple] = {}
    if composed:
        # a composed two-level plan outranks the GSPMD fastpath by design
        # (DCN-volume control is the point), so the bare call IS the
        # composed plan — an "xla" row here would time the same program
        # under a baseline label.  The flat-baseline arm comes from the
        # projected (non --hier) invocation.
        ops[("allreduce", "two_level_composed")] = (
            lambda: engine.all_reduce(flat, active_gpus=list(range(world))),
            per_rank,
        )
    else:
        ops[("allreduce", "xla")] = (lambda: engine.all_reduce(flat), per_rank)
        ops[("allreduce", "strategy")] = (
            lambda: engine.all_reduce(flat, active_gpus=list(range(world))),
            per_rank,
        )
    ops[("all_gather", gs_impl)] = (lambda: engine.all_gather(flat), total)
    ops[("reduce_scatter", gs_impl)] = (
        lambda: engine.reduce_scatter(flat), per_rank,
    )
    # subset rows: one rank masked out — regression-pins the cost of the
    # active-mask relay path on the gather/scatter primitives (VERDICT r4
    # item 3); same bytes accounting as the full-world rows.  world >= 2
    # only: at world=1 the "subset" would be empty and the rows would time
    # an all-zeros identity program masquerading as the relay path
    subset = list(range(world - 1))
    if world >= 2:
        ops[("all_gather", "subset")] = (
            lambda: engine.all_gather(flat, active_gpus=subset), total,
        )
        if elems % world == 0:
            ops[("reduce_scatter", "subset")] = (
                lambda: engine.reduce_scatter(flat, active_gpus=subset), per_rank,
            )
    if not two_level:
        ops[("allreduce", "pallas_ring")] = (
            lambda: engine.ring_allreduce(flat), per_rank,
        )
        if elems % world == 0:
            ops[("reduce_scatter", "pallas_ring")] = (
                lambda: engine.ring_reduce_scatter(flat), per_rank,
            )
        from adapcc_tpu.comm.pallas_ring import _tile_elems

        if elems % _tile_elems(dtype) == 0:
            ops[("all_gather", "pallas_ring")] = (
                lambda: engine.ring_all_gather(flat), total,
            )
        # active_gpus pins the schedule path; bare calls ride the XLA
        # fastpath (flat meshes only — see docstring)
        ops[("reduce", "xla")] = (lambda: engine.reduce(flat), per_rank)
        ops[("broadcast", "xla")] = (lambda: engine.broadcast(flat), per_rank)
    ops[("reduce", "strategy")] = (
        lambda: engine.reduce(flat, active_gpus=list(range(world))), per_rank,
    )
    ops[("broadcast", "strategy")] = (
        lambda: engine.broadcast(flat, active_gpus=list(range(world))), per_rank,
    )
    if elems % world == 0:
        blocked = jax.device_put(
            np.asarray(flat).reshape(world, world, elems // world), sharding
        )
        ops[("all_to_all", gs_impl)] = (lambda: engine.all_to_all(blocked), total)
        if world >= 2:
            ops[("all_to_all", "subset")] = (
                lambda: engine.all_to_all(blocked, active_gpus=subset), total,
            )
    return ops


def _strategy_label(engine) -> str:
    """Self-describing artifact rows: strategy shape + whether the engine's
    schedule path runs merged multi-tree rounds (both the flat and the
    two-level plan respect the ADAPCC_MERGE_ROUNDS kill-switch, so A/B rows
    are distinguishable)."""
    strat = engine.strategy
    label = f"{strat.synthesis or 'unnamed'} x{strat.num_trans}"
    if getattr(engine, "two_level", False):
        from adapcc_tpu.comm.two_level import _two_level_merged_plan

        merged = _two_level_merged_plan(
            strat, engine.num_slices, engine.ici_size
        ) is not None
    else:
        from adapcc_tpu.comm.engine import _merged_plan

        merged = _merged_plan(strat) is not None
    return label + (" (merged)" if merged else "")


def run_sweep(
    engine,
    sizes_bytes: Sequence[int],
    collectives: Optional[Sequence[str]] = None,
    impls: Optional[Sequence[str]] = None,
    iters: int = 20,
    warmup: int = 2,
    dtype=jnp.float32,
) -> List[BenchResult]:
    """Sweep ``sizes_bytes`` (per-rank payload bytes) over the engine's ops."""
    world = engine.world_size
    results: List[BenchResult] = []
    itemsize = jnp.dtype(dtype).itemsize
    for nbytes in sizes_bytes:
        elems = max(1, nbytes // itemsize)
        for (coll, impl), (fn, moved) in _make_ops(engine, elems, dtype).items():
            if collectives and coll not in collectives:
                continue
            if impls and impl not in impls:
                continue
            sec = _time_op(fn, iters, warmup)
            algbw = moved / sec / 1e9
            results.append(
                BenchResult(
                    collective=coll,
                    impl=impl,
                    size_bytes=moved,
                    world=world,
                    time_us=sec * 1e6,
                    algbw_gbps=algbw,
                    busbw_gbps=algbw * BUS_FACTORS[coll](world),
                    dtype=jnp.dtype(dtype).name,
                    strategy=(
                        _strategy_label(engine)
                        if impl in ("strategy", "two_level_composed")
                        else ""
                    ),
                )
            )
    return results


def format_table(results: Sequence[BenchResult]) -> str:
    """nccl-tests-style report table."""
    lines = [
        f"{'collective':<15}{'impl':<13}{'size':>8}{'time(us)':>12}"
        f"{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}"
    ]
    for r in results:
        lines.append(
            f"{r.collective:<15}{r.impl:<13}{_format_size(r.size_bytes):>8}"
            f"{r.time_us:>12.1f}{r.algbw_gbps:>13.3f}{r.busbw_gbps:>13.3f}"
        )
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> None:
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.strategy.ir import Strategy

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--world", type=int, default=0, help="mesh size (default: all devices)")
    ap.add_argument("--sizes", default="4K,64K,1M,16M", help="comma list, K/M/G suffixes")
    ap.add_argument("--collectives", default="", help="comma subset (default: all)")
    ap.add_argument(
        "--impls", default="",
        help="comma subset of xla,strategy,pallas_ring,subset "
        "(plus two_level on a --two-level mesh)",
    )
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--strategy", choices=["ring", "binary"], default="binary")
    ap.add_argument("--trans", type=int, default=1,
                    help="num_trans parallel trees (the reference's parallel-"
                    "transmission axis; >1 engages merged-round execution)")
    ap.add_argument("--dtype", choices=["f32", "bf16", "int8"], default="f32",
                    help="payload dtype (pallas_ring has per-dtype tiling)")
    ap.add_argument(
        "--wire-dtype", choices=["off", "bf16", "int8"], default="off",
        help="strategy wire codec for the IR path (the compiled program "
        "carries it, so an ADAPCC_WIRE_DTYPE pin of the same codec agrees "
        "instead of tripping the engine's conflict guard)",
    )
    ap.add_argument(
        "--two-level", default="",
        help='"DxI" (e.g. 2x4): hierarchical (dcn, ici) mesh — the strategy '
        "is ParTrees-synthesized over the slice layout and executes as "
        "ICI-collective + DCN master-tree rounds (comm/two_level.py)",
    )
    ap.add_argument(
        "--hier", action="store_true",
        help="under --two-level: synthesize the composed two-level plan "
        "(strategy/hierarchy.py — RS-within-pod, AR-across-leaders, "
        "AG-within-pod) instead of the ParTrees projection.  Allreduce "
        "then emits a single 'two_level_composed' row (the composed plan "
        "outranks the GSPMD fastpath, so there is no honest in-invocation "
        "'xla' baseline); the flat/projected arms come from a separate "
        "non --hier invocation — the A/B the hw battery's "
        "two_level_synth entry assembles (docs/HIERARCHY.md)",
    )
    ap.add_argument("--json", action="store_true", help="emit JSON lines instead of a table")
    args = ap.parse_args(argv)

    impls = [i for i in args.impls.split(",") if i] or None
    if args.two_level:
        import re

        from adapcc_tpu.comm.mesh import mesh_ip_table
        from adapcc_tpu.comm.two_level import build_two_level_mesh
        from adapcc_tpu.primitives import ALLREDUCE
        from adapcc_tpu.strategy.synthesizer import Synthesizer

        m = re.fullmatch(r"([1-9]\d*)x([1-9]\d*)", args.two_level.lower())
        if not m or int(m.group(1)) < 2 or int(m.group(2)) < 2:
            ap.error(
                f'--two-level expects "DxI" with D, I >= 2 (e.g. 2x4), '
                f"got {args.two_level!r}"
            )
        if args.world or args.strategy != "binary":
            ap.error(
                "--two-level is exclusive with --world/--strategy: the mesh "
                "size is DxI and the hierarchy is ParTrees-synthesized "
                "(--trans feeds the synthesizer's parallel_degree)"
            )
        if impls and "pallas_ring" in impls:
            ap.error(
                "pallas_ring is a flat-mesh kernel; drop it from --impls "
                "under --two-level"
            )
        dcn, ici = int(m.group(1)), int(m.group(2))
        world = dcn * ici
        mesh = build_two_level_mesh(dcn, ici)
        if args.hier:
            # the synthesized composed plan (docs/HIERARCHY.md): the
            # engine dispatches its RS→AR→AG phases for the strategy rows
            from adapcc_tpu.strategy.hierarchy import (
                HierarchySketch,
                synthesize_two_level,
            )

            plan = synthesize_two_level(
                HierarchySketch(dcn, ici, tuple(mesh_ip_table(mesh))),
                nbytes=4 << 20,
                num_trans=args.trans,
            )
            strategy = plan.strategy
        else:
            # uniform profile → ParTrees emits the masters-plus-chains
            # hierarchy that the two-level executor splits into ICI + DCN
            # phases
            ones = [[1.0] * world for _ in range(world)]
            strategy = Synthesizer(None, mesh_ip_table(mesh)).synthesize(
                ALLREDUCE, args.trans, 4 << 20, ones, ones
            )
        # impls stays None (no filter): _make_ops already emits only the
        # surfaces a two-level mesh supports (no pallas_ring rows there),
        # and a hardcoded label list would silently drop any future impl —
        # exactly the bug that once hid the two_level/subset rows
    else:
        if args.hier:
            ap.error(
                "--hier synthesizes a two-level plan; it needs --two-level "
                '"DxI" to name the pod layout'
            )
        world = args.world or len(jax.devices())
        mesh = build_world_mesh(world)
        strategy = (
            Strategy.ring(world, args.trans)
            if args.strategy == "ring"
            else Strategy.binary(world, args.trans)
        )
    if args.wire_dtype != "off":
        strategy.wire_dtype = args.wire_dtype
    engine = CollectiveEngine(mesh, strategy)

    results = run_sweep(
        engine,
        [parse_size(s) for s in args.sizes.split(",") if s],
        collectives=[c for c in args.collectives.split(",") if c] or None,
        impls=impls,
        iters=args.iters,
        warmup=args.warmup,
        dtype={"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[args.dtype],
    )
    if args.json:
        for r in results:
            print(r.to_json())
    else:
        print(f"# world={world} platform={jax.devices()[0].platform} dtype={args.dtype}")
        print(format_table(results))


if __name__ == "__main__":
    main()
