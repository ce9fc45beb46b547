"""Hardware-free collective sweep on the calibrated α-β simulator.

The simulated twin of :mod:`benchmarks.collectives`: the same collectives ×
sizes × strategies grid, but every number is a model *prediction* from
:mod:`adapcc_tpu.sim` instead of a wall-clock measurement — so the sweep
runs (and ranks the schedule levers) where no chip is attached.

Rows carry ``"mode": "simulated"`` and ``pred_time_us`` (never ``time_us``)
so a reader — human or the battery post-processor — can never mistake a
prediction for a measurement.  Predictions are anchored to the last good
hardware round through the calibration artifact
(``topology/calibration.json``, see docs/SIMULATION.md); without one, the
deterministic synthetic defaults price the sweep.

The sweep is fully deterministic: the replay is analytic (no wall clock,
no RNG), and the ParTrees/flow-LP candidates are synthesized from the
calibrated link matrices, so two runs over the same calibration emit
byte-identical rows — the property the tier-1 rig asserts.

Usage (any backend, typically ``JAX_PLATFORMS=cpu``)::

    python -m benchmarks.sim_collectives --world 8 --sizes 4K,1M,16M --json
"""

from __future__ import annotations

import argparse
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

from adapcc_tpu.sim.calibrate import DEFAULT_CALIBRATION_PATH, load_or_default
from adapcc_tpu.sim.cost_model import (
    DCN,
    DEFAULT_HBM_BYTES_PER_S,
    LinkCostModel,
    collective_lower_bound,
    optimality_gap,
)
from adapcc_tpu.sim.replay import simulate_flow_broadcast, simulate_strategy
from adapcc_tpu.sim.vector import resolve_sim_engine
from adapcc_tpu.strategy.ir import Strategy

from benchmarks.collectives import BUS_FACTORS, parse_size

#: collectives the tree replay lowers (the engine's ppermute-schedule subset)
SIM_COLLECTIVES = ("allreduce", "reduce", "broadcast")

#: candidate schedules swept side by side, mirroring the measured sweep's
#: impl axis (xla/strategy/pallas_ring → here: schedule shapes); labels
#: match Synthesizer.candidates so artifact rows and sim-rank-stamped XML
#: group under one name ("partrees" is accepted as a CLI alias)
SIM_STRATEGIES = ("ring", "binary", "par-trees")

_STRATEGY_ALIASES = {"partrees": "par-trees"}


def _ip_table(world: int, hosts: int) -> List[str]:
    """Synthetic rank→ip table: ``world`` ranks over ``hosts`` hosts in
    contiguous runs (the launcher's placement)."""
    hosts = max(1, min(hosts, world))
    per = -(-world // hosts)
    return [f"10.0.0.{r // per}" for r in range(world)]


def _graphs_from_model(
    model: LinkCostModel,
) -> Tuple[List[List[float]], List[List[float]]]:
    """(bandwidth [GB/s], latency [s]) matrices for the synthesizers, read
    off the calibrated coefficients so candidate *shapes* see the same
    network the replay prices (one definition:
    :meth:`LinkCostModel.to_graphs`, shared with the online re-rank)."""
    return model.to_graphs()


def strategy_candidates(
    world: int,
    names: Sequence[str],
    model: LinkCostModel,
    ips: Optional[Dict[int, str]] = None,
    degree: int = 1,
) -> List[Tuple[str, Strategy]]:
    """Labeled candidate strategies for the sweep — the synthesizer's own
    candidate pool (so the sweep and the sim-rank policy can never drift),
    filtered to ``names``.  ParTrees is skipped (not fatal) when synthesis
    fails on a degenerate topology; Synthesizer.candidates handles that."""
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    if ips is None:
        # a calibration artifact may carry its own ip table — candidate
        # shapes must be synthesized for the network the replay prices
        ips = model.ips
    table = (
        [ips[r] for r in range(world)] if ips else _ip_table(world, 1)
    )
    bw, lat = _graphs_from_model(model)
    pool = dict(Synthesizer(None, table).candidates(degree, bw, lat))
    out: List[Tuple[str, Strategy]] = []
    for name in names:
        label = _STRATEGY_ALIASES.get(name, name)
        if label not in SIM_STRATEGIES:
            raise ValueError(
                f"unknown strategy {name!r}; expected one of {SIM_STRATEGIES}"
            )
        if label in pool:
            out.append((label, pool[label]))
    return out


def _solve_flow(world: int, model: LinkCostModel):
    """Flow-LP broadcast solution on the calibrated complete graph; None
    when the LP backend (scipy) is unavailable.  The LP depends only on the
    topology, so callers solve once and re-simulate per message size."""
    try:
        from adapcc_tpu.strategy.flow_lp import solve_broadcast_lp
    except ImportError:
        return None
    edges = [(s, d) for s in range(world) for d in range(world) if s != d]
    bandwidth = [
        1.0 / max(model.coeffs(s, d).beta, 1e-15) for s, d in edges
    ]
    try:
        return solve_broadcast_lp(world, edges, bandwidth)
    except Exception:
        return None


def _finish_row(row: dict, collective: str, world: int) -> dict:
    row["impl"] = "sim"
    row["busbw_gbps"] = round(
        row["algbw_gbps"] * BUS_FACTORS[collective](world), 6
    )
    return row


def sweep(
    world: int,
    sizes: Sequence[int],
    collectives: Sequence[str] = SIM_COLLECTIVES,
    strategies: Sequence[str] = SIM_STRATEGIES,
    model: Optional[LinkCostModel] = None,
    hosts: int = 1,
    degree: int = 1,
    flow_lp: bool = True,
) -> List[dict]:
    """The full prediction grid as artifact rows (pure function — the CLI
    and the battery fallback both call this)."""
    ips = (
        {r: ip for r, ip in enumerate(_ip_table(world, hosts))}
        if hosts > 1
        else None
    )
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    if ips is not None and model.ips is None:
        # the synthetic host split must actually price cross-host edges as
        # DCN; a calibration carrying its own ip table keeps it
        model = model.with_ips(ips)
    elif ips is not None and model.ips != ips:
        # candidate shapes and replay pricing must see the SAME host layout;
        # silently synthesizing for one network and pricing on another makes
        # the ranking meaningless
        raise ValueError(
            f"--hosts {hosts} conflicts with the host layout recorded in "
            f"the calibration ({model.source}); drop --hosts to sweep the "
            "calibrated layout"
        )
    candidates = strategy_candidates(world, strategies, model, ips, degree)
    flow = (
        _solve_flow(world, model)
        if flow_lp and "broadcast" in collectives
        else None
    )
    rows: List[dict] = []
    for collective in collectives:
        if collective not in SIM_COLLECTIVES:
            raise ValueError(
                f"unknown collective {collective!r}; "
                f"expected one of {SIM_COLLECTIVES}"
            )
        for nbytes in sizes:
            for label, strategy in candidates:
                timeline = simulate_strategy(
                    strategy, model, nbytes, collective, keep_transfers=False
                )
                row = _finish_row(timeline.to_row(), collective, world)
                row["strategy"] = label
                rows.append(row)
            if collective == "broadcast" and flow is not None:
                lp = _finish_row(
                    simulate_flow_broadcast(flow, model, nbytes).to_row(),
                    "broadcast", world,
                )
                lp["strategy"] = "flow-lp"
                rows.append(lp)
    if not rows:
        # an explicitly requested strategy that failed to synthesize (or an
        # empty grid) must not read as "ran fine, no data" — same
        # fail-loudly rule as collectives.py's --impls validation
        raise ValueError(
            f"sweep produced no rows: none of strategies={list(strategies)} "
            f"synthesized for world={world} and no flow-lp row applied"
        )
    for row in rows:
        row["calibration"] = model.source
    return rows


def ring_chunk_sweep(
    world: int,
    sizes: Sequence[int],
    chunk_sizes: Sequence[int],
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Predicted staged-ring rows over a chunk-size grid — the hardware-free
    regression artifact for ring chunk tuning (``make ring-sweep``).

    Each row prices the Pallas ring at one ``chunk_bytes`` staging
    granularity with :func:`adapcc_tpu.sim.cost_model.
    staged_ring_allreduce_time`, on the *bottleneck* ring link (a lockstep
    ring advances at its slowest hop).  The executed path and tile come from
    the kernel's own planner (:func:`adapcc_tpu.comm.pallas_ring.
    plan_ring_schedule` — pure planning, no kernel execution), so a sweep
    row can never disagree with what the data plane would actually run.
    Deterministic: same calibration → byte-identical rows.
    """
    from adapcc_tpu.comm.pallas_ring import plan_ring_schedule
    from adapcc_tpu.sim.cost_model import (
        bottleneck_ring_coeffs,
        staged_ring_allreduce_time,
    )

    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    # lockstep ring: the slowest (src → src+1) hop paces every step
    coeffs = bottleneck_ring_coeffs(model, world)
    rows: List[dict] = []
    for nbytes in sizes:
        for chunk in chunk_sizes:
            plan = plan_ring_schedule(
                -(-int(nbytes) // 4), "float32", world, int(chunk)
            )
            # both paths execute the same 2(w−1)-step ring walk, so both are
            # priced with the staged model; the vmem path just pays no HBM
            # staging (payload already VMEM-resident) — pricing them with
            # different schedule shapes would invert the vmem/stream knee
            seconds = staged_ring_allreduce_time(
                world, nbytes, coeffs, plan.stage_bytes,
                hbm_bytes_per_s=(
                    float("inf") if plan.path == "vmem" else
                    DEFAULT_HBM_BYTES_PER_S
                ),
            )
            algbw = nbytes / seconds / 1e9 if seconds > 0 else 0.0
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "pallas_ring",
                "strategy": "ring",
                "world": world,
                "size_bytes": int(nbytes),
                "chunk_bytes": int(chunk),
                "ring_path": plan.path,
                "stage_bytes": plan.stage_bytes,
                "n_tiles": plan.n_tiles,
                "vmem_bound_bytes": plan.vmem_bound_bytes,
                "pred_time_us": round(seconds * 1e6, 3),
                "algbw_gbps": round(algbw, 6),
                "busbw_gbps": round(algbw * BUS_FACTORS["allreduce"](world), 6),
                "calibration": model.source,
            })
    if not rows:
        raise ValueError(
            f"ring sweep produced no rows: sizes={list(sizes)} "
            f"chunks={list(chunk_sizes)}"
        )
    return rows


def wire_dtype_sweep(
    world: int,
    sizes: Sequence[int],
    wire_dtypes: Sequence[str],
    model: Optional[LinkCostModel] = None,
    block_size: Optional[int] = None,
) -> List[dict]:
    """Predicted wire-codec rows over the allreduce ring — the hardware-free
    regression artifact for codec selection (``make quant-bench``).

    Each row prices the quantized ppermute ring at one wire dtype with
    :func:`adapcc_tpu.sim.cost_model.quantized_ring_allreduce_time` — the
    exact term the sim-rank policy uses to set ``Strategy.wire_dtype`` — on
    the bottleneck ring link (a lockstep ring advances at its slowest hop).
    ``chosen`` marks the dtype :func:`choose_wire_dtype` would commit for
    that size, so the artifact shows not just the curve but the decision.
    Deterministic: same calibration → byte-identical rows.
    """
    from adapcc_tpu.quant import DEFAULT_BLOCK_SIZE, get_codec
    from adapcc_tpu.sim.cost_model import (
        bottleneck_ring_coeffs,
        choose_wire_dtype,
        quantized_ring_allreduce_time,
        wire_bytes_per_element,
    )

    if block_size is None:
        block_size = DEFAULT_BLOCK_SIZE
    for wd in wire_dtypes:
        get_codec(wd)  # loud on a typo'd codec, before any row is emitted
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, world)
    rows: List[dict] = []
    for nbytes in sizes:
        chosen, _ = choose_wire_dtype(
            world, nbytes, coeffs, block_size, candidates=tuple(wire_dtypes)
        )
        for wd in wire_dtypes:
            seconds = quantized_ring_allreduce_time(
                world, nbytes, coeffs, wd, block_size
            )
            algbw = nbytes / seconds / 1e9 if seconds > 0 else 0.0
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "quant_ring",
                "strategy": "ring",
                "world": world,
                "size_bytes": int(nbytes),
                "wire_dtype": wd,
                "block_size": int(block_size),
                "wire_bytes_per_elem": round(
                    wire_bytes_per_element(wd, block_size), 6
                ),
                "chosen": wd == chosen,
                "pred_time_us": round(seconds * 1e6, 3),
                "algbw_gbps": round(algbw, 6),
                "busbw_gbps": round(algbw * BUS_FACTORS["allreduce"](world), 6),
                "calibration": model.source,
            })
    if not rows:
        raise ValueError(
            f"wire-dtype sweep produced no rows: sizes={list(sizes)} "
            f"wire_dtypes={list(wire_dtypes)}"
        )
    return rows


def fused_wire_sweep(
    world: int,
    sizes: Sequence[int],
    chunk_sizes: Sequence[int],
    wire_dtypes: Sequence[str] = ("bf16", "int8"),
    model: Optional[LinkCostModel] = None,
    block_size: Optional[int] = None,
) -> List[dict]:
    """Predicted fused-vs-unfused codec rows over (size × wire_dtype ×
    chunk_bytes) — the hardware-free regression artifact for the fused
    quantized streaming ring (``make fused-bench``, docs/RING.md §5).

    Each row prices the SAME payload both ways on the bottleneck ring
    link: ``pred_fused_us`` with :func:`adapcc_tpu.sim.cost_model.
    fused_quantized_ring_allreduce_time` (codec inside the staged kernel,
    per-tile codec overlapped with RDMA) at the planner-resolved tile for
    that ``chunk_bytes``, and ``pred_unfused_us`` with
    :func:`quantized_ring_allreduce_time` (the ppermute reroute's serial
    codec passes).  ``fused_faster`` flags the winner per row and
    ``crossover_bytes`` stamps, per (wire_dtype, chunk) curve, the
    smallest swept size where the fused path wins (None when it never
    does — small payloads pay the per-tile α and the exposed codec
    fill/drain).  The executed path/tile come from
    :func:`adapcc_tpu.comm.pallas_ring.plan_ring_schedule`, so a row can
    never claim a geometry the data plane would not run.  Deterministic:
    same calibration → byte-identical rows.
    """
    from adapcc_tpu.comm.pallas_ring import (
        fused_wire_unsupported_reason,
        plan_ring_schedule,
    )
    from adapcc_tpu.quant import DEFAULT_BLOCK_SIZE
    from adapcc_tpu.sim.cost_model import (
        bottleneck_ring_coeffs,
        fused_quantized_ring_allreduce_time,
        quantized_ring_allreduce_time,
        wire_bytes_per_element,
    )

    if block_size is None:
        block_size = DEFAULT_BLOCK_SIZE
    for wd in wire_dtypes:
        reason = fused_wire_unsupported_reason("float32", wd, block_size)
        if reason is not None:
            # loud on off/unknown/ungeometric codecs before any row exists
            raise ValueError(f"fused sweep cannot price {wd!r}: {reason}")
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    from adapcc_tpu.sim.cost_model import DEFAULT_HBM_BYTES_PER_S

    coeffs = bottleneck_ring_coeffs(model, world)
    sizes = [int(s) for s in sizes]

    def fused_pred(nbytes: int, wd: str, chunk: int):
        """(plan, fused seconds) with the tuner prior's exact pricing rule
        — vmem plans pay no HBM streaming (the payload is VMEM-resident),
        so the artifact and prior_time can never disagree on a ranking."""
        plan = plan_ring_schedule(
            nbytes // 4, "float32", world, int(chunk),
            wire_dtype=wd, block_size=block_size,
        )
        hbm = (
            float("inf") if plan.path == "vmem" else DEFAULT_HBM_BYTES_PER_S
        )
        return plan, fused_quantized_ring_allreduce_time(
            world, nbytes, coeffs, plan.stage_bytes, wd, block_size,
            hbm_bytes_per_s=hbm,
        )

    # price every cell exactly once; rows and crossovers read the dicts
    preds = {
        (s, wd, int(chunk)): fused_pred(s, wd, chunk)
        for s in sizes for wd in wire_dtypes for chunk in chunk_sizes
    }
    unfused = {
        (s, wd): quantized_ring_allreduce_time(world, s, coeffs, wd, block_size)
        for s in sizes for wd in wire_dtypes
    }
    rows: List[dict] = []
    crossover: Dict[Tuple[str, int], Optional[int]] = {
        (wd, int(chunk)): next(
            (
                s for s in sorted(sizes)
                if preds[(s, wd, int(chunk))][1] < unfused[(s, wd)]
            ),
            None,
        )
        for wd in wire_dtypes for chunk in chunk_sizes
    }
    for nbytes in sizes:
        for wd in wire_dtypes:
            unfused_s = unfused[(nbytes, wd)]
            for chunk in chunk_sizes:
                plan, fused_s = preds[(nbytes, wd, int(chunk))]
                algbw = nbytes / fused_s / 1e9 if fused_s > 0 else 0.0
                rows.append({
                    "mode": "simulated",
                    "collective": "allreduce",
                    "impl": "fused_ring",
                    "strategy": "ring",
                    "world": world,
                    "size_bytes": int(nbytes),
                    "wire_dtype": wd,
                    "block_size": int(block_size),
                    "chunk_bytes": int(chunk),
                    "ring_path": plan.path,
                    "stage_bytes": plan.stage_bytes,
                    "wire_stage_bytes": plan.wire_stage_bytes,
                    "scale_slot_bytes": plan.scale_slot_bytes,
                    "vmem_bound_bytes": plan.vmem_bound_bytes,
                    "wire_bytes_per_elem": round(
                        wire_bytes_per_element(wd, block_size), 6
                    ),
                    "pred_fused_us": round(fused_s * 1e6, 3),
                    "pred_unfused_us": round(unfused_s * 1e6, 3),
                    "fused_faster": fused_s < unfused_s,
                    "crossover_bytes": crossover[(wd, int(chunk))],
                    "algbw_gbps": round(algbw, 6),
                    "busbw_gbps": round(
                        algbw * BUS_FACTORS["allreduce"](world), 6
                    ),
                    "calibration": model.source,
                })
    if not rows:
        raise ValueError(
            f"fused sweep produced no rows: sizes={list(sizes)} "
            f"chunks={list(chunk_sizes)} wire_dtypes={list(wire_dtypes)}"
        )
    return rows


def latency_sweep(
    world: int,
    sizes: Sequence[int],
    algos: Sequence[str] = ("ring", "rd", "tree"),
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Predicted allreduce-algorithm rows over a size grid spanning the
    ring↔recursive-doubling crossover — the hardware-free regression
    artifact for the latency-bound regime (``make latency-bench``,
    docs/LATENCY.md).

    Each row prices one (size, algorithm) cell on the bottleneck ring link
    (the pacing rule every ring-shaped pricing shares): ``ring`` with the
    classic ``2·(p−1)·(α + β·n/p)`` term, ``rd`` with
    :func:`adapcc_tpu.sim.cost_model.recursive_doubling_allreduce_time`
    (hop-serialized recursive halving/doubling), ``tree`` as two
    single-shot binomial phases.  ``chosen`` marks the algorithm
    :func:`choose_allreduce_algo` would commit for that size — the sized
    decision ``ADAPCC_COLL_ALGO=auto`` executes — and every row stamps
    ``crossover_bytes`` (ring vs rd break-even; ``None`` when rd never
    loses, i.e. β = 0).  Deterministic: same calibration → byte-identical
    rows.
    """
    from adapcc_tpu.sim.cost_model import (
        COLL_ALGO_CANDIDATES,
        allreduce_crossover_bytes,
        bottleneck_ring_coeffs,
        choose_allreduce_algo,
    )

    algos = [a.strip() for a in algos if str(a).strip()]
    bad = [a for a in algos if a not in COLL_ALGO_CANDIDATES]
    if bad:
        raise ValueError(
            f"unknown algorithm(s) {bad}; expected a subset of "
            f"{COLL_ALGO_CANDIDATES}"
        )
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, world)
    crossover = allreduce_crossover_bytes(world, coeffs)
    crossover_field = (
        None if crossover == float("inf") else int(round(crossover))
    )
    rows: List[dict] = []
    for nbytes in sizes:
        chosen, times = choose_allreduce_algo(
            world, int(nbytes), coeffs, candidates=tuple(algos)
        )
        for algo in algos:
            seconds = times[algo]
            algbw = nbytes / seconds / 1e9 if seconds > 0 else 0.0
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "latency",
                "strategy": "ring",
                "world": world,
                "size_bytes": int(nbytes),
                "algo": algo,
                "chosen": algo == chosen,
                "sub_crossover": float(nbytes) < crossover,
                "crossover_bytes": crossover_field,
                "pred_time_us": round(seconds * 1e6, 3),
                "algbw_gbps": round(algbw, 6),
                "busbw_gbps": round(
                    algbw * BUS_FACTORS["allreduce"](world), 6
                ),
                "calibration": model.source,
            })
    if not rows:
        raise ValueError(
            f"latency sweep produced no rows: sizes={list(sizes)} "
            f"algos={list(algos)}"
        )
    return rows


#: schedule-sweep program grid: the three hand-written planes re-emitted
#: as compiler IR, plus the pipelined bidirectional schedule only the IR
#: can express (adapcc_tpu/compiler/synthesize.py)
SCHEDULE_PROGRAMS = ("ring", "rd", "tree", "pipelined")


def schedule_sweep(
    world: int,
    sizes: Sequence[int],
    programs: Sequence[str] = SCHEDULE_PROGRAMS,
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Predicted rows for IR-lowered schedule programs over a size grid —
    the hardware-free regression artifact for the schedule compiler
    (``make compiler-bench``, docs/COMPILER.md).

    Each row prices one (size, program) cell twice: ``pred_time_us`` is the
    verified :class:`~adapcc_tpu.compiler.ScheduleProgram` under
    :func:`~adapcc_tpu.sim.cost_model.schedule_program_time` (barrier
    rounds, coalesced per-link bytes, full-duplex fully-connected), and
    ``legacy_pred_time_us`` is the same algorithm's hand-written plane
    pricing (the classic ring term / ``recursive_doubling_allreduce_time``
    / ``2 × binomial_tree_time``), so drift between the IR pricing and the
    plane pricing is visible in one artifact.  The ``pipelined`` program
    has no legacy plane — that is the compiler's point — so its row stamps
    ``legacy_pred_time_us = None`` and ``lockstep_ring_us`` instead, with
    ``beats_lockstep_ring`` flagging the bandwidth-bound win.  Every
    program passes :func:`~adapcc_tpu.compiler.verify_program` before it is
    priced.  Deterministic: same calibration → byte-identical rows.

    Each row also carries the optimizer A/B (``compiler/optimize.py``):
    ``dispatches`` / ``opt_dispatches`` are the naive and optimized
    programs' static collective dispatch counts from the lowering's color
    plan, ``opt_pred_time_us`` prices the optimized program with the
    per-dispatch launch term set to the calibrated α (the overhead each
    coalesced ppermute saves), ``opt_speedup`` is naive-priced-with-α over
    that, and ``opt_faster`` flags a strict win.  ``passes`` and
    ``opt_fingerprint`` record what rewrote and what executes — empty /
    equal to ``program_fingerprint`` for programs the optimizer leaves
    alone (the segmented ring is already one dispatch per round).
    """
    from adapcc_tpu.compiler import (
        dispatch_count,
        optimize_program,
        pipelined_allreduce_program,
        rd_allreduce_program,
        ring_allreduce_program,
        tree_allreduce_program,
        verify_program,
    )
    from adapcc_tpu.sim.cost_model import (
        binomial_tree_time,
        bottleneck_ring_coeffs,
        quantized_ring_allreduce_time,
        recursive_doubling_allreduce_time,
        ring_allreduce_time,
        schedule_program_time,
    )

    programs = [p.strip() for p in programs if str(p).strip()]
    bad = [p for p in programs if p not in SCHEDULE_PROGRAMS]
    if bad:
        raise ValueError(
            f"unknown program(s) {bad}; expected a subset of "
            f"{SCHEDULE_PROGRAMS}"
        )
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, world)

    builders = {
        "ring": lambda: ring_allreduce_program(world),
        "rd": lambda: rd_allreduce_program(world),
        "tree": lambda: tree_allreduce_program(world),
        "pipelined": lambda: pipelined_allreduce_program(world),
    }
    legacy = {
        # the segmented-ring plane's own term, 2(w−1)·(α + β·n/w) — the IR
        # re-emission must reproduce it exactly, and the row shows it does
        "ring": lambda n: quantized_ring_allreduce_time(world, n, coeffs, "off"),
        "rd": lambda n: recursive_doubling_allreduce_time(world, n, coeffs),
        "tree": lambda n: 2.0 * binomial_tree_time(world, n, coeffs),
        "pipelined": None,
    }
    rows: List[dict] = []
    for name in programs:
        prog = builders[name]()
        verify_program(prog)
        fp = prog.fingerprint()
        # the full canonical pipeline, independent of the ambient
        # ADAPCC_IR_OPT, so the artifact is byte-deterministic
        opt = optimize_program(prog, passes=["dce", "fuse_codec", "coalesce"])
        naive_dispatches = dispatch_count(prog)
        opt_dispatches = dispatch_count(opt)
        for nbytes in sizes:
            seconds = schedule_program_time(prog, float(nbytes), coeffs)
            algbw = nbytes / seconds / 1e9 if seconds > 0 else 0.0
            legacy_fn = legacy[name]
            legacy_us = (
                round(legacy_fn(float(nbytes)) * 1e6, 3)
                if legacy_fn is not None else None
            )
            # the optimizer gap, priced with the launch-overhead term the
            # default model coalesces away: one α per collective dispatch
            naive_with_launch = schedule_program_time(
                prog, float(nbytes), coeffs, per_dispatch_s=coeffs.alpha
            )
            opt_with_launch = schedule_program_time(
                opt, float(nbytes), coeffs, per_dispatch_s=coeffs.alpha
            )
            row = {
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "ir",
                "strategy": prog.name,
                "program_fingerprint": fp,
                "world": world,
                "size_bytes": int(nbytes),
                "chunks": prog.chunks,
                "rounds": prog.num_rounds,
                "pred_time_us": round(seconds * 1e6, 3),
                "legacy_pred_time_us": legacy_us,
                "algbw_gbps": round(algbw, 6),
                "busbw_gbps": round(
                    algbw * BUS_FACTORS["allreduce"](world), 6
                ),
                "dispatches": naive_dispatches,
                "opt_dispatches": opt_dispatches,
                "opt_fingerprint": opt.fingerprint(),
                "passes": list(opt.applied_passes),
                "opt_pred_time_us": round(opt_with_launch * 1e6, 3),
                "naive_launch_pred_time_us": round(naive_with_launch * 1e6, 3),
                "opt_speedup": round(
                    naive_with_launch / opt_with_launch, 6
                ) if opt_with_launch > 0 else None,
                "opt_faster": opt_with_launch < naive_with_launch,
                "calibration": model.source,
            }
            if name == "pipelined":
                lockstep = ring_allreduce_time(world, float(nbytes), coeffs)
                row["lockstep_ring_us"] = round(lockstep * 1e6, 3)
                row["beats_lockstep_ring"] = seconds < lockstep
            rows.append(row)
    if not rows:
        raise ValueError(
            f"schedule sweep produced no rows: sizes={list(sizes)} "
            f"programs={list(programs)}"
        )
    return rows


def pipe_sweep(
    sizes: Sequence[int],
    stages_grid: Sequence[int] = (2, 4),
    microbatch_grid: Sequence[int] = (2, 4, 8),
    fwd_us: float = 100.0,
    model: Optional[LinkCostModel] = None,
    engine: Optional[str] = None,
) -> List[dict]:
    """Predicted GPipe-vs-1F1B frontier over a (stages × microbatches ×
    hop-bytes) grid — the hardware-free regression artifact for the
    pipeline plane (``make pipe-bench``, docs/PIPELINE.md).

    Each cell builds the SAME objects the executor runs: the tick table
    (:func:`~adapcc_tpu.pipe.schedule.pipeline_schedule`), its emitted hop
    program (verified by :func:`~adapcc_tpu.compiler.verify_program`
    before pricing), and three prices per row — ``pred_step_us`` from the
    closed-form :func:`~adapcc_tpu.sim.cost_model.pipeline_step_time`
    (compute + hops over the calibrated link class), ``hop_program_us``
    from replaying the verified program through ``simulate_program``
    (engine funneled like every replay: ``ADAPCC_SIM_ENGINE``), and
    ``stash_bytes`` from the closed-form per-stage stash bound (max over
    stages).  The frontier's two invariants are visible per row:
    ``bubble_fraction`` depends only on (stages, microbatches) and
    shrinks as microbatches grow, and the 1F1B row at ``microbatches >
    stages − 1`` stamps ``memory_win_vs_gpipe`` — same ticks, smaller
    stash, the whole reason the schedule exists.  Deterministic: same
    calibration → byte-identical rows.
    """
    from adapcc_tpu.compiler import verify_program
    from adapcc_tpu.pipe.schedule import (
        PIPE_SCHEDULES,
        pipeline_program,
        pipeline_schedule,
    )
    from adapcc_tpu.sim.cost_model import (
        ICI,
        bottleneck_ring_coeffs,
        pipeline_bubble_fraction,
        pipeline_step_time,
        pipeline_stash_bytes,
    )
    from adapcc_tpu.sim.replay import simulate_program
    from adapcc_tpu.sim.vector import resolve_sim_engine
    from adapcc_tpu.tuner.policy import pipe_path

    stages_grid = [int(s) for s in stages_grid]
    microbatch_grid = [int(m) for m in microbatch_grid]
    bad = [s for s in stages_grid if s < 2]
    if bad:
        raise ValueError(
            f"pipe sweep stages must be >= 2 (a single stage has no "
            f"pipeline), got {bad}"
        )
    if any(m < 1 for m in microbatch_grid):
        raise ValueError(
            f"pipe sweep microbatches must be >= 1, got {microbatch_grid}"
        )
    if fwd_us < 0:
        raise ValueError(f"fwd_us must be >= 0, got {fwd_us}")
    if model is None:
        model = load_or_default(world=max(stages_grid))
    coeffs = bottleneck_ring_coeffs(model, model.world)

    rows: List[dict] = []
    for stages in stages_grid:
        # the hop fabric: one uniform class model at the calibration's
        # bottleneck coefficients, sized to the stage chain
        hop_model = LinkCostModel(
            stages, classes={ICI: coeffs}, source=model.source
        )
        for microbatches in microbatch_grid:
            gpipe_stash: Dict[int, int] = {}
            for schedule in PIPE_SCHEDULES:
                sched = pipeline_schedule(stages, microbatches, schedule)
                prog = pipeline_program(sched, tied_embedding=True)
                verify_program(prog)
                fp = prog.fingerprint()
                for nbytes in sizes:
                    step_s = pipeline_step_time(
                        stages, microbatches, fwd_us * 1e-6,
                        float(nbytes), coeffs,
                    )
                    # each program chunk carries one hop payload, so the
                    # replay's total is hop bytes × chunks
                    tl = simulate_program(
                        prog, hop_model, float(nbytes) * prog.chunks,
                        keep_transfers=False, engine=engine,
                        keep_links=False,
                    )
                    stash = max(
                        int(pipeline_stash_bytes(
                            stages, microbatches, schedule, s, nbytes
                        ))
                        for s in range(stages)
                    )
                    row = {
                        "mode": "simulated",
                        "collective": "pipeline",
                        "impl": pipe_path(schedule),
                        "schedule": schedule,
                        "stages": stages,
                        "microbatches": microbatches,
                        "size_bytes": int(nbytes),
                        "ticks": sched.num_ticks,
                        "rounds": prog.num_rounds,
                        "program_fingerprint": fp,
                        "bubble_fraction": round(
                            pipeline_bubble_fraction(stages, microbatches),
                            6,
                        ),
                        "pred_step_us": round(step_s * 1e6, 3),
                        "hop_program_us": round(tl.seconds * 1e6, 3),
                        "stash_bytes": stash,
                        "engine": resolve_sim_engine(engine, prog.world),
                        "calibration": model.source,
                    }
                    if schedule == "gpipe":
                        gpipe_stash[int(nbytes)] = stash
                    else:
                        row["memory_win_vs_gpipe"] = (
                            stash < gpipe_stash[int(nbytes)]
                        )
                    rows.append(row)
    if not rows:
        raise ValueError(
            f"pipe sweep produced no rows: sizes={list(sizes)} "
            f"stages={stages_grid} microbatches={microbatch_grid}"
        )
    return rows


def hier_sweep(
    sizes: Sequence[int],
    pods: Sequence[int] = (2, 4, 8),
    pod_sizes: Sequence[int] = (4, 8),
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Predicted two-level-vs-flat rows over the (pods × pod_size × size)
    grid — the hardware-free regression artifact for the hierarchical
    sketch synthesis (``make hier-bench``, docs/HIERARCHY.md §4).

    Each row prices the best composed two-level allreduce (both pod
    algorithms × their best leader schedule,
    :func:`adapcc_tpu.sim.cost_model.two_level_allreduce_time`) against
    the flat lockstep ring on the DCN bottleneck for one topology cell,
    stamping the winner in ``chosen`` and the pod count where the
    hierarchy starts paying in ``crossover_pods``
    (:func:`~adapcc_tpu.sim.cost_model.two_level_crossover_pods`).  Only
    the calibration's ICI/DCN *class* coefficients are read — the sweep
    grid names its own topologies, so the model's world is irrelevant
    (and world² state is never touched).  Deterministic: same calibration
    → byte-identical rows.
    """
    from adapcc_tpu.sim.cost_model import (
        DCN,
        ICI,
        choose_two_level,
        two_level_crossover_pods,
    )

    pods = [int(p) for p in pods]
    pod_sizes = [int(i) for i in pod_sizes]
    bad = [p for p in pods if p < 2] + [i for i in pod_sizes if i < 2]
    if bad:
        raise ValueError(
            f"hier sweep needs pods >= 2 and pod sizes >= 2, got pods="
            f"{pods} pod_sizes={pod_sizes}"
        )
    if model is None:
        model = load_or_default()
    ici, dcn = model.classes[ICI], model.classes[DCN]
    rows: List[dict] = []
    for num_pods in pods:
        for pod_size in pod_sizes:
            world = num_pods * pod_size
            for nbytes in sizes:
                chosen, times = choose_two_level(
                    num_pods, pod_size, int(nbytes), ici, dcn
                )
                two, flat = times["two_level"], times["flat"]
                algbw = (
                    int(nbytes) / two / 1e9 if two > 0 else 0.0
                )
                rows.append({
                    "mode": "simulated",
                    "collective": "allreduce",
                    "impl": "two_level",
                    "strategy": "two-level",
                    "world": world,
                    "pods": num_pods,
                    "pod_size": pod_size,
                    "size_bytes": int(nbytes),
                    "pred_two_level_us": round(two * 1e6, 3),
                    "pred_flat_us": round(flat * 1e6, 3),
                    "chosen": chosen,
                    "two_level_faster": chosen == "two_level",
                    "crossover_pods": two_level_crossover_pods(
                        pod_size, int(nbytes), ici, dcn
                    ),
                    "algbw_gbps": round(algbw, 6),
                    "busbw_gbps": round(
                        algbw * BUS_FACTORS["allreduce"](world), 6
                    ),
                    "calibration": model.source,
                })
    if not rows:
        raise ValueError(
            f"hier sweep produced no rows: sizes={list(sizes)} pods={pods} "
            f"pod_sizes={pod_sizes}"
        )
    return rows


def overlap_sweep(
    world: int,
    sizes: Sequence[int],
    accums: Sequence[int] = (1, 2, 4),
    bucket_caps_mb: Sequence[float] = (1.0, 4.0),
    compute_ratios: Sequence[float] = (0.25, 4.0),
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Predicted overlapped-step rows over (accum × bucket cap × overlap
    schedule) — the hardware-free regression artifact for the overlapped
    gradient sync (``make overlap-bench``, docs/OVERLAP.md §4).

    Each row prices one DDP step with :func:`adapcc_tpu.sim.cost_model.
    overlapped_step_time` on the bottleneck ring link (the pacing rule
    every other ring-shaped pricing shares).  The gradient is ``size``
    bytes split into equal buckets of at most ``bucket_cap_mb`` (the
    leaf-free proxy for ``build_bucket_plan``'s greedy fill); the step's
    compute is ``compute_ratio ×`` the baseline sync time, so the grid
    covers both the comm-bound (``ratio < 1``) and compute-bound regimes.
    For every comm-bound configuration the ``"bucket"`` schedule's
    ``exposed_comm_us`` is strictly below the ``"off"`` baseline's — the
    property the regression test pins.  Deterministic: same calibration →
    byte-identical rows.
    """
    from adapcc_tpu.sim.cost_model import (
        OVERLAP_MODE_CANDIDATES,
        bottleneck_ring_coeffs,
        overlapped_step_time,
    )

    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, world)
    rows: List[dict] = []
    for nbytes in sizes:
        for cap_mb in bucket_caps_mb:
            cap = max(1, int(cap_mb * 1024 * 1024))
            n_buckets = max(1, -(-int(nbytes) // cap))
            bucket_bytes = [nbytes / n_buckets] * n_buckets
            baseline = overlapped_step_time(
                world, nbytes, coeffs, 0.0, overlap="off",
                bucket_bytes=bucket_bytes,
            )["comm_s"]
            for accum in accums:
                for ratio in compute_ratios:
                    compute_s = ratio * baseline
                    for mode in OVERLAP_MODE_CANDIDATES:
                        if mode == "microbatch" and accum < 2:
                            continue  # no pipeline with one microbatch
                        r = overlapped_step_time(
                            world, nbytes, coeffs, compute_s,
                            accum=accum, overlap=mode,
                            bucket_bytes=bucket_bytes,
                        )
                        rows.append({
                            "mode": "simulated",
                            "collective": "ddp_step",
                            "impl": "overlap",
                            "world": world,
                            "size_bytes": int(nbytes),
                            "accum": int(accum),
                            "bucket_cap_mb": float(cap_mb),
                            "n_buckets": n_buckets,
                            "compute_ratio": float(ratio),
                            "comm_bound": ratio < 1.0,
                            "overlap": mode,
                            "pred_step_us": round(r["step_time_s"] * 1e6, 3),
                            "compute_us": round(r["compute_s"] * 1e6, 3),
                            "comm_us": round(r["comm_s"] * 1e6, 3),
                            "exposed_comm_us": round(
                                r["exposed_comm_s"] * 1e6, 3
                            ),
                            "fill_us": round(r["fill_s"] * 1e6, 3),
                            "drain_us": round(r["drain_s"] * 1e6, 3),
                            "calibration": model.source,
                        })
    if not rows:
        raise ValueError(
            f"overlap sweep produced no rows: sizes={list(sizes)} "
            f"accums={list(accums)} caps={list(bucket_caps_mb)}"
        )
    return rows


def fault_sweep(
    world: int,
    sizes: Sequence[int],
    hosts: int = 1,
    model: Optional[LinkCostModel] = None,
    heartbeat_timeout_s: float = 1.0,
    slowdown: float = 4.0,
) -> List[dict]:
    """Deterministic simulated failover rows — the hardware-free regression
    artifact for elastic fault tolerance (``make elastic-bench``,
    docs/ELASTIC.md).

    Two row families per payload size:

    - **summary** rows (``phase: "failover"``) price each injected fault
      shape end to end with :func:`adapcc_tpu.sim.cost_model.failover_cost`:
      detection latency (heartbeat timeout + half a step), the plan-swap
      stall both ways (``swap_cached_us`` — the standby cache hit — vs
      ``swap_cold_us`` — the recompile the cache exists to avoid), and the
      healthy / undetected / degraded steady states.  Scenarios:
      ``rank-down``, ``rank-slow`` and, on multi-host layouts
      (``hosts > 1``), ``host-down``.
    - **timeline** rows (``phase: "timeline"``) replay one canonical
      :class:`~adapcc_tpu.elastic.faults.FaultPlan` (rank dies → another
      straggles → both recover) step by step through
      :func:`adapcc_tpu.sim.replay.simulate_fault_plan`: per-step predicted
      collective cost under that step's fault state, with detection + swap
      stamped on the transition steps — the detection → swap → steady-state
      shape of one failover, as data.

    Deterministic: same calibration → byte-identical rows.
    """
    from adapcc_tpu.elastic.faults import FaultEvent, FaultPlan
    from adapcc_tpu.sim.cost_model import bottleneck_ring_coeffs, failover_cost
    from adapcc_tpu.sim.replay import simulate_fault_plan

    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    ips = (
        {r: ip for r, ip in enumerate(_ip_table(world, hosts))}
        if hosts > 1 else None
    )
    if ips is not None and model.ips is None:
        model = model.with_ips(ips)
    coeffs = bottleneck_ring_coeffs(model, world)
    per_host = -(-world // max(1, hosts))
    scenarios = [("rank-down", 1, None), ("rank-slow", 1, slowdown)]
    if hosts > 1 and per_host < world:
        scenarios.append(("host-down", per_host, None))

    # one canonical plan: a rank dies, another straggles, both recover —
    # the storyline the elastic acceptance test drives live
    plan = FaultPlan(
        [
            FaultEvent(step=2, kind="down", rank=world - 1),
            FaultEvent(step=3, kind="slow", rank=1, slowdown=slowdown),
            FaultEvent(step=6, kind="recover", rank=world - 1),
            FaultEvent(step=7, kind="recover", rank=1),
        ],
        world=world,
        label="canonical-failover",
    )
    strategy = Strategy.ring(world, ips=ips)

    rows: List[dict] = []
    for nbytes in sizes:
        for label, n_down, slow in scenarios:
            cost = failover_cost(
                world, nbytes, coeffs, n_down=n_down, slowdown=slow,
                heartbeat_timeout_s=heartbeat_timeout_s,
                standby_cached=True,
            )
            cold = failover_cost(
                world, nbytes, coeffs, n_down=n_down, slowdown=slow,
                heartbeat_timeout_s=heartbeat_timeout_s,
                standby_cached=False,
            )
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "elastic",
                "phase": "failover",
                "scenario": label,
                "world": world,
                "size_bytes": int(nbytes),
                "n_down": n_down,
                "slowdown": slow,
                "heartbeat_timeout_s": heartbeat_timeout_s,
                "detection_us": round(cost["detection_s"] * 1e6, 3),
                "swap_cached_us": round(cost["swap_s"] * 1e6, 3),
                "swap_cold_us": round(cold["swap_s"] * 1e6, 3),
                "healthy_us": round(cost["healthy_s"] * 1e6, 3),
                "undetected_us": round(cost["undetected_s"] * 1e6, 3),
                "degraded_us": round(cost["degraded_s"] * 1e6, 3),
                "degraded_ratio": round(cost["degraded_ratio"], 6),
                "failover_total_us": round(cost["failover_total_s"] * 1e6, 3),
                "calibration": model.source,
            })
        for step_row in simulate_fault_plan(
            strategy, model, nbytes, plan,
            heartbeat_timeout_s=heartbeat_timeout_s,
        ):
            row = step_row.to_row()
            row.update({
                "collective": "allreduce",
                "impl": "elastic",
                "phase": "timeline",
                "scenario": plan.label,
                "world": world,
                "size_bytes": int(nbytes),
                "calibration": model.source,
            })
            rows.append(row)
    if not rows:
        raise ValueError(f"fault sweep produced no rows: sizes={list(sizes)}")
    return rows


def chaos_sweep(
    world: int,
    sizes: Sequence[int],
    model: Optional[LinkCostModel] = None,
    periods: Sequence[float] = (0.25, 0.5, 1.0, 2.0),
    graces: Sequence[int] = (1, 2, 4),
    timeout_periods: int = 3,
    sweep_period_s: float = 0.25,
) -> List[dict]:
    """Deterministic supervised-failover rows — the hardware-free
    regression artifact for the autonomous supervisor (``make
    chaos-bench``, docs/SUPERVISOR.md).

    Two row families per payload size:

    - **detection** rows (``phase: "detection"``) price the out-of-band
      liveness machine over the (heartbeat period × grace) grid with
      :func:`adapcc_tpu.sim.cost_model.supervised_detection_latency_s`
      (suspicion after ``timeout_periods`` missed beats, confirmation
      after ``grace`` further periods, half a supervisor sweep to
      observe), next to the swap stall both ways and the degraded steady
      state from :func:`failover_cost` — so the period/grace trade
      (detection latency vs false-positive headroom, printed as
      ``confirm_window_s``, the longest SIGSTOP pause a rank survives
      undemoted) is data, not folklore;
    - **schedule** rows (``phase: "schedule"``) compile the canonical
      fault plan (rank dies → another straggles → both recover) into its
      cross-process chaos spelling via
      :meth:`~adapcc_tpu.elastic.faults.FaultPlan.chaos_schedule` — the
      SIGKILL/SIGSTOP-duty-cycle action list the multi-process drill
      delivers — and pins its deterministic shape (action counts, first
      kill offset, stop/cont pairing).

    Deterministic: same calibration → byte-identical rows.
    """
    from adapcc_tpu.elastic.faults import FaultEvent, FaultPlan
    from adapcc_tpu.sim.cost_model import (
        bottleneck_ring_coeffs,
        failover_cost,
        supervised_detection_latency_s,
    )

    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, world)
    slowdown = 4.0
    plan = FaultPlan(
        [
            FaultEvent(step=2, kind="down", rank=world - 1),
            FaultEvent(step=3, kind="slow", rank=1, slowdown=slowdown),
            FaultEvent(step=6, kind="recover", rank=world - 1),
            FaultEvent(step=7, kind="recover", rank=1),
        ],
        world=world,
        label="canonical-failover",
    )
    rows: List[dict] = []
    for nbytes in sizes:
        healthy = None
        for period in periods:
            timeout = timeout_periods * period
            for grace in graces:
                detect = supervised_detection_latency_s(
                    period, timeout, grace, sweep_period_s
                )
                cost = failover_cost(
                    world, nbytes, coeffs, n_down=1,
                    heartbeat_timeout_s=timeout, standby_cached=True,
                )
                cold = failover_cost(
                    world, nbytes, coeffs, n_down=1,
                    heartbeat_timeout_s=timeout, standby_cached=False,
                )
                healthy = cost["healthy_s"]
                rows.append({
                    "mode": "simulated",
                    "collective": "allreduce",
                    "impl": "supervisor",
                    "phase": "detection",
                    "world": world,
                    "size_bytes": int(nbytes),
                    "heartbeat_period_s": period,
                    "heartbeat_timeout_s": timeout,
                    "grace": int(grace),
                    "sweep_period_s": sweep_period_s,
                    "detection_us": round(detect * 1e6, 3),
                    # the false-positive headroom the grace window buys:
                    # a pause shorter than this never demotes the rank
                    "confirm_window_s": round(
                        timeout + grace * period, 9
                    ),
                    "swap_cached_us": round(cost["swap_s"] * 1e6, 3),
                    "swap_cold_us": round(cold["swap_s"] * 1e6, 3),
                    "degraded_ratio": round(cost["degraded_ratio"], 6),
                    # steady-state collectives burnt while undetected
                    "detection_steps_lost": round(detect / healthy, 1)
                    if healthy > 0 else None,
                    "calibration": model.source,
                })
        # the canonical plan's cross-process spelling at a step period of
        # one healthy collective (floored so the schedule stays sane on a
        # sub-microsecond sim step)
        step_period = max(float(healthy or 0.0), 0.05)
        schedule = plan.chaos_schedule(step_period)
        kills = [a for a in schedule if a.kind == "kill"]
        stops = [a for a in schedule if a.kind == "stop"]
        conts = [a for a in schedule if a.kind == "cont"]
        rows.append({
            "mode": "simulated",
            "collective": "allreduce",
            "impl": "supervisor",
            "phase": "schedule",
            "scenario": plan.label,
            "world": world,
            "size_bytes": int(nbytes),
            "step_period_s": round(step_period, 9),
            "actions": len(schedule),
            "kills": len(kills),
            "stops": len(stops),
            "conts": len(conts),
            "first_kill_s": round(kills[0].at_s, 9) if kills else None,
            "slowdown": slowdown,
            # the duty cycle's invariant: every stop has a cont after it
            "stop_cont_paired": len(stops) <= len(conts),
            "calibration": model.source,
        })
    if not rows:
        raise ValueError(f"chaos sweep produced no rows: sizes={list(sizes)}")
    return rows


def recovery_sweep(
    sizes: Sequence[int],
    worlds: Sequence[int] = (8, 32, 64),
    replicas: int = 1,
    save_interval_steps: int = 100,
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Deterministic durable-recovery rows — the hardware-free regression
    artifact for replicated ZeRO-1 shards vs a checkpoint reload (``make
    recovery-bench``, docs/RECOVERY.md §4).

    One row per (world × payload) cell, priced by
    :func:`adapcc_tpu.sim.cost_model.recovery_cost` on the calibration's
    ICI class coefficients (the replica piggyback rides ring-neighbor
    hops; the grid names its own worlds, so — like ``--hier-sweep`` — the
    model's world is irrelevant and no world² state is touched):

    - the per-step **replication overhead** next to the baseline step
      comm, with ``overhead_ok`` stamping the acceptance bound (< 5 % of
      step comm — holds from world=32 up at k=1, the default config: the
      shard shrinks as 1/world while step comm saturates at 2·nbytes);
    - the **repair** arm (one shard over one hop + warm plan swap, zero
      lost steps) against the **reload** arm (full state from shared
      storage + ``save_interval/2`` steps of re-done work), with
      ``repair_speedup`` and the failure-rate break-even.

    Deterministic: same calibration → byte-identical rows.
    """
    from adapcc_tpu.sim.cost_model import ICI, recovery_cost

    worlds = [int(w) for w in worlds]
    bad = [w for w in worlds if w < 2]
    if bad:
        raise ValueError(f"recovery sweep needs worlds >= 2, got {worlds}")
    if replicas < 1:
        raise ValueError(
            f"recovery sweep needs replicas >= 1, got {replicas} "
            "(replicas=0 prices nothing: replication is off)"
        )
    if model is None:
        model = load_or_default()
    coeffs = model.classes[ICI]
    rows: List[dict] = []
    for world in worlds:
        if replicas >= world:
            # an unreplicable cell (k >= world) is skipped LOUDLY in-band:
            # a silent drop would read as "priced that world" when nothing
            # was
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "recovery",
                "world": world,
                "replicas": replicas,
                "skipped": f"replicas={replicas} needs world > replicas",
                "calibration": model.source,
            })
            continue
        for nbytes in sizes:
            # fp32 Adam on an nbytes gradient: passed explicitly so the
            # emitted row and the priced times can never disagree about
            # what state size was modeled
            state_bytes = 3 * int(nbytes)
            cost = recovery_cost(
                world,
                int(nbytes),
                coeffs,
                state_bytes=float(state_bytes),
                replicas=replicas,
                save_interval_steps=save_interval_steps,
            )
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "recovery",
                "world": world,
                "size_bytes": int(nbytes),
                "state_bytes": state_bytes,
                "replicas": replicas,
                "save_interval_steps": int(save_interval_steps),
                "baseline_step_comm_us": round(
                    cost["baseline_step_comm_s"] * 1e6, 3
                ),
                "replication_overhead_us": round(
                    cost["replication_overhead_s"] * 1e6, 3
                ),
                "replication_overhead_ratio": round(
                    cost["replication_overhead_ratio"], 6
                ),
                # the acceptance bound: replica upkeep must stay in the
                # piggyback window's noise, not become a second collective
                "overhead_ok": cost["replication_overhead_ratio"] < 0.05,
                "replica_repair_us": round(cost["replica_repair_s"] * 1e6, 3),
                "ckpt_reload_us": round(cost["ckpt_reload_s"] * 1e6, 3),
                "repair_speedup": round(cost["repair_speedup"], 3),
                "overhead_break_even_steps": (
                    round(cost["overhead_break_even_steps"], 1)
                    if cost["overhead_break_even_steps"] != float("inf")
                    else None
                ),
                "calibration": model.source,
            })
    if not rows:
        raise ValueError(
            f"recovery sweep produced no rows: worlds={worlds} "
            f"sizes={list(sizes)}"
        )
    return rows


def adapt_sweep(
    world: int,
    sizes: Sequence[int],
    hosts: int = 2,
    model: Optional[LinkCostModel] = None,
    drift_factor: float = 2.0,
    drift_window: int = 4,
    drift_onset: int = 4,
    steps: int = 16,
    degrade: float = 8.0,
) -> List[dict]:
    """Deterministic closed-adaptation-loop rows — the hardware-free
    regression artifact for drift → re-calibration → re-rank → hot swap
    (``make adapt-bench``, docs/ADAPT.md).

    Two row families per payload size:

    - **timeline** rows replay one drift incident through the REAL
      :class:`~adapcc_tpu.adapt.DriftDetector`: per step, the observed
      dispatch time is the calibrated model's own prediction (healthy
      before ``drift_onset``, every DCN link ``degrade``× slower after —
      exactly what a live run's medians converge to), with the detector's
      ratio and fired bit stamped per step.  Detection lag (steps from
      onset to fire) falls out of the rows.
    - the **summary** row prices the incident end to end: the stale
      strategy's steady state under the degraded costs vs the re-ranked
      winner's (the sim-rank pass over the synthesizer's own candidate
      pool, flat-ring incumbent listed first), and the two one-time
      stalls — ``hot_swap_stall_us`` (the standby-cached epoch swap) vs
      ``full_rebuild_stall_us`` (probe traffic + re-synthesis + cold
      compile) via :func:`adapcc_tpu.sim.cost_model.adaptation_cost`, with
      each arm's break-even step count.  Hot-swap stall is strictly below
      the full rebuild's by construction — the acceptance property the
      regression test pins.

    Deterministic: no RNG, no wall clock — same calibration →
    byte-identical rows.
    """
    from adapcc_tpu import sim
    from adapcc_tpu.adapt import DriftDetector
    from adapcc_tpu.sim.cost_model import (
        DCN,
        LinkCostModel as _Model,
        adaptation_cost,
        bottleneck_ring_coeffs,
    )
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner.db import TuningDatabase, TuningKey, size_bucket
    from adapcc_tpu.tuner.policy import TuningPolicy

    if drift_onset < drift_window:
        raise ValueError(
            f"drift_onset ({drift_onset}) must be >= drift_window "
            f"({drift_window}): the detector needs one healthy window "
            "before the incident or the control property is untestable"
        )
    if steps <= drift_onset:
        raise ValueError(f"steps ({steps}) must exceed onset ({drift_onset})")
    if degrade <= 1.0:
        raise ValueError(f"degrade must be > 1, got {degrade}")
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    ips = {r: ip for r, ip in enumerate(_ip_table(world, max(2, hosts)))}
    if model.ips is None:
        model = model.with_ips(ips)
    else:
        ips = model.ips
    # the degraded network: every DCN link `degrade`x slower (class + any
    # per-link fits), ICI untouched — the inter-host drift the reference's
    # variability study measures
    classes = dict(model.classes)
    classes[DCN] = classes[DCN].scaled(degrade)
    links = {
        l: (c.scaled(degrade) if model.link_class_of(*l) == DCN else c)
        for l, c in model.links.items()
    }
    degraded_model = _Model(
        world, links=links, classes=classes, ips=ips,
        source=model.source + f"+dcn-x{degrade:g}",
    )

    def _pred(m: LinkCostModel, key: TuningKey, nbytes: int) -> float:
        return TuningPolicy(
            TuningDatabase(persist=False), world, "adapt-sweep", cost_model=m
        ).prior_time(key, nbytes)

    rows: List[dict] = []
    for nbytes in sizes:
        nbytes = int(nbytes)
        key = TuningKey(
            "allreduce", size_bucket(nbytes), world, "adapt-sweep",
            "xla", 0, "off",
        )
        detector = DriftDetector(
            world, "adapt-sweep", cost_model=model,
            factor=drift_factor, window=drift_window,
        )
        healthy_obs = _pred(model, key, nbytes)
        degraded_obs = _pred(degraded_model, key, nbytes)
        detection_step: Optional[int] = None
        for step in range(steps):
            obs = healthy_obs if step < drift_onset else degraded_obs
            detector.observe(key, obs, ts=float(step), nbytes=nbytes)
            report = detector.check()
            fired = report.drifted
            if fired and detection_step is None:
                detection_step = step
            signal = report.signals[0] if report.signals else None
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "adapt",
                "phase": "timeline",
                "world": world,
                "size_bytes": nbytes,
                "step": step,
                "degraded": step >= drift_onset,
                "observed_us": round(obs * 1e6, 3),
                "predicted_us": (
                    round(signal.reference_s * 1e6, 3) if signal else None
                ),
                "ratio": round(signal.ratio, 6) if signal else None,
                "fired": fired,
                "calibration": model.source,
            })
        # the re-rank: the synthesizer's own candidate pool under the
        # degraded costs, flat-ring incumbent (the stale strategy) first
        incumbent = Strategy.ring(world, 1, ips)
        candidates = [("incumbent", incumbent)] + strategy_candidates(
            world, SIM_STRATEGIES, degraded_model, ips, degree=1
        )
        ranked = sim.rank_candidates(
            candidates, degraded_model, nbytes, "allreduce"
        )
        stale = next(r.seconds for r in ranked if r.label == "incumbent")
        winner = ranked[0]
        cost = adaptation_cost(
            world, nbytes, bottleneck_ring_coeffs(model, world),
            stale_steady_s=stale, adapted_steady_s=winner.seconds,
        )
        rows.append({
            "mode": "simulated",
            "collective": "allreduce",
            "impl": "adapt",
            "phase": "summary",
            "world": world,
            "size_bytes": nbytes,
            "drift_factor": float(drift_factor),
            "drift_window": int(drift_window),
            "drift_onset_step": int(drift_onset),
            "detection_step": detection_step,
            "detection_lag_steps": (
                detection_step - drift_onset
                if detection_step is not None else None
            ),
            "degrade": float(degrade),
            "adapted_label": winner.label,
            "stale_steady_us": round(cost["stale_steady_s"] * 1e6, 3),
            "adapted_steady_us": round(cost["adapted_steady_s"] * 1e6, 3),
            "hot_swap_stall_us": round(cost["hot_swap_stall_s"] * 1e6, 3),
            "full_rebuild_stall_us": round(
                cost["full_rebuild_stall_s"] * 1e6, 3
            ),
            "hot_swap_break_even_steps": (
                round(cost["hot_swap_break_even_steps"], 3)
                if cost["hot_swap_break_even_steps"] != float("inf") else None
            ),
            "full_rebuild_break_even_steps": (
                round(cost["full_rebuild_break_even_steps"], 3)
                if cost["full_rebuild_break_even_steps"] != float("inf")
                else None
            ),
            "recovered": winner.seconds < stale,
            "calibration": model.source,
        })
    if not rows:
        raise ValueError(f"adapt sweep produced no rows: sizes={list(sizes)}")
    return rows


def fabric_sweep(
    world: int,
    sizes: Sequence[int],
    intensities: Sequence[float] = (1.0, 2.0, 4.0),
    mixes: Sequence[str] = ("high-low", "high-high"),
    share_penalty: float = 2.0,
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Deterministic multi-tenant fabric rows — the hardware-free
    regression artifact for priority-aware synthesis and graceful QoS
    yielding (``make fabric-bench``, docs/FABRIC.md).

    The grid is (payload size × background congestion intensity ×
    priority mix) on a fixed two-pod split of ``--world``:

    - **intensity** scales the shared DCN class's effective bandwidth
      (β × intensity, α intact — ambient neighbor traffic both tenants
      suffer, :meth:`LinkCostModel.contended`);
    - mix ``"high-low"`` is the coordinated fabric: the low-priority
      job's candidates are ranked under the high-priority job's link
      occupancy (contended by the share penalty), so its winning tree
      yields the high job's hot links;
    - mix ``"high-high"`` is the uncoordinated baseline: two equal
      tenants greedily pick the clean-network winner and pile onto the
      same links.

    Every row carries both jobs' priced steady states under the final
    shared fabric, Jain's fairness index, and aggregate throughput; the
    ``high-low`` rows additionally stamp ``high_beats_uncoordinated`` —
    the acceptance property that priority coordination makes the high
    job's sharing steady state strictly better than the pile-up.
    Deterministic: no RNG, no wall clock — same calibration →
    byte-identical rows.
    """
    from adapcc_tpu.adapt.fabric import SharedFabric

    if world < 4 or world % 2:
        raise ValueError(
            f"fabric sweep needs an even world >= 4 (two pods of world/2), "
            f"got {world}"
        )
    bad = [m for m in mixes if m not in ("high-low", "high-high")]
    if bad:
        raise ValueError(
            f"unknown priority mixes {bad}; expected a subset of "
            "['high-low', 'high-high']"
        )
    if any(i < 1.0 for i in intensities):
        raise ValueError(
            f"congestion intensities must be >= 1, got {list(intensities)}"
        )
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    table = _ip_table(world, 2)
    ips = {r: ip for r, ip in enumerate(table)}
    base = model.with_ips(ips)

    def _plan(ambient, mix: str):
        fab = SharedFabric(ambient, table, share_penalty=share_penalty)
        if mix == "high-low":
            fab.add_job("job0", priority="high", nbytes=nbytes)
            fab.add_job("job1", priority="low", nbytes=nbytes)
            return fab.plan(coordinated=True)
        fab.add_job("job0", priority="high", nbytes=nbytes)
        fab.add_job("job1", priority="high", nbytes=nbytes)
        return fab.plan(coordinated=False)

    rows: List[dict] = []
    for nbytes in sizes:
        nbytes = int(nbytes)
        for intensity in intensities:
            intensity = float(intensity)
            ambient = (
                base.contended({DCN: intensity}) if intensity > 1.0 else base
            )
            plans = {mix: _plan(ambient, mix) for mix in mixes}
            baseline = plans.get("high-high") or _plan(ambient, "high-high")
            for mix in mixes:
                plan = plans[mix]
                j0, j1 = plan.job("job0"), plan.job("job1")
                row = {
                    "mode": "simulated",
                    "collective": "allreduce",
                    "impl": "fabric",
                    "world": world,
                    "size_bytes": nbytes,
                    "intensity": intensity,
                    "mix": mix,
                    "share_penalty": float(share_penalty),
                    "coordinated": plan.coordinated,
                    "job0_strategy": j0.label,
                    "job1_strategy": j1.label,
                    "job0_us": round(j0.shared_s * 1e6, 3),
                    "job1_us": round(j1.shared_s * 1e6, 3),
                    "job0_alone_us": round(j0.alone_s * 1e6, 3),
                    "job1_alone_us": round(j1.alone_s * 1e6, 3),
                    "shared_links": len(plan.shared_links),
                    "fairness": round(plan.fairness(), 6),
                    "throughput_gbps": round(plan.throughput_gbps(), 6),
                    "calibration": model.source,
                }
                if mix == "high-low":
                    row["high_beats_uncoordinated"] = (
                        j0.shared_s < baseline.job("job0").shared_s
                    )
                rows.append(row)
    if not rows:
        raise ValueError(
            f"fabric sweep produced no rows: sizes={list(sizes)} "
            f"intensities={list(intensities)} mixes={list(mixes)}"
        )
    return rows


def serve_sweep(
    world: int,
    rates: Sequence[float] = (0.05, 0.1, 0.25),
    slots_grid: Sequence[int] = (1, 2, 4, 8),
    num_requests: int = 64,
    n_layer: int = 2,
    d_model: int = 128,
    seed: int = 0,
    slo_ms: Optional[float] = None,
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """Deterministic latency/throughput frontier rows for the serving
    plane — the hardware-free regression artifact for the continuous
    batcher (``make serve-bench``, docs/SERVING.md §5).

    The grid is (arrival rate × decode slots) on one seeded Poisson
    trace per rate (:func:`adapcc_tpu.serve.trace
    .synthesize_arrival_trace` — the SAME module the live server
    replays, so the sweep and the workload can never price different
    traffic).  Each cell:

    - prices the decode step with :func:`adapcc_tpu.sim.cost_model
      .decode_step_time` — per layer, a ``slots × d_model`` allreduce on
      the calibrated coefficients, the algorithm chosen by the selector's
      own crossover (at serving sizes: the small-message plane);
    - replays the trace through :func:`adapcc_tpu.sim.cost_model
      .simulate_serve_queue`, the queueing twin of the batcher's
      admission discipline, for p50/p99 sojourn on the step clock;
    - stamps throughput, utilization, and (with ``slo_ms``) SLO
      attainment — the frontier an admission policy trades along.

    Deterministic: the trace is seeded ``jax.random``, the replay is
    analytic — same calibration, same seed → byte-identical rows.
    """
    from adapcc_tpu.serve.trace import synthesize_arrival_trace
    from adapcc_tpu.sim.cost_model import (
        bottleneck_ring_coeffs,
        decode_step_time,
        serve_queue_metrics,
    )

    if world < 1:
        raise ValueError(f"world must be >= 1, got {world}")
    if num_requests < 1:
        raise ValueError(f"num_requests must be >= 1, got {num_requests}")
    if any(r <= 0 for r in rates):
        raise ValueError(
            f"arrival rates must be > 0 requests/step, got {list(rates)}"
        )
    if any(s < 1 for s in slots_grid):
        raise ValueError(f"slot counts must be >= 1, got {list(slots_grid)}")
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    coeffs = bottleneck_ring_coeffs(model, max(2, world))
    rows: List[dict] = []
    for rate in rates:
        rate = float(rate)
        trace = synthesize_arrival_trace(
            world, num_requests, rate, seed=seed,
            label=f"serve-sweep-r{rate:g}",
        )
        arrivals = [r.arrival_step for r in trace.requests]
        services = [r.service_steps for r in trace.requests]
        generated = [r.max_new_tokens for r in trace.requests]
        for slots in slots_grid:
            slots = int(slots)
            step = decode_step_time(
                world, slots, n_layer, d_model, coeffs
            )
            metrics = serve_queue_metrics(
                arrivals, services, slots,
                float(step["step_time_s"]), slo_ms=slo_ms,
                generated_steps=generated,
            )
            row = {
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "serve",
                "world": world,
                "slots": slots,
                "rate_req_per_step": rate,
                "requests": num_requests,
                "trace_seed": seed,
                "n_layer": n_layer,
                "d_model": d_model,
                "algo": step["algo"],
                "collective_bytes": step["collective_bytes"],
                "pred_step_us": round(float(step["step_time_s"]) * 1e6, 3),
                "pred_comm_us": round(float(step["comm_s"]) * 1e6, 3),
                "p50_sojourn_steps": int(metrics["p50_sojourn_steps"]),
                "p99_sojourn_steps": int(metrics["p99_sojourn_steps"]),
                "p50_sojourn_ms": round(metrics["p50_sojourn_ms"], 6),
                "p99_sojourn_ms": round(metrics["p99_sojourn_ms"], 6),
                "p99_queue_steps": int(metrics["p99_queue_steps"]),
                "throughput_tok_s": round(metrics["throughput_tok_s"], 3),
                "utilization": round(metrics["utilization"], 6),
                "calibration": model.source,
            }
            if slo_ms is not None:
                row["slo_ms"] = float(slo_ms)
                row["slo_attainment"] = round(metrics["slo_attainment"], 6)
            rows.append(row)
    if not rows:
        raise ValueError(
            f"serve sweep produced no rows: rates={list(rates)} "
            f"slots={list(slots_grid)}"
        )
    return rows


#: request mixes of the disaggregation frontier: (prompt range, max-new
#: range) — "prefill-heavy" is prompt-dominated traffic (long contexts,
#: short answers), "decode-heavy" the inverse (chat tails)
DISAGG_MIXES = {
    "prefill-heavy": ((24, 48), (4, 8)),
    "balanced": ((8, 16), (8, 16)),
    "decode-heavy": ((4, 8), (24, 48)),
}


def disagg_sweep(
    world: int,
    mixes: Sequence[str] = ("prefill-heavy", "balanced", "decode-heavy"),
    splits: Sequence[str] = ("1:1", "3:1"),
    dims: Sequence[int] = (128, 256),
    rate: float = 0.05,
    num_requests: int = 64,
    total_slots: int = 8,
    n_layer: int = 2,
    seed: int = 0,
    slo_ms: Optional[float] = None,
    model: Optional[LinkCostModel] = None,
) -> List[dict]:
    """The colocated-vs-disaggregated serving frontier (``make
    disagg-bench``, docs/SERVING.md §7): for each (request mix × pool
    split × d_model) cell, the SAME seeded arrival trace is priced both
    ways at **equal chip count and equal total KV-lane budget** (slots
    follow chips — lane count is bounded by per-chip KV HBM, so a pod
    with ``k`` of the chips gets ``k``'s share of the lanes):

    - **disaggregated**: a prefill pod and a decode pod splitting
      ``--world`` per ``split`` (``"3:1"`` = three quarters of the chips
      prefill), each pod's step priced by :func:`decode_step_time` at
      its own world and lane count, the KV handoff priced on the
      calibrated **DCN** α-β (mean-prompt page bytes, ceil'd to router
      ticks), the tandem queue replayed by
      :func:`~adapcc_tpu.sim.cost_model.disagg_queue_metrics`;
    - **colocated**: one ``--world``-wide batcher with all
      ``total_slots`` lanes, replayed by :func:`serve_queue_metrics`
      (TTFT recovered from the admission triples).

    Each row stamps ``disagg_beats_colocated_p99_ttft`` — the frontier
    claim the regression suite pins: half-world pods pay fewer α hops
    and smaller per-step payloads per token, so prefill-heavy traffic at
    moderate load beats the colocated tail on p99 TTFT **ms**, while the
    queueing twin prices exactly where the smaller prefill pool's queue
    eats the win (rate up → colocated's 2× lanes win back).
    Deterministic: seeded trace, analytic replay — byte-identical rows.
    """
    from adapcc_tpu.serve.trace import synthesize_arrival_trace
    from adapcc_tpu.sim.cost_model import (
        DCN,
        bottleneck_ring_coeffs,
        decode_step_time,
        disagg_queue_metrics,
        serve_queue_metrics,
        simulate_serve_queue,
    )
    from adapcc_tpu.utils.observability import nearest_rank_percentile

    if world < 2:
        raise ValueError(
            f"world must be >= 2 to split into two pods, got {world}"
        )
    if num_requests < 1:
        raise ValueError(f"num_requests must be >= 1, got {num_requests}")
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    if total_slots < 2:
        raise ValueError(
            f"total_slots must be >= 2 (one lane per pool), got "
            f"{total_slots}"
        )
    unknown = [m for m in mixes if m not in DISAGG_MIXES]
    if unknown:
        raise ValueError(
            f"unknown request mix(es) {unknown}; expected "
            f"{sorted(DISAGG_MIXES)}"
        )
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")
    dcn = model.classes[DCN]
    rows: List[dict] = []
    for mix in mixes:
        prompt_rng, new_rng = DISAGG_MIXES[mix]
        trace = synthesize_arrival_trace(
            world, num_requests, float(rate), seed=seed,
            prompt_len=prompt_rng, max_new_tokens=new_rng,
            label=f"disagg-sweep-{mix}",
        )
        arrivals = [r.arrival_step for r in trace.requests]
        prompts = [len(r.prompt) for r in trace.requests]
        prefills = prompts  # one forced step per prompt token
        decodes = [r.max_new_tokens - 1 for r in trace.requests]
        services = [p + d for p, d in zip(prefills, decodes)]  # total - 1
        generated = [r.max_new_tokens for r in trace.requests]
        mean_prompt = sum(prompts) / len(prompts)
        for split in splits:
            try:
                p_share, d_share = (int(x) for x in split.split(":"))
            except ValueError as e:
                raise ValueError(
                    f"pool split {split!r} is not 'P:D' integers"
                ) from e
            parts = p_share + d_share
            if p_share < 1 or d_share < 1:
                raise ValueError(
                    f"pool split {split!r}: both shares must be >= 1"
                )
            if world % parts or total_slots % parts:
                raise ValueError(
                    f"pool split {split!r} does not divide world={world} "
                    f"and total_slots={total_slots} into whole pods"
                )
            pw = world * p_share // parts
            dw = world - pw
            ps = total_slots * p_share // parts
            ds = total_slots - ps
            for d_model in dims:
                d_model = int(d_model)
                p_step = decode_step_time(
                    pw, ps, n_layer, d_model,
                    bottleneck_ring_coeffs(model, max(2, pw)),
                )
                d_step = decode_step_time(
                    dw, ds, n_layer, d_model,
                    bottleneck_ring_coeffs(model, max(2, dw)),
                )
                c_step = decode_step_time(
                    world, total_slots, n_layer, d_model,
                    bottleneck_ring_coeffs(model, max(2, world)),
                )
                tick_s = max(
                    float(p_step["step_time_s"]),
                    float(d_step["step_time_s"]),
                )
                # the migrated payload: the filled KV prefix of a mean
                # prompt (K and V, all layers, fp32), on the DCN wire
                kv_bytes = 2 * n_layer * mean_prompt * d_model * 4
                transfer_steps = int(math.ceil(dcn.time(kv_bytes) / tick_s))
                dm = disagg_queue_metrics(
                    arrivals, prefills, decodes, ps, ds, transfer_steps,
                    float(p_step["step_time_s"]),
                    float(d_step["step_time_s"]), slo_ms=slo_ms,
                )
                cm = serve_queue_metrics(
                    arrivals, services, total_slots,
                    float(c_step["step_time_s"]), slo_ms=slo_ms,
                    generated_steps=generated,
                )
                triples = simulate_serve_queue(
                    arrivals, services, total_slots
                )
                coloc_ttfts = sorted(
                    adm + p - a
                    for (a, adm, _), p in zip(triples, prefills)
                )
                coloc_p99_ttft = int(
                    nearest_rank_percentile(coloc_ttfts, 0.99)
                )
                coloc_step_s = float(c_step["step_time_s"])
                row = {
                    "mode": "simulated",
                    "collective": "allreduce",
                    "impl": "disagg",
                    "world": world,
                    "mix": mix,
                    "split": split,
                    "rate_req_per_step": float(rate),
                    "requests": num_requests,
                    "trace_seed": seed,
                    "n_layer": n_layer,
                    "d_model": d_model,
                    "prefill_world": pw,
                    "decode_world": dw,
                    "prefill_slots": ps,
                    "decode_slots": ds,
                    "coloc_slots": total_slots,
                    "transfer_steps": transfer_steps,
                    "kv_bytes_mean": int(kv_bytes),
                    "prefill_algo": p_step["algo"],
                    "decode_algo": d_step["algo"],
                    "coloc_algo": c_step["algo"],
                    "pred_prefill_step_us": round(
                        float(p_step["step_time_s"]) * 1e6, 3
                    ),
                    "pred_decode_step_us": round(
                        float(d_step["step_time_s"]) * 1e6, 3
                    ),
                    "pred_coloc_step_us": round(coloc_step_s * 1e6, 3),
                    "p50_ttft_ms": round(dm["p50_ttft_ms"], 6),
                    "p99_ttft_steps": int(dm["p99_ttft_steps"]),
                    "p99_ttft_ms": round(dm["p99_ttft_ms"], 6),
                    "p99_sojourn_ms": round(dm["p99_sojourn_ms"], 6),
                    "p99_queue_steps": int(dm["p99_queue_steps"]),
                    "p99_decode_wait_steps": int(
                        dm["p99_decode_wait_steps"]
                    ),
                    "throughput_tok_s": round(dm["throughput_tok_s"], 3),
                    "prefill_utilization": round(
                        dm["prefill_utilization"], 6
                    ),
                    "decode_utilization": round(
                        dm["decode_utilization"], 6
                    ),
                    "coloc_p99_ttft_steps": coloc_p99_ttft,
                    "coloc_p99_ttft_ms": round(
                        coloc_p99_ttft * coloc_step_s * 1e3, 6
                    ),
                    "coloc_p99_sojourn_ms": round(
                        cm["p99_sojourn_ms"], 6
                    ),
                    "coloc_throughput_tok_s": round(
                        cm["throughput_tok_s"], 3
                    ),
                    "disagg_beats_colocated_p99_ttft": bool(
                        dm["p99_ttft_ms"]
                        < coloc_p99_ttft * coloc_step_s * 1e3
                    ),
                    "calibration": model.source,
                }
                if slo_ms is not None:
                    row["slo_ms"] = float(slo_ms)
                    row["slo_attainment"] = round(
                        dm["slo_attainment"], 6
                    )
                    row["coloc_slo_attainment"] = round(
                        cm["slo_attainment"], 6
                    )
                rows.append(row)
    if not rows:
        raise ValueError(
            f"disagg sweep produced no rows: mixes={list(mixes)} "
            f"splits={list(splits)} dims={list(dims)}"
        )
    return rows


def tune_replay_sweep(
    world: int,
    sizes: Sequence[int],
    chunk_grid: Optional[Sequence[int]] = None,
    model: Optional[LinkCostModel] = None,
    trial_budget: int = 4,
    exploit_rounds: int = 8,
) -> List[dict]:
    """Deterministic tuner-convergence rows on a synthetic cost surface —
    the hardware-free regression artifact for the autotuner
    (``make tune-bench``).

    For each payload size the sweep builds a fresh in-memory tuning
    database and a :class:`adapcc_tpu.tuner.TuningPolicy`, then runs the
    policy against a synthetic "true" cost surface: the sim cost model's
    per-cell prediction warped by a deterministic per-cell factor (hash of
    the cell, ±25%) so the measured optimum *disagrees* with the prior
    somewhere — the exact situation the tuner exists for.  Exploration runs
    at epsilon=1 until every cell meets its trial budget, then
    ``exploit_rounds`` greedy rounds settle the incumbent.  One row per
    cell, ``chosen`` flagging the policy's final plan and ``surface_best``
    the true argmin, so the artifact shows both the decision and whether it
    converged.  Everything is seeded/hashed: same inputs → byte-identical
    rows.
    """
    import hashlib

    from adapcc_tpu.tuner import TuningDatabase
    from adapcc_tpu.tuner.policy import DEFAULT_CHUNK_GRID, TuningPolicy

    if chunk_grid is None:
        chunk_grid = DEFAULT_CHUNK_GRID
    if model is None:
        model = load_or_default(world=world)
    elif model.world != world:
        raise ValueError(f"model world {model.world} != sweep world {world}")

    def cell_factor(key) -> float:
        digest = hashlib.md5(repr(key).encode()).digest()
        return 0.75 + 0.5 * (digest[0] / 255.0)  # deterministic, in [0.75, 1.25]

    def sample_jitter(key, i: int) -> float:
        digest = hashlib.md5(f"{key!r}#{i}".encode()).digest()
        return 0.98 + 0.04 * (digest[0] / 255.0)  # ±2% around the cell truth

    rows: List[dict] = []
    for nbytes in sizes:
        db = TuningDatabase(persist=False)  # the replay must not write repo
        # artifacts; epsilon=1 fills the grid deterministically (seeded rng)
        policy = TuningPolicy(
            db, world, topology="tune-replay", chunk_grid=chunk_grid,
            epsilon=1.0, trial_budget=trial_budget, cost_model=model, seed=0,
            # the replay is a synthetic surface, not a data plane: force the
            # fused-path cells in so the artifact pins the full grid (chunk
            # × codec × path) on any build, TPU or not
            fused_paths=True,
        )
        cells = policy.candidates("allreduce", int(nbytes))
        surface = {
            c: policy.prior_time(c, int(nbytes)) * cell_factor(c) for c in cells
        }
        counts = {c: 0 for c in cells}
        for _ in range(trial_budget * len(cells) + exploit_rounds):
            plan = policy.choose("allreduce", int(nbytes))
            i = counts[plan.key] = counts[plan.key] + 1
            db.record(
                plan.key,
                surface[plan.key] * sample_jitter(plan.key, i),
                ts=float(i),
            )
        final = policy.choose("allreduce", int(nbytes))
        best_true = min(cells, key=lambda c: (surface[c], cells.index(c)))
        for cell in cells:
            stats = db.stats(cell)
            rows.append({
                "mode": "simulated",
                "collective": "allreduce",
                "impl": "tuner",
                "world": world,
                "size_bytes": int(nbytes),
                "path": cell.path,
                "chunk_bytes": cell.chunk_bytes,
                "wire_dtype": cell.wire_dtype,
                "samples": stats.count if stats else 0,
                "median_us": round(stats.median_s * 1e6, 3) if stats else None,
                "surface_us": round(surface[cell] * 1e6, 3),
                "prior_us": round(policy.prior_time(cell, int(nbytes)) * 1e6, 3),
                "chosen": cell == final.key,
                "choice_source": final.source if cell == final.key else None,
                "surface_best": cell == best_true,
                "converged": final.key == best_true,
                "calibration": model.source,
            })
    if not rows:
        raise ValueError(f"tune replay produced no rows: sizes={list(sizes)}")
    return rows


#: default --scale-worlds grid: pod scale, where only the vectorized
#: engine replays in seconds (docs/SIMULATION.md §7)
SCALE_WORLDS = (1024, 4096, 16384)

#: largest world the ring schedule is priced at in the scale sweep — a
#: ring is ``world`` rounds deep, so its replay cost grows linearly with
#: world even on the vectorized engine; past this the sweep emits an
#: explicit skip row instead of silently dropping the shape
RING_SCALE_MAX_WORLD = 16384


def scale_sweep(
    worlds: Sequence[int],
    sizes: Sequence[int],
    collective: str = "allreduce",
    degree: int = 1,
) -> List[dict]:
    """Replay-scaling grid: (world × size × strategy) priced on a uniform
    synthetic topology, every row stamped with its certified
    ``optimality_gap`` against the α-β collective lower bound
    (docs/SIMULATION.md §7).

    Strategies are constructed directly (``Strategy.ring`` /
    ``Strategy.binary``) — never via :func:`strategy_candidates`, whose
    ``to_graphs()`` materializes an O(world²) matrix that is exactly the
    scaling wall this sweep exists to demonstrate the engine clears.  Rows
    carry no wall-clock times, so two runs of the same grid are
    byte-identical (the measured replay-latency rows live in
    ``benchmarks.synthesis_scale``, which is allowed to be nondeterministic).
    """
    if collective not in SIM_COLLECTIVES:
        raise ValueError(
            f"unknown collective {collective!r}; "
            f"expected one of {SIM_COLLECTIVES}"
        )
    bad = [w for w in worlds if w < 2]
    if bad:
        raise ValueError(f"scale sweep worlds must be >= 2, got {bad}")
    rows: List[dict] = []
    for world in worlds:
        # per-world uniform model: O(#classes) memory, deterministic, and
        # source="synthetic" so the calibration column is honest about it
        model = LinkCostModel.uniform(world)
        engine = resolve_sim_engine(None, world)
        lower = {
            int(n): collective_lower_bound(model, n, collective, world)
            for n in sizes
        }
        candidates: List[Tuple[str, Optional[Strategy]]] = [
            ("binary", Strategy.binary(world, degree)),
            (
                "ring",
                Strategy.ring(world, degree)
                if world <= RING_SCALE_MAX_WORLD
                else None,
            ),
        ]
        for nbytes in sizes:
            for label, strategy in candidates:
                if strategy is None:
                    rows.append({
                        "mode": "simulated",
                        "collective": collective,
                        "world": world,
                        "size_bytes": int(nbytes),
                        "strategy": label,
                        "skipped": (
                            f"ring is {world} rounds deep; capped at "
                            f"--scale-worlds <= {RING_SCALE_MAX_WORLD}"
                        ),
                        "calibration": model.source,
                    })
                    continue
                timeline = simulate_strategy(
                    strategy, model, nbytes, collective, keep_transfers=False
                )
                row = _finish_row(timeline.to_row(), collective, world)
                row["strategy"] = label
                row["engine"] = engine
                lb = lower[int(nbytes)]
                row["lower_bound_us"] = round(lb * 1e6, 3)
                row["optimality_gap"] = round(
                    optimality_gap(timeline.seconds, lb), 6
                )
                row["calibration"] = model.source
                rows.append(row)
    if not rows:
        raise ValueError(
            f"scale sweep produced no rows: worlds={list(worlds)} "
            f"sizes={list(sizes)}"
        )
    return rows


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--sizes", default="4K,1M,16M")
    ap.add_argument("--collectives", default=",".join(SIM_COLLECTIVES))
    ap.add_argument("--strategies", default=",".join(SIM_STRATEGIES))
    ap.add_argument(
        "--hosts", type=int, default=1,
        help="synthetic host count (>1 prices DCN edges between hosts)",
    )
    ap.add_argument(
        "--degree", type=int, default=1, help="parallel transmissions per strategy"
    )
    ap.add_argument(
        "--calibration", default=DEFAULT_CALIBRATION_PATH,
        help="calibration artifact path (synthetic defaults when absent)",
    )
    ap.add_argument("--no-flow-lp", action="store_true")
    ap.add_argument(
        "--ring-sweep", action="store_true",
        help="sweep the staged Pallas ring over --chunks instead of the "
        "strategy grid (chunk-size tuning rows, make ring-sweep)",
    )
    ap.add_argument(
        "--chunks", default="256K,1M,4M,16M",
        help="ring-sweep staging granularities (chunk_bytes grid)",
    )
    ap.add_argument(
        "--wire-dtype", default="",
        help="comma list of wire codecs (off,bf16,int8): sweep the "
        "quantized ring's codec A/B instead of the strategy grid, priced "
        "by the sim-rank cost-model term (make quant-bench)",
    )
    ap.add_argument(
        "--fused-sweep", action="store_true",
        help="price the FUSED quantized streaming ring against the unfused "
        "ppermute reroute over (size x wire_dtype x chunk_bytes), crossover "
        "size flagged per row (make fused-bench; docs/RING.md)",
    )
    ap.add_argument(
        "--fused-wire", default="bf16,int8",
        help="fused-sweep codec grid (codecs the fused kernels speak)",
    )
    ap.add_argument(
        "--tune-replay", action="store_true",
        help="replay the autotuner's policy against a deterministic "
        "synthetic cost surface over the (chunk x codec) grid instead of "
        "the strategy grid: one row per cell with the chosen plan flagged "
        "per size (make tune-bench; docs/TUNER.md)",
    )
    ap.add_argument(
        "--fault-sweep", action="store_true",
        help="price elastic failover instead of the strategy grid: per-fault "
        "detection/swap/degraded summary rows plus a canonical fault plan's "
        "step-by-step timeline (make elastic-bench; docs/ELASTIC.md)",
    )
    ap.add_argument(
        "--heartbeat-timeout-s", type=float, default=1.0,
        help="fault-sweep heartbeat timeout priced into detection latency",
    )
    ap.add_argument(
        "--chaos-sweep", action="store_true",
        help="price the autonomous supervisor's out-of-band detection "
        "over the (heartbeat period x grace) grid — detection latency vs "
        "false-positive headroom — plus the canonical fault plan's "
        "deterministic chaos (SIGKILL/SIGSTOP) schedule (make "
        "chaos-bench; docs/SUPERVISOR.md)",
    )
    ap.add_argument(
        "--hb-periods", default="0.25,0.5,1,2",
        help="chaos-sweep heartbeat period grid (seconds)",
    )
    ap.add_argument(
        "--hb-graces", default="1,2,4",
        help="chaos-sweep confirmation-count grid",
    )
    ap.add_argument(
        "--recovery-sweep", action="store_true",
        help="price durable elastic recovery instead of the strategy "
        "grid: per-(world x payload) replication wire overhead vs "
        "baseline step comm, and the in-fabric shard repair vs a "
        "checkpoint reload (make recovery-bench; docs/RECOVERY.md)",
    )
    ap.add_argument(
        "--rec-worlds", default="8,32,64",
        help="recovery-sweep world grid",
    )
    ap.add_argument(
        "--rec-replicas", type=int, default=1,
        help="recovery-sweep shard replica count (k)",
    )
    ap.add_argument(
        "--rec-save-interval", type=int, default=100,
        help="recovery-sweep checkpoint save interval (steps) priced "
        "into the reload arm's lost work",
    )
    ap.add_argument(
        "--hier-sweep", action="store_true",
        help="price the composed two-level allreduce against the flat "
        "ring over a (pods x pod_size x size) grid, with the per-row "
        "two-level-vs-flat decision and the pod-count crossover flagged "
        "(make hier-bench; docs/HIERARCHY.md)",
    )
    ap.add_argument(
        "--pods", default="2,4,8",
        help="hier-sweep pod-count grid",
    )
    ap.add_argument(
        "--pod-sizes", default="4,8",
        help="hier-sweep ranks-per-pod grid",
    )
    ap.add_argument(
        "--latency-sweep", action="store_true",
        help="price the latency-bound allreduce algorithms (ring vs "
        "recursive doubling vs binomial tree) over --sizes instead of the "
        "strategy grid, with the per-size chosen algorithm and the ring-rd "
        "crossover flagged per row (make latency-bench; docs/LATENCY.md)",
    )
    ap.add_argument(
        "--algos", default="ring,rd,tree",
        help="latency-sweep algorithm grid",
    )
    ap.add_argument(
        "--schedule-sweep", action="store_true",
        help="price IR-lowered schedule programs (compiler.ScheduleProgram: "
        "ring/rd/tree re-emitted as IR plus the pipelined bidirectional "
        "schedule no hand-written plane expresses) over --sizes instead of "
        "the strategy grid, each verified then priced by "
        "schedule_program_time next to its legacy plane's pricing (make "
        "compiler-bench; docs/COMPILER.md)",
    )
    ap.add_argument(
        "--programs", default=",".join(SCHEDULE_PROGRAMS),
        help="schedule-sweep program grid",
    )
    ap.add_argument(
        "--adapt-sweep", action="store_true",
        help="replay the closed adaptation loop instead of the strategy "
        "grid: per-step drift-detection timeline rows plus a summary row "
        "pricing stale-vs-adapted steady state and hot-swap vs "
        "full-rebuild stall (make adapt-bench; docs/ADAPT.md)",
    )
    ap.add_argument(
        "--degrade-factor", type=float, default=8.0,
        help="adapt-sweep DCN slowdown injected at the drift onset",
    )
    ap.add_argument(
        "--fabric-sweep", action="store_true",
        help="price the multi-tenant fabric instead of the strategy grid: "
        "two prioritized jobs on a two-pod split of --world, over "
        "(congestion intensity x priority mix), with the coordinated "
        "high-low yield priced against the uncoordinated high-high "
        "pile-up per row (make fabric-bench; docs/FABRIC.md)",
    )
    ap.add_argument(
        "--intensities", default="1,2,4",
        help="fabric-sweep background DCN congestion factor grid",
    )
    ap.add_argument(
        "--serve-sweep", action="store_true",
        help="price the serving plane's latency/throughput frontier "
        "instead of the strategy grid: a seeded Poisson arrival trace "
        "replayed through the continuous batcher's queueing twin over "
        "(--rates x --serve-slots), each cell priced by the decode-step "
        "service time on the calibrated coefficients, p50/p99 sojourn "
        "and SLO attainment stamped per row (make serve-bench; "
        "docs/SERVING.md)",
    )
    ap.add_argument(
        "--rates", default="0.05,0.1,0.25",
        help="serve-sweep Poisson arrival-rate grid (requests per decode "
        "step)",
    )
    ap.add_argument(
        "--serve-slots", default="1,2,4,8",
        help="serve-sweep decode-slot grid (the continuous batcher's "
        "fixed lane count)",
    )
    ap.add_argument(
        "--serve-requests", type=int, default=64,
        help="serve-sweep requests per synthesized trace",
    )
    ap.add_argument(
        "--slo-ms", type=float, default=0.0,
        help="serve-sweep per-request sojourn SLO in milliseconds "
        "(0 = no SLO-attainment column)",
    )
    ap.add_argument(
        "--disagg-sweep", action="store_true",
        help="price the colocated-vs-disaggregated serving frontier "
        "instead of the strategy grid: one seeded arrival trace per "
        "request mix, replayed through the two-pool tandem queue "
        "(prefill pod -> DCN KV transfer -> decode pod) AND the "
        "colocated batcher at equal chip count, p99 TTFT verdict "
        "stamped per row (make disagg-bench; docs/SERVING.md §7)",
    )
    ap.add_argument(
        "--disagg-mixes", default="prefill-heavy,balanced,decode-heavy",
        help="disagg-sweep request-mix grid (prompt-vs-decode balance)",
    )
    ap.add_argument(
        "--disagg-splits", default="1:1,3:1",
        help="disagg-sweep prefill:decode chip-split grid (slots follow "
        "chips — the per-chip KV HBM budget)",
    )
    ap.add_argument(
        "--disagg-dims", default="128,256",
        help="disagg-sweep d_model grid",
    )
    ap.add_argument(
        "--disagg-slots", type=int, default=8,
        help="disagg-sweep TOTAL cluster lane budget (the colocated arm "
        "runs all of them in one pool)",
    )
    ap.add_argument(
        "--disagg-rate", type=float, default=0.05,
        help="disagg-sweep Poisson arrival rate (requests per step)",
    )
    ap.add_argument(
        "--overlap-sweep", action="store_true",
        help="price the overlapped DDP gradient sync over (accum x "
        "bucket cap x overlap schedule) with overlapped_step_time instead "
        "of the strategy grid (make overlap-bench; docs/OVERLAP.md)",
    )
    ap.add_argument(
        "--accums", default="1,2,4",
        help="overlap-sweep gradient-accumulation grid",
    )
    ap.add_argument(
        "--bucket-caps-mb", default="1,4",
        help="overlap-sweep bucket cap grid (MB)",
    )
    ap.add_argument(
        "--scale-sweep", action="store_true",
        help="replay-scaling grid instead of the strategy grid: "
        "(--scale-worlds x --sizes) priced on per-world uniform synthetic "
        "topologies through the vectorized engine, each row stamped with "
        "its certified optimality_gap against the α-β collective lower "
        "bound (make simscale-bench; docs/SIMULATION.md §7)",
    )
    ap.add_argument(
        "--scale-worlds", default=",".join(str(w) for w in SCALE_WORLDS),
        help="scale-sweep world grid (pod scale; ring is skipped above "
        f"{RING_SCALE_MAX_WORLD})",
    )
    ap.add_argument(
        "--pipe-sweep", action="store_true",
        help="price the GPipe-vs-1F1B pipeline frontier instead of the "
        "strategy grid: (stages x microbatches x hop bytes), each cell's "
        "verified hop program replayed next to the closed-form step time "
        "and stash bound (make pipe-bench; docs/PIPELINE.md)",
    )
    ap.add_argument(
        "--pipe-stages", default="2,4",
        help="pipe-sweep stage-count grid",
    )
    ap.add_argument(
        "--pipe-microbatches", default="2,4,8",
        help="pipe-sweep microbatch grid",
    )
    ap.add_argument(
        "--pipe-fwd-us", type=float, default=100.0,
        help="pipe-sweep per-stage forward compute term (microseconds)",
    )
    ap.add_argument("--json", action="store_true", help="one JSON row per line")
    args = ap.parse_args(argv)

    exclusive = [
        name for name, on in (
            ("--wire-dtype", bool(args.wire_dtype)),
            ("--ring-sweep", args.ring_sweep),
            ("--fused-sweep", args.fused_sweep),
            ("--tune-replay", args.tune_replay),
            ("--overlap-sweep", args.overlap_sweep),
            ("--hier-sweep", args.hier_sweep),
            ("--latency-sweep", args.latency_sweep),
            ("--schedule-sweep", args.schedule_sweep),
            ("--fault-sweep", args.fault_sweep),
            ("--adapt-sweep", args.adapt_sweep),
            ("--chaos-sweep", args.chaos_sweep),
            ("--fabric-sweep", args.fabric_sweep),
            ("--recovery-sweep", args.recovery_sweep),
            ("--serve-sweep", args.serve_sweep),
            ("--disagg-sweep", args.disagg_sweep),
            ("--scale-sweep", args.scale_sweep),
            ("--pipe-sweep", args.pipe_sweep),
        ) if on
    ]
    if len(exclusive) > 1:
        # different sweep grids over one --sizes axis: silently running one
        # and dropping the others would read as "ran fine, no data"
        ap.error(f"{' and '.join(exclusive)} are mutually exclusive; "
                 "run one sweep per invocation")
    if args.scale_sweep:
        if args.hosts > 1:
            # the sweep prices per-world uniform synthetic topologies;
            # silently accepting --hosts would read as "priced that host
            # split" when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --scale-sweep (each world "
                     "is priced on its own uniform synthetic topology)")
        rows = scale_sweep(
            worlds=[int(w) for w in args.scale_worlds.split(",") if w],
            sizes=[parse_size(s) for s in args.sizes.split(",") if s],
            degree=args.degree,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            elif "skipped" in row:
                print(
                    f"[sim] scale world={row['world']:>6} "
                    f"{row['strategy']:<6} skipped: {row['skipped']}"
                )
            else:
                print(
                    f"[sim] scale world={row['world']:>6} "
                    f"{row['strategy']:<6} {row['size_bytes']:>10}B  "
                    f"pred={row['pred_time_us']:>10.1f}us  "
                    f"lb={row['lower_bound_us']:>10.1f}us  "
                    f"gap={row['optimality_gap']:>8.4f}  "
                    f"engine={row['engine']}"
                )
        return 0
    model = load_or_default(args.calibration, world=args.world)
    if args.pipe_sweep:
        if args.hosts > 1:
            # the sweep prices stage chains on the calibration's bottleneck
            # class; silently accepting --hosts would read as "priced that
            # host split" when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --pipe-sweep (each stage "
                     "chain prices on the calibration's bottleneck link "
                     "class)")
        rows = pipe_sweep(
            sizes=[parse_size(s) for s in args.sizes.split(",") if s],
            stages_grid=[int(s) for s in args.pipe_stages.split(",") if s],
            microbatch_grid=[
                int(m) for m in args.pipe_microbatches.split(",") if m
            ],
            fwd_us=args.pipe_fwd_us,
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                win = row.get("memory_win_vs_gpipe")
                print(
                    f"[sim] pipe {row['schedule']:<5} "
                    f"s={row['stages']:>2} m={row['microbatches']:>2} "
                    f"{row['size_bytes']:>10}B  "
                    f"bubble={row['bubble_fraction']:.3f}  "
                    f"step={row['pred_step_us']:>10.1f}us  "
                    f"hops={row['hop_program_us']:>9.1f}us  "
                    f"stash={row['stash_bytes']:>10}B"
                    + ("  mem-win" if win else "")
                )
        return 0
    if args.serve_sweep:
        if args.hosts > 1:
            # the frontier prices the TP decode mesh of --world; silently
            # accepting --hosts would read as "priced that host split"
            # when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --serve-sweep (the decode "
                     "mesh is --world)")
        if args.slo_ms < 0:
            ap.error(f"--slo-ms must be >= 0, got {args.slo_ms}")
        rows = serve_sweep(
            world=args.world,
            rates=[float(r) for r in args.rates.split(",") if r],
            slots_grid=[int(s) for s in args.serve_slots.split(",") if s],
            num_requests=args.serve_requests,
            slo_ms=args.slo_ms if args.slo_ms > 0 else None,
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                att = row.get("slo_attainment")
                print(
                    f"[sim] serve rate={row['rate_req_per_step']:>5g} "
                    f"slots={row['slots']:>2} algo={row['algo']:<4} "
                    f"step={row['pred_step_us']:>8.1f}us  "
                    f"p50={row['p50_sojourn_ms']:>9.3f}ms "
                    f"p99={row['p99_sojourn_ms']:>9.3f}ms  "
                    f"tok/s={row['throughput_tok_s']:>11.1f}  "
                    f"util={row['utilization']:.3f}"
                    + (f"  slo={att:.3f}" if att is not None else "")
                )
        return 0
    if args.disagg_sweep:
        if args.hosts > 1:
            # the sweep fixes its own two-pod split of --world; silently
            # accepting --hosts would read as "priced that host split"
            # when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --disagg-sweep (the sweep "
                     "splits --world into its own prefill/decode pods)")
        if args.slo_ms < 0:
            ap.error(f"--slo-ms must be >= 0, got {args.slo_ms}")
        rows = disagg_sweep(
            world=args.world,
            mixes=[m for m in args.disagg_mixes.split(",") if m],
            splits=[s for s in args.disagg_splits.split(",") if s],
            dims=[int(d) for d in args.disagg_dims.split(",") if d],
            rate=args.disagg_rate,
            num_requests=args.serve_requests,
            total_slots=args.disagg_slots,
            slo_ms=args.slo_ms if args.slo_ms > 0 else None,
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = (
                    "*" if row["disagg_beats_colocated_p99_ttft"] else " "
                )
                print(
                    f"[sim] disagg {row['mix']:<13} {row['split']:<4} "
                    f"d={row['d_model']:>4}{star} "
                    f"ttft p99={row['p99_ttft_ms']:>9.3f}ms "
                    f"(coloc {row['coloc_p99_ttft_ms']:>9.3f}ms)  "
                    f"xfer={row['transfer_steps']:>2}st  "
                    f"tok/s={row['throughput_tok_s']:>10.1f} "
                    f"(coloc {row['coloc_throughput_tok_s']:>10.1f})"
                )
        return 0
    if args.fabric_sweep:
        if args.hosts > 1:
            # the sweep fixes its own two-pod split of --world; silently
            # accepting --hosts would read as "priced that host split"
            # when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --fabric-sweep (the sweep "
                     "uses a fixed two-pod split of --world)")
        rows = fabric_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            intensities=[
                float(i) for i in args.intensities.split(",") if i
            ],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = (
                    "*" if row.get("high_beats_uncoordinated") else " "
                )
                print(
                    f"[sim] fabric {row['size_bytes']:>12}B "
                    f"x{row['intensity']:g} {row['mix']:<9}{star} "
                    f"high={row['job0_us']:>10.1f}us "
                    f"({row['job0_strategy']})  "
                    f"peer={row['job1_us']:>10.1f}us "
                    f"({row['job1_strategy']})  "
                    f"fair={row['fairness']:.4f}"
                )
        return 0
    if args.hier_sweep:
        if args.hosts > 1:
            # the sweep grid names its own topologies (pods x pod_size);
            # silently accepting --hosts would read as "priced that host
            # split" when nothing used it (the --chaos-sweep precedent)
            ap.error("--hosts has no effect on --hier-sweep (use --pods/"
                     "--pod-sizes)")
        rows = hier_sweep(
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            pods=[int(p) for p in args.pods.split(",") if p],
            pod_sizes=[int(i) for i in args.pod_sizes.split(",") if i],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = "*" if row["two_level_faster"] else " "
                print(
                    f"[sim] hier {row['size_bytes']:>12}B "
                    f"pods={row['pods']:>3} pod_size={row['pod_size']:>2}{star} "
                    f"two_level={row['pred_two_level_us']:>10.1f}us  "
                    f"flat={row['pred_flat_us']:>10.1f}us  "
                    f"crossover_pods={row['crossover_pods']}"
                )
        return 0
    if args.recovery_sweep:
        if args.hosts > 1:
            # the grid names its own worlds and the replica piggyback is
            # priced on the ICI class alone; silently accepting --hosts
            # would read as "priced that host split" when nothing used it
            ap.error("--hosts has no effect on --recovery-sweep (use "
                     "--rec-worlds)")
        rows = recovery_sweep(
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            worlds=[int(w) for w in args.rec_worlds.split(",") if w],
            replicas=args.rec_replicas,
            save_interval_steps=args.rec_save_interval,
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            elif "skipped" in row:
                print(
                    f"[sim] recovery world={row['world']:>3} "
                    f"SKIP ({row['skipped']})"
                )
            else:
                star = "*" if row["overhead_ok"] else "!"
                print(
                    f"[sim] recovery world={row['world']:>3} "
                    f"{row['size_bytes']:>12}B k={row['replicas']}{star} "
                    f"overhead={row['replication_overhead_ratio']*100:>6.2f}% "
                    f"repair={row['replica_repair_us']:>10.1f}us  "
                    f"reload={row['ckpt_reload_us']:>12.1f}us  "
                    f"speedup={row['repair_speedup']:>8.1f}x"
                )
        return 0
    if args.chaos_sweep:
        if args.hosts > 1:
            # the liveness machine is topology-blind (a heartbeat is a
            # heartbeat): silently accepting --hosts would read as
            # "priced the multi-host layout" when nothing used it
            ap.error("--hosts has no effect on --chaos-sweep")
        rows = chaos_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            model=model,
            periods=[float(p) for p in args.hb_periods.split(",") if p],
            graces=[int(g) for g in args.hb_graces.split(",") if g],
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            elif row["phase"] == "detection":
                print(
                    f"[sim] chaos {row['size_bytes']:>12}B "
                    f"period={row['heartbeat_period_s']:>5}s "
                    f"grace={row['grace']} "
                    f"detect={row['detection_us']:>12.1f}us  "
                    f"confirm_window={row['confirm_window_s']:>6.2f}s  "
                    f"swap={row['swap_cached_us']:>7.1f}us"
                )
            else:
                print(
                    f"[sim] chaos {row['size_bytes']:>12}B schedule "
                    f"{row['actions']} actions ({row['kills']} kill, "
                    f"{row['stops']} stop/{row['conts']} cont) "
                    f"first_kill={row['first_kill_s']}s"
                )
        return 0
    if args.adapt_sweep:
        rows = adapt_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            hosts=args.hosts,
            model=model,
            degrade=args.degrade_factor,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            elif row["phase"] == "timeline":
                star = "*" if row["fired"] else " "
                print(
                    f"[sim] adapt {row['size_bytes']:>12}B "
                    f"step={row['step']:>2}{star} "
                    f"obs={row['observed_us']:>10.1f}us  "
                    f"ratio={row['ratio'] if row['ratio'] else 0:>7.3f}"
                )
            else:
                print(
                    f"[sim] adapt {row['size_bytes']:>12}B summary "
                    f"lag={row['detection_lag_steps']} steps  "
                    f"swap={row['hot_swap_stall_us']:>8.1f}us vs "
                    f"rebuild={row['full_rebuild_stall_us']:>12.1f}us  "
                    f"stale={row['stale_steady_us']:>10.1f}us -> "
                    f"adapted={row['adapted_steady_us']:>10.1f}us "
                    f"({row['adapted_label']})"
                )
        return 0
    if args.fault_sweep:
        rows = fault_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            hosts=args.hosts,
            model=model,
            heartbeat_timeout_s=args.heartbeat_timeout_s,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            elif row["phase"] == "failover":
                print(
                    f"[sim] fault {row['size_bytes']:>12}B "
                    f"{row['scenario']:<10} "
                    f"detect={row['detection_us']:>10.1f}us  "
                    f"swap={row['swap_cached_us']:>7.1f}us "
                    f"(cold {row['swap_cold_us']:>10.1f}us)  "
                    f"degraded_ratio={row['degraded_ratio']:.3f}"
                )
            else:
                star = "*" if row["swapped"] else " "
                print(
                    f"[sim] fault {row['size_bytes']:>12}B "
                    f"step={row['step']:>2} epoch={row['epoch']}{star} "
                    f"alive={len(row['alive'])} relays={len(row['relays'])} "
                    f"pred={row['pred_time_us']:>10.1f}us"
                )
        return 0
    if args.schedule_sweep:
        if args.hosts > 1:
            # the program grid prices the flat --world mesh; silently
            # accepting --hosts would read as "priced that host split"
            # when nothing used it (the --hier-sweep precedent)
            ap.error("--hosts has no effect on --schedule-sweep (programs "
                     "price the flat --world mesh)")
        rows = schedule_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            programs=[p.strip() for p in args.programs.split(",") if p.strip()],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                legacy = row["legacy_pred_time_us"]
                legacy_str = (
                    f"legacy={legacy:>10.1f}us" if legacy is not None
                    else f"lockstep={row['lockstep_ring_us']:>8.1f}us"
                    + ("*" if row.get("beats_lockstep_ring") else " ")
                )
                print(
                    f"[sim] schedule {row['size_bytes']:>12}B "
                    f"{row['strategy']:<20} "
                    f"pred={row['pred_time_us']:>10.1f}us  {legacy_str}  "
                    f"busbw={row['busbw_gbps']:>8.3f}GB/s  "
                    f"rounds={row['rounds']:>2} chunks={row['chunks']:>2}"
                )
        return 0
    if args.latency_sweep:
        rows = latency_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            algos=[a.strip() for a in args.algos.split(",") if a.strip()],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = "*" if row["chosen"] else " "
                print(
                    f"[sim] latency {row['size_bytes']:>12}B "
                    f"algo={row['algo']:<5}{star} "
                    f"pred={row['pred_time_us']:>10.1f}us  "
                    f"busbw={row['busbw_gbps']:>8.3f}GB/s  "
                    f"crossover={row['crossover_bytes']}"
                )
        return 0
    if args.overlap_sweep:
        rows = overlap_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            accums=[int(a) for a in args.accums.split(",") if a],
            bucket_caps_mb=[
                float(c) for c in args.bucket_caps_mb.split(",") if c
            ],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                print(
                    f"[sim] overlap {row['size_bytes']:>12}B "
                    f"accum={row['accum']} cap={row['bucket_cap_mb']:>5}MB "
                    f"ratio={row['compute_ratio']:>5} "
                    f"{row['overlap']:<10} "
                    f"step={row['pred_step_us']:>10.1f}us  "
                    f"exposed={row['exposed_comm_us']:>10.1f}us"
                )
        return 0
    if args.fused_sweep:
        rows = fused_wire_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            chunk_sizes=[parse_size(c) for c in args.chunks.split(",") if c],
            wire_dtypes=[
                w.strip() for w in args.fused_wire.split(",") if w.strip()
            ],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = "*" if row["fused_faster"] else " "
                print(
                    f"[sim] fused {row['size_bytes']:>12}B "
                    f"wire={row['wire_dtype']:<5} "
                    f"chunk={row['chunk_bytes']:>9}B{star} "
                    f"fused={row['pred_fused_us']:>10.1f}us  "
                    f"unfused={row['pred_unfused_us']:>10.1f}us  "
                    f"crossover={row['crossover_bytes']}"
                )
        return 0
    if args.tune_replay:
        rows = tune_replay_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            chunk_grid=[parse_size(c) for c in args.chunks.split(",") if c],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = "*" if row["chosen"] else (
                    "!" if row["surface_best"] else " "
                )
                med = row["median_us"]
                print(
                    f"[sim] tune {row['size_bytes']:>12}B "
                    f"{row['path']:<11} chunk={row['chunk_bytes']:>9} "
                    f"wire={row['wire_dtype']:<5}{star} "
                    f"n={row['samples']:>3}  "
                    f"median={med if med is not None else '-':>10}us  "
                    f"true={row['surface_us']:>10}us"
                )
        return 0
    if args.wire_dtype:
        rows = wire_dtype_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            wire_dtypes=[w.strip() for w in args.wire_dtype.split(",") if w.strip()],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                star = "*" if row["chosen"] else " "
                print(
                    f"[sim] quant {row['size_bytes']:>12}B "
                    f"wire={row['wire_dtype']:<5}{star} "
                    f"({row['wire_bytes_per_elem']:.3f} B/elem)  "
                    f"pred={row['pred_time_us']:>10.1f}us  "
                    f"busbw={row['busbw_gbps']:>8.3f}GB/s"
                )
        return 0
    if args.ring_sweep:
        rows = ring_chunk_sweep(
            world=args.world,
            sizes=[parse_size(s) for s in args.sizes.split(",")],
            chunk_sizes=[parse_size(c) for c in args.chunks.split(",") if c],
            model=model,
        )
        for row in rows:
            if args.json:
                print(json.dumps(row))
            else:
                print(
                    f"[sim] ring {row['size_bytes']:>12}B chunk="
                    f"{row['chunk_bytes']:>10}B  path={row['ring_path']:<10} "
                    f"pred={row['pred_time_us']:>10.1f}us  "
                    f"busbw={row['busbw_gbps']:>8.3f}GB/s"
                )
        return 0
    rows = sweep(
        world=args.world,
        sizes=[parse_size(s) for s in args.sizes.split(",")],
        collectives=[c.strip() for c in args.collectives.split(",") if c.strip()],
        strategies=[s.strip() for s in args.strategies.split(",") if s.strip()],
        model=model,
        hosts=args.hosts,
        degree=args.degree,
        flow_lp=not args.no_flow_lp,
    )
    for row in rows:
        if args.json:
            print(json.dumps(row))
        else:
            print(
                f"[sim] {row['collective']:<14} {row['strategy']:<10} "
                f"{row['size_bytes']:>12}B  pred={row['pred_time_us']:>10.1f}us  "
                f"busbw={row['busbw_gbps']:>8.3f}GB/s  ({row['calibration']})"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
