"""Drift → re-adaptation loop: the committed closure of the variability story.

The reference ships long-horizon variability traces
(cloud/trace/bandwidth-hw.txt: iperf readings dipping 14.7 → 1.7-scale) as
the *motivation* for periodic re-adaptation, but never a committed run of
the loop itself.  This harness drives the whole loop on the virtual pod,
A/B-ing BOTH re-adaptation paths against the same injected inter-host
degradation:

**Full-rebuild arm** (the reference's loop):

1. :class:`VariabilityMonitor` samples neighbor-ring probes over a
   ``--slices x --lanes`` two-level (DCN × ICI) world and appends the
   ``ts value`` trace files (the cloud/trace artifact shape);
2. a **synthetic inter-host degradation** (every host-0 ↔ host-1 link's
   bandwidth scaled by ``--factor`` from sample ``--degrade-at`` — the
   inter-VM drift the reference's study measures) is injected at the
   physical seam — the probe timing and the profiler's measured matrices —
   leaving every downstream stage real;
3. the monitor's drift detector fires ``on_drift``, which calls the real
   ``AdapCC.reconstruct_topology`` (clear contexts → detect → profile →
   ParTrees re-synthesis → rebuild engines) — active probe traffic plus a
   cold recompile, with the walltime recorded as the **rebuild stall**;
4. the re-synthesized strategy re-routes its master trees around the
   degraded DCN path — its fingerprint changes — and a post-rebuild
   allreduce oracle proves the contexts came back alive.

**Hot-swap arm** (docs/ADAPT.md — this PR's headline):

the same degradation factor drives the *passive* loop instead: a
:class:`DriftDetector` is fed the degraded timing series (what the flows
already measure — zero probe traffic), fires, the α-β model re-calibrates
with decay, sim-rank re-ranks the candidate strategies under the corrected
costs, the winner is AOT-compiled through the standby cache, and adoption
is one ``advance_epoch`` — the **epoch-swap stall**, measured next to the
rebuild arm's.  The A/B row (``hotswap_stall_s`` vs ``rebuild_stall_s``)
prices what the closed loop buys.

Attribution control (both arms): with the link healthy, a full
re-adaptation leaves the strategy fingerprint *unchanged* and the passive
loop performs *zero* swaps — so each arm's post-drift change is
attributable to the drift, not to re-synthesis nondeterminism.

Usage::

    python -m benchmarks.drift_loop --world 8 --samples 24 --degrade-at 10
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> Dict:

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slices", type=int, default=4,
                    help="DCN hosts (needs >= 3 for master re-routing)")
    ap.add_argument("--lanes", type=int, default=2, help="ICI lanes per host")
    ap.add_argument("--samples", type=int, default=24)
    ap.add_argument("--degrade-at", type=int, default=10)
    ap.add_argument("--factor", type=float, default=0.1,
                    help="degraded host-0<->host-1 bandwidth multiplier")
    ap.add_argument("--threshold", type=float, default=0.6,
                    help="drift threshold: above CPU-box probe noise "
                    "(~30-50%% swings under load), far below the injected "
                    "10x drop")
    ap.add_argument("--consecutive", type=int, default=2,
                    help="sustained-drop requirement: single noisy probes "
                    "on a loaded host must not fire a re-synthesis")
    ap.add_argument("--drift-window", type=int, default=4,
                    help="hot-swap arm: passive detector window (samples)")
    ap.add_argument("--out-dir", default=None,
                    help="trace-file directory (cloud/trace analog)")
    ap.add_argument("--out", default=None, help="append the JSON summary here")
    ap.add_argument("--workdir", default=None,
                    help="bootstrap artifact dir (default: a temp dir)")
    args = ap.parse_args(argv)

    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapcc_tpu import ALLREDUCE, DETECT, AdapCC
    from adapcc_tpu.comm.two_level import build_two_level_mesh
    from adapcc_tpu.config import CommArgs
    from adapcc_tpu.strategy.xml_io import parse_strategy_xml
    from adapcc_tpu.topology.profile import NetworkProfiler
    from adapcc_tpu.topology.variability import VariabilityMonitor

    world = args.slices * args.lanes
    mesh = build_two_level_mesh(args.slices, args.lanes)
    link = {"factor": 1.0}  # emulated physical state of the host0<->host1 path
    h0 = list(range(args.lanes))
    h1 = list(range(args.lanes, 2 * args.lanes))

    # -- injection seam: what the profiler *measures* ----------------------
    # Deterministic matrices (uniform 10 GB/s, 10 us) with the degraded
    # inter-host links scaled — deterministic so a fingerprint change is
    # attributable to the drift, not to probe noise between re-synthesis
    # runs.  ONE definition feeds both arms: the profiler seam (rebuild)
    # and the passive calibration (hot-swap).
    def degraded_matrices(factor: float):
        lat = np.full((world, world), 1e-5)
        bw = np.full((world, world), 10.0)
        np.fill_diagonal(lat, 0.0)
        np.fill_diagonal(bw, 0.0)
        for a in h0:
            for b in h1:
                bw[a, b] = bw[b, a] = 10.0 * factor
        return lat, bw

    def synthetic_profile(self):
        return degraded_matrices(link["factor"])

    orig_profile = NetworkProfiler.profile
    NetworkProfiler.profile = synthetic_profile
    try:
        workdir = args.workdir or tempfile.mkdtemp(prefix="adapcc-drift-")
        comm_args = CommArgs(
            strategy_file=os.path.join(workdir, "strategy.xml"),
            logical_graph=os.path.join(workdir, "logical_graph.xml"),
            topology_dir=workdir,
            entry_point=DETECT,
            parallel_degree=2,
        )
        AdapCC.init(comm_args, mesh=mesh)
        AdapCC.setup(ALLREDUCE)
        fp_initial = parse_strategy_xml(comm_args.strategy_file).fingerprint()

        # -- attribution control: healthy re-adaptation is a no-op ---------
        AdapCC.reconstruct_topology(comm_args, ALLREDUCE)
        fp_control = parse_strategy_xml(comm_args.strategy_file).fingerprint()
        if fp_control != fp_initial:
            raise RuntimeError(
                "control re-adaptation changed the strategy on a healthy "
                f"fabric ({fp_initial} -> {fp_control}); drift attribution "
                "would be unsound"
            )

        # -- hot-swap arm (docs/ADAPT.md): the passive closed loop ---------
        hotswap = _hot_swap_arm(
            AdapCC.communicator, world, degraded_matrices, args.factor,
            window=args.drift_window, workdir=workdir,
        )

        # -- monitored run with mid-run degradation (full-rebuild arm) -----
        drift_events: List[Dict] = []
        rebuild = {"stall_s": None}

        def on_drift(gbps: float) -> None:
            if drift_events:
                return  # re-adapt once per incident
            drift_events.append({"sample": state["i"], "bw_gbps": gbps})
            t0 = time.perf_counter()
            AdapCC.reconstruct_topology(comm_args, ALLREDUCE)
            rebuild["stall_s"] = time.perf_counter() - t0

        # on_drift attaches after warmup — compile-time spikes must not
        # consume the one re-adaptation
        monitor = VariabilityMonitor(
            mesh,
            interval_s=0.0,
            probe_floats=1 << 14,
            drift_threshold=args.threshold,
            drift_consecutive=args.consecutive,
            drift_direction="down",  # re-adaptation exists for degradations
        )
        # probe-timing seam: the neighbor-ring probe slows when the path does
        orig_probe = monitor._bw_probe
        monitor._bw_probe = lambda: orig_probe() / link["factor"]

        # warm the probe programs OUTSIDE the measured trace: first-call
        # compile time reads as a huge upward bandwidth step and would trip
        # the (direction-agnostic) drift detector at sample 1
        state = {"i": -1}
        for _ in range(3):
            monitor.sample()
        monitor.bandwidth_trace.clear()
        monitor.latency_trace.clear()
        monitor.on_drift = on_drift
        monitor.out_dir = args.out_dir
        if args.out_dir:
            os.makedirs(args.out_dir, exist_ok=True)
            for name in ("bandwidth.txt", "latency.txt"):
                # trace files are append-mode; a refreshed artifact must not
                # inherit a previous run's rows
                try:
                    os.remove(os.path.join(args.out_dir, name))
                except FileNotFoundError:
                    pass

        for i in range(args.samples):
            state["i"] = i
            if i == args.degrade_at:
                link["factor"] = args.factor
            monitor.sample()

        fp_after = parse_strategy_xml(comm_args.strategy_file).fingerprint()

        # -- post-rebuild liveness oracle ----------------------------------
        x = jnp.stack([jnp.ones(16) * 3.0 for _ in range(world)])
        out = AdapCC.allreduce(x, size=16)
        assert np.allclose(np.asarray(out), 3.0 * world), "post-rebuild allreduce"
        AdapCC.clear(ALLREDUCE)

        bw_values = [v for _, v in monitor.bandwidth_trace]
        summary = {
            "world": world,
            "samples": args.samples,
            "degrade_at": args.degrade_at,
            "factor": args.factor,
            "drift_detected_at": drift_events[0]["sample"] if drift_events else None,
            "bw_before_median": round(
                float(np.median(bw_values[: args.degrade_at])), 4
            ),
            "bw_after_median": round(
                float(np.median(bw_values[args.degrade_at :])), 4
            ),
            "fingerprint_initial": fp_initial,
            "fingerprint_control": fp_control,
            "fingerprint_after_drift": fp_after,
            "strategy_changed": fp_after != fp_initial,
            # the A/B headline: what one re-adaptation STALLS the job for
            # on each arm — the full-rebuild teardown walltime vs the
            # epoch-swap's advance_epoch walltime (hot-swap AOT warm runs
            # off the critical path and is reported separately)
            "rebuild_stall_s": (
                round(rebuild["stall_s"], 6)
                if rebuild["stall_s"] is not None else None
            ),
            "hotswap_stall_s": hotswap["stall_s"],
            "rebuild": rebuild["stall_s"] and {
                "stall_s": round(rebuild["stall_s"], 6),
                "fingerprint_changed": fp_after != fp_initial,
            },
            "hotswap": hotswap,
            "backend": jax.devices()[0].platform,
        }
        print(json.dumps(summary), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(summary) + "\n")
        return summary
    finally:
        NetworkProfiler.profile = orig_profile


def _hot_swap_arm(
    communicator, world: int, degraded_matrices, factor: float,
    window: int, workdir: str,
) -> Dict:
    """Run the passive closed loop (docs/ADAPT.md) against the same
    injected degradation: healthy control first (zero swaps pinned), then
    the degraded timing series → detect → re-calibrate → re-rank →
    epoch-swap, with the swap stall measured next to the rebuild arm's.

    The arm runs on its own flat engine over the same devices, starting
    from the flat default ring (the pre-synthesis incumbent a world runs
    before any strategy artifact exists) — the stale strategy the loop
    must route around.  Timings fed to the detector are the calibrated
    model's own predictions under the healthy/degraded matrices: exactly
    what a live run's DispatchTimer medians converge to, deterministic so
    the A/B is attributable.
    """
    import jax.numpy as jnp
    import numpy as np

    from adapcc_tpu.adapt import AdaptationController
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.sim.calibrate import calibrate_from_matrices
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.strategy.synthesizer import Synthesizer
    from adapcc_tpu.tuner.db import TuningDatabase, TuningKey, size_bucket
    from adapcc_tpu.tuner.policy import TuningPolicy
    from adapcc_tpu.utils.observability import CollectiveTrace

    ips = {r: ip for r, ip in enumerate(communicator.ip_table)}
    lat_h, bw_h = degraded_matrices(1.0)
    healthy = calibrate_from_matrices(lat_h, bw_h, ips, source="drift-healthy")
    lat_d, bw_d = degraded_matrices(factor)
    degraded = calibrate_from_matrices(
        lat_d, bw_d, ips, source="drift-degraded"
    )

    mesh = build_world_mesh(world)
    trace = CollectiveTrace()
    incumbent = Strategy.ring(world, 1, ips)
    engine = CollectiveEngine(mesh, incumbent, trace=trace)
    synthesizer = Synthesizer(None, list(communicator.ip_table))
    cal_path = os.path.join(workdir, "calibration.json")
    from adapcc_tpu.adapt import DriftDetector
    from adapcc_tpu.tuner.db import topology_fingerprint

    fingerprint = topology_fingerprint(world, ips)
    controller = AdaptationController(
        engine,
        synthesizer,
        mode="swap",
        cost_model=healthy.cost_model(),
        calibration_path=cal_path,
        nbytes=1 << 20,
        parallel_degree=2,
        fingerprint=fingerprint,
        detector=DriftDetector(
            world, fingerprint, cost_model=healthy.cost_model(),
            window=window,
        ),
        warm_shape=(64,),
    )

    nb = 1 << 20
    key = TuningKey(
        "allreduce", size_bucket(nb), world, controller.fingerprint,
        "xla", 0, "off",
    )
    healthy_pred = controller.detector.predicted_s(key)
    deg_policy = TuningPolicy(
        TuningDatabase(persist=False), world, "drift-loop",
        cost_model=degraded.cost_model(),
    )
    degraded_obs = deg_policy.prior_time(key, key.size_bucket)

    # attribution control: a healthy series must produce ZERO swaps
    for i in range(window):
        controller.observe(key, healthy_pred * (1.05 if i % 2 else 0.95))
    control_report = controller.maybe_adapt()
    if control_report.swapped:
        raise RuntimeError(
            "hot-swap control adapted on a healthy series; drift "
            "attribution would be unsound"
        )

    # the degradation lands in the measured series — nothing else
    detection_samples = 0
    fired = False
    for i in range(window):
        controller.observe(key, degraded_obs * (1.02 if i % 2 else 0.98))
        detection_samples = i + 1
        if controller.check().drifted:
            fired = True
            break
    report = controller.maybe_adapt()

    # the post-swap dispatch must replay a warm program (cache-key switch)
    x = jnp.ones((world, 64), jnp.float32)
    engine.all_reduce(x, active_gpus=list(range(world)))
    cache_hit = bool(trace.events()[-1].extra.get("cache_hit"))

    from adapcc_tpu.sim.cost_model import adaptation_cost, bottleneck_ring_coeffs

    priced = None
    if report.swapped and report.incumbent_pred_s is not None:
        cost = adaptation_cost(
            world, nb,
            bottleneck_ring_coeffs(healthy.cost_model(), world),
            stale_steady_s=report.incumbent_pred_s,
            adapted_steady_s=report.winner_pred_s,
        )
        priced = {
            k: (round(v, 9) if np.isfinite(v) else None)
            for k, v in cost.items()
        }
    return {
        "control_swapped": bool(control_report.swapped),
        "fired": fired,
        "detection_samples": detection_samples,
        "window": window,
        "outcome": report.outcome,
        "swapped": bool(report.swapped),
        "winner_label": report.winner_label,
        "fingerprint_before": incumbent.fingerprint(),
        "fingerprint_after": engine.strategy.fingerprint(),
        "strategy_changed": (
            engine.strategy.fingerprint() != incumbent.fingerprint()
        ),
        "cache_hit": cache_hit,
        "stall_s": round(report.stall_s, 6) if report.stall_s else None,
        "aot_warm_s": (
            round(report.aot_warm_s, 6) if report.aot_warm_s else None
        ),
        "recalibrated": report.recalibrated,
        "ranked": report.ranked,
        "priced": priced,
    }


if __name__ == "__main__":
    main()
