"""Trace-driven collective simulator and calibrated α-β cost model.

Without a chip nothing can be ranked or regressed if every number needs
live hardware.  This package is the hardware-free half of the profile →
synthesize → execute loop: an analytical per-link α-β (latency +
inverse-bandwidth) cost model calibrated from the profiler's probe CSVs or
committed hardware traces, a discrete-event engine that replays
schedule-IR rounds with chunk pipelining and link contention, and a
ranking API the synthesizer uses where no chip is attached.

The same modeling family TACCL and SCCL (PAPERS.md) use to rank candidate
schedules offline — here wired to this repo's strategy IR, relay masks, and
artifact formats.

Layers:

- :mod:`adapcc_tpu.sim.cost_model` — per-link α-β coefficients with ICI/DCN
  link classes, least-squares fit from probe points;
- :mod:`adapcc_tpu.sim.events` — discrete-event replay of communication
  rounds (chunk pipelining, merged-tree round coloring, link/port
  contention);
- :mod:`adapcc_tpu.sim.replay` — lower strategies / XML schedules / flow-LP
  solutions into simulated timelines;
- :mod:`adapcc_tpu.sim.rank` — strategy ranking + straggler/relay
  degradation prediction;
- :mod:`adapcc_tpu.sim.calibrate` — fit + persist calibration artifacts so
  simulated numbers stay anchored to the last good hardware round.
"""

from adapcc_tpu.sim.congestion import (
    CONGESTION_PROFILE_ENV,
    CongestionProfile,
    CongestionWindow,
    load_congestion_profile,
)
from adapcc_tpu.sim.cost_model import (
    DCN,
    ICI,
    LinkCoeffs,
    LinkCostModel,
    bandwidth_lower_bound,
    choose_wire_dtype,
    collective_lower_bound,
    congested_ring_allreduce_time,
    congested_two_level_allreduce_time,
    contended_coeffs,
    contended_lower_bound,
    disagg_queue_metrics,
    fastest_coeffs,
    fit_alpha_beta,
    latency_lower_bound,
    optimality_gap,
    quantized_ring_allreduce_time,
    simulate_disagg_queue,
    wire_bytes_per_element,
)
from adapcc_tpu.sim.events import EventSimulator, SimReport, Transfer, TreeSchedule
from adapcc_tpu.sim.vector import (
    SIM_ENGINE_ENV,
    SIM_ENGINES,
    VECTOR_MIN_WORLD,
    LoweredColumns,
    ProgramColumns,
    clear_lowering_cache,
    clear_program_cache,
    lowered_columns,
    lowering_cache_info,
    program_cache_info,
    program_columns,
    resolve_sim_engine,
    vector_program_run,
    vector_run,
)
from adapcc_tpu.sim.replay import (
    CongestionStepRow,
    SimTimeline,
    simulate_broadcast,
    simulate_congestion_profile,
    simulate_flow_broadcast,
    simulate_reduce,
    simulate_strategy,
    simulate_xml,
)
from adapcc_tpu.sim.rank import (
    RankedCandidate,
    predict_degradation,
    rank_candidates,
    relay_latency,
)
from adapcc_tpu.sim.calibrate import (
    Calibration,
    calibrate_from_battery,
    calibrate_from_matrices,
    calibrate_from_profile_dir,
    load_calibration,
)

__all__ = [
    "CONGESTION_PROFILE_ENV",
    "CongestionProfile",
    "CongestionStepRow",
    "CongestionWindow",
    "DCN",
    "ICI",
    "SIM_ENGINE_ENV",
    "SIM_ENGINES",
    "VECTOR_MIN_WORLD",
    "LoweredColumns",
    "ProgramColumns",
    "bandwidth_lower_bound",
    "clear_lowering_cache",
    "clear_program_cache",
    "collective_lower_bound",
    "fastest_coeffs",
    "latency_lower_bound",
    "lowered_columns",
    "lowering_cache_info",
    "optimality_gap",
    "program_cache_info",
    "program_columns",
    "resolve_sim_engine",
    "vector_program_run",
    "vector_run",
    "LinkCoeffs",
    "LinkCostModel",
    "choose_wire_dtype",
    "congested_ring_allreduce_time",
    "congested_two_level_allreduce_time",
    "contended_coeffs",
    "contended_lower_bound",
    "disagg_queue_metrics",
    "simulate_disagg_queue",
    "fit_alpha_beta",
    "load_congestion_profile",
    "simulate_congestion_profile",
    "quantized_ring_allreduce_time",
    "wire_bytes_per_element",
    "EventSimulator",
    "SimReport",
    "Transfer",
    "TreeSchedule",
    "SimTimeline",
    "simulate_broadcast",
    "simulate_flow_broadcast",
    "simulate_reduce",
    "simulate_strategy",
    "simulate_xml",
    "RankedCandidate",
    "predict_degradation",
    "rank_candidates",
    "relay_latency",
    "Calibration",
    "calibrate_from_battery",
    "calibrate_from_matrices",
    "calibrate_from_profile_dir",
    "load_calibration",
]
