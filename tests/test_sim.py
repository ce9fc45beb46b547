"""Simulator subsystem: α-β calibration, event replay, ranking, degradation.

Everything here is analytic (no backend, no wall clock, no RNG), so the
whole file runs deterministically in tier-1 — the point of the subsystem:
strategy decisions stay priced even where no chip is attached.
"""

import json
import sys

import numpy as np
import pytest

from adapcc_tpu.sim import (
    Calibration,
    EventSimulator,
    LinkCoeffs,
    LinkCostModel,
    calibrate_from_battery,
    calibrate_from_matrices,
    calibrate_from_profile_dir,
    fit_alpha_beta,
    predict_degradation,
    rank_candidates,
    relay_latency,
    simulate_flow_broadcast,
    simulate_strategy,
    simulate_xml,
)
from adapcc_tpu.sim.calibrate import load_or_default
from adapcc_tpu.sim.cost_model import (
    BANDWIDTH_PROBE_BYTES,
    DCN,
    ICI,
    LATENCY_PROBE_BYTES,
    ring_allreduce_time,
)
from adapcc_tpu.sim.events import TreeSchedule
from adapcc_tpu.strategy.ir import Strategy

MB = 1 << 20

#: ground-truth wire for the synthetic-trace round trips
ALPHA, BETA = 2e-6, 1.0 / 40e9


def uniform_model(world, alpha=ALPHA, beta=BETA):
    return LinkCostModel.uniform(world, alpha=alpha, beta=beta)


def single_chunk(strategy):
    """Force one chunk so the replay matches the unpipelined oracle."""
    strategy.chunk_bytes = 1 << 40
    return strategy


# --------------------------------------------------------------------------- #
# α-β fitting
# --------------------------------------------------------------------------- #

def test_fit_alpha_beta_recovers_exact_line():
    pts = [(n, ALPHA + BETA * n) for n in (256, 4 * MB)]
    c = fit_alpha_beta(pts)
    assert c.alpha == pytest.approx(ALPHA, rel=1e-9)
    assert c.beta == pytest.approx(BETA, rel=1e-9)


def test_fit_alpha_beta_clamps_noise_to_physical():
    # big transfer "measured" faster than the small one → slope would be
    # negative; the model must never pay you to send data
    c = fit_alpha_beta([(256, 1e-4), (4 * MB, 1e-6)])
    assert c.alpha >= 0 and c.beta >= 0


def test_fit_single_point_is_pure_latency():
    c = fit_alpha_beta([(256, 3e-6)])
    assert (c.alpha, c.beta) == (3e-6, 0.0)


def test_cost_model_classes_and_fallback():
    ips = {0: "a", 1: "a", 2: "b", 3: "b"}
    m = LinkCostModel(4, ips=ips)
    assert m.link_class_of(0, 1) == ICI and m.link_class_of(1, 2) == DCN
    # unprobed links price at class coefficients — DCN costs more
    assert m.time_for(1, 2, MB) > m.time_for(0, 1, MB)


# --------------------------------------------------------------------------- #
# calibration round trips
# --------------------------------------------------------------------------- #

def probe_matrices(world):
    """What the profiler would measure on an ideal (ALPHA, BETA) wire."""
    lat = np.zeros((world, world))
    bw = np.zeros((world, world))
    for s in range(world):
        for d in range(world):
            if s == d:
                continue
            lat[s][d] = ALPHA + BETA * LATENCY_PROBE_BYTES
            t_bw = ALPHA + BETA * BANDWIDTH_PROBE_BYTES
            bw[s][d] = BANDWIDTH_PROBE_BYTES / t_bw / 1e9
    return lat, bw


def test_calibration_roundtrip_from_probe_csvs(tmp_path):
    """CSV shards → fit → save → load → the model prices the true wire."""
    world = 4
    lat, bw = probe_matrices(world)
    shard = tmp_path / "topo_profile_0"
    with open(shard, "w") as f:
        for s in range(world):
            for d in range(world):
                if s == d:
                    continue
                f.write(f"{s},{d},lat,{lat[s][d]:.12f}\n")
                f.write(f"{s},{d},bw,{bw[s][d]:.9f}\n")
    cal = calibrate_from_profile_dir(str(tmp_path), world)
    path = cal.save(str(tmp_path / "calibration.json"))
    model = Calibration.load(path).cost_model()
    for nbytes in (256, MB, 64 * MB):
        truth = ALPHA + BETA * nbytes
        assert model.time_for(0, 1, nbytes) == pytest.approx(truth, rel=0.05)
    assert model.source.startswith("profile:")


def test_calibration_matrices_roundtrip_dict():
    lat, bw = probe_matrices(3)
    cal = calibrate_from_matrices(lat, bw, ips={0: "a", 1: "a", 2: "b"})
    clone = Calibration.from_dict(
        json.loads(json.dumps(cal.to_dict()))
    )
    assert clone.world == 3 and clone.links == cal.links
    assert clone.ips == cal.ips


def test_calibration_version_gate():
    with pytest.raises(ValueError, match="version"):
        Calibration.from_dict({"version": 0, "world": 4, "classes": {}})


def test_battery_calibration_roundtrip(tmp_path):
    """Busbw sweep rows generated from the true wire → recovered (α, β)."""
    rows = []
    for collective, (rounds_fn, byte_fn) in (
        ("allreduce", (lambda w: 2 * (w - 1), lambda w: 2 * (w - 1) / w)),
        ("broadcast", (lambda w: w - 1, lambda w: 1.0)),
    ):
        for size in (4096, 16 * MB):
            w = 8
            t = rounds_fn(w) * ALPHA + byte_fn(w) * size * BETA
            rows.append({
                "collective": collective, "impl": "xla", "world": w,
                "size_bytes": size, "time_us": t * 1e6,
            })
    art = tmp_path / "hw_sim.jsonl"
    art.write_text(
        json.dumps({"phase": "busbw", "rows": rows}) + "\n"
        + json.dumps({"phase": "junk, not json"})[:-2] + "\n"  # tolerated
    )
    cal = calibrate_from_battery(str(art))
    assert cal is not None
    ici = cal.classes[ICI]
    assert ici.alpha == pytest.approx(ALPHA, rel=0.02)
    assert ici.beta == pytest.approx(BETA, rel=0.02)
    # DCN stays priced worse than ICI even though the battery never saw it
    assert cal.classes[DCN].beta > ici.beta


def test_battery_rows_not_double_counted_via_parsed(tmp_path):
    """A battery artifact stores every sweep row in "rows" AND the last line
    again in "parsed"; the fit must see each measurement once, or the
    largest sweep size gets double weight in the lstsq design."""
    from adapcc_tpu.sim.calibrate import _battery_rows

    r1 = {"collective": "allreduce", "impl": "xla", "world": 8,
          "size_bytes": 4096, "time_us": 10.0}
    r2 = {"collective": "allreduce", "impl": "xla", "world": 8,
          "size_bytes": 16 * MB, "time_us": 900.0}
    art = tmp_path / "hw_dup.jsonl"
    art.write_text(json.dumps({"rows": [r1, r2], "parsed": r2}) + "\n")
    assert len(_battery_rows(str(art))) == 2
    # single-line phases (no rows list) still contribute their parsed row
    art.write_text(json.dumps({"parsed": r1}) + "\n")
    assert len(_battery_rows(str(art))) == 1


def test_battery_calibration_refuses_single_size(tmp_path):
    row = {"collective": "allreduce", "impl": "xla", "world": 8,
           "size_bytes": 4096, "time_us": 10.0}
    art = tmp_path / "hw_one.jsonl"
    art.write_text(json.dumps({"rows": [row, dict(row)]}) + "\n")
    assert calibrate_from_battery(str(art)) is None


def test_load_or_default_missing_and_resize(tmp_path):
    model = load_or_default(str(tmp_path / "absent.json"), world=4)
    assert model.world == 4 and model.source == "defaults"
    lat, bw = probe_matrices(4)
    path = calibrate_from_matrices(lat, bw).save(str(tmp_path / "c.json"))
    resized = load_or_default(path, world=16)
    assert resized.world == 16
    # class coefficients survive the resize, so links still price ≈ true wire
    assert resized.time_for(0, 9, MB) == pytest.approx(
        ALPHA + BETA * MB, rel=0.05
    )


# --------------------------------------------------------------------------- #
# event replay vs the analytical oracle
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("world", [2, 4, 8])
def test_ring_allreduce_matches_oracle_single_chunk(world):
    model = uniform_model(world)
    t = simulate_strategy(single_chunk(Strategy.ring(world)), model, MB)
    oracle = ring_allreduce_time(world, MB, model.coeffs(0, 1), chunks=1)
    assert t.seconds == pytest.approx(oracle, rel=1e-9)
    assert t.to_row()["mode"] == "simulated"


def test_ring_allreduce_pipelined_tracks_oracle():
    """Chunked replay sits between the multi-port lower bound and the bound
    plus a small port-conflict constant (single-port model)."""
    world, nbytes = 8, 8 * MB
    model = uniform_model(world)
    ring = Strategy.ring(world)
    ring.chunk_bytes = MB  # 8 pipelined chunks
    sim = simulate_strategy(ring, model, nbytes).seconds
    chunks = 8
    lower = ring_allreduce_time(world, nbytes, model.coeffs(0, 1), chunks)
    per_hop = model.coeffs(0, 1).time(nbytes / chunks)
    # the single-port replay pays at most one port-conflict hop per chunk
    # where the reduce tail overlaps the broadcast head (measured: chunks−2)
    assert lower <= sim <= lower + chunks * per_hop
    # and pipelining must beat the unpipelined schedule
    assert sim < ring_allreduce_time(world, nbytes, model.coeffs(0, 1), 1)


def test_replay_utilization_and_bytes_accounting():
    model = uniform_model(4)
    t = simulate_strategy(single_chunk(Strategy.ring(4)), model, MB)
    # chain allreduce: 3 up-edges + 3 down-edges, full payload each
    assert t.report.bytes_moved() == pytest.approx(6 * MB)
    for frac in t.per_link_utilization().values():
        assert 0.0 < frac <= 1.0


def test_contention_serializes_shared_link():
    """Two trees pushing the same directed edge in one color cannot overlap."""
    from adapcc_tpu.strategy.ir import CommRound

    model = uniform_model(2)
    rounds = [CommRound(((0, 1),))]
    one = EventSimulator(model).run(
        [TreeSchedule(rounds=list(rounds), nbytes=MB, chunk_bytes=1 << 40)]
    )
    two = EventSimulator(model).run(
        [TreeSchedule(rounds=list(rounds), nbytes=MB, chunk_bytes=1 << 40),
         TreeSchedule(rounds=list(rounds), nbytes=MB, chunk_bytes=1 << 40)]
    )
    assert two.makespan == pytest.approx(2 * one.makespan, rel=1e-9)


def test_simulate_xml_equals_in_memory_strategy(tmp_path):
    from adapcc_tpu.strategy.xml_io import emit_strategy_xml

    strategy = Strategy.binary(8, num_trans=2)
    path = str(tmp_path / "strategy.xml")
    emit_strategy_xml(strategy, path)
    model = uniform_model(8)
    assert simulate_xml(path, model, MB).seconds == pytest.approx(
        simulate_strategy(strategy, model, MB).seconds, rel=1e-9
    )


# --------------------------------------------------------------------------- #
# ranking
# --------------------------------------------------------------------------- #

def test_rank_orders_fastest_first_and_keeps_incumbent_on_tie():
    model = uniform_model(8)
    ring, binary = Strategy.ring(8), Strategy.binary(8)
    ranked = rank_candidates(
        [("ring", ring), ("binary", binary)], model, MB
    )
    assert [r.label for r in ranked] == ["binary", "ring"]  # log-depth wins
    assert ranked[0].seconds <= ranked[1].seconds
    # identical candidates tie → input order preserved (incumbent first)
    tie = rank_candidates(
        [("incumbent", Strategy.ring(8)), ("challenger", Strategy.ring(8))],
        model, MB,
    )
    assert tie[0].label == "incumbent"


def test_flow_lp_never_worse_than_dominated_chain():
    """The LP optimum can only match or beat the chain broadcast it
    strictly dominates (same links, strictly more routing freedom)."""
    pytest.importorskip("scipy")
    from adapcc_tpu.strategy.flow_lp import solve_broadcast_lp

    world = 6
    model = uniform_model(world)
    edges = [(s, d) for s in range(world) for d in range(world) if s != d]
    flow = solve_broadcast_lp(
        world, edges, [1.0 / BETA] * len(edges)
    )
    flow_tl = simulate_flow_broadcast(flow, model, MB)
    chain = single_chunk(Strategy.ring(world))
    ranked = rank_candidates(
        [("flow-lp", flow_tl), ("chain", chain)], model, MB,
        collective="broadcast",
    )
    by_label = {r.label: r.seconds for r in ranked}
    assert by_label["flow-lp"] <= by_label["chain"] * (1 + 1e-9)


def test_flow_redundant_delivery_never_delays_a_ready_node():
    """Alternate LP optima can park flow on edges into nodes that already
    hold the payload (including the source); receiving data you have must
    not push your readiness later and delay your own sends."""
    from types import SimpleNamespace

    model = uniform_model(3)
    hop = ALPHA + BETA * MB
    flow = SimpleNamespace(
        source=0,
        num_nodes=3,
        rounds=[
            {(0, 1): 1.0},        # source seeds node 1
            {(1, 0): 0.5},        # redundant: lands back on the source
            {(0, 2): 1.0},        # the source's own send must not wait on it
        ],
    )
    tl = simulate_flow_broadcast(flow, model, MB)
    # (0,2) starts as soon as the source's port frees after round 1 — the
    # redundant round-2 delivery adds no dependency edge
    assert tl.seconds == pytest.approx(2 * hop, rel=1e-9)


def test_flow_partial_delivery_does_not_grant_readiness():
    """A node holding only half the payload must not forward the whole of
    it: readiness requires CUMULATIVE receipts to cover the payload, so the
    relay send waits for the complementary fraction (store-and-forward)."""
    from types import SimpleNamespace

    model = uniform_model(3)
    half = ALPHA + BETA * (MB / 2)
    full = ALPHA + BETA * MB
    flow = SimpleNamespace(
        source=0,
        num_nodes=3,
        rounds=[
            {(0, 1): 0.5},        # first half lands at t=half
            {(0, 1): 0.5},        # second half lands at t=2*half (same link)
            {(1, 2): 1.0},        # may start only once BOTH halves arrived
        ],
    )
    tl = simulate_flow_broadcast(flow, model, MB)
    assert tl.seconds == pytest.approx(2 * half + full, rel=1e-9)


def test_rank_rejects_empty():
    with pytest.raises(ValueError, match="at least one"):
        rank_candidates([], uniform_model(4), MB)


# --------------------------------------------------------------------------- #
# relay masks and degradation
# --------------------------------------------------------------------------- #

def test_relay_mask_latency_monotone_in_active_set():
    """Nested shrinking active sets prune supersets of edges → predicted
    latency is non-increasing (the relay controller's core assumption)."""
    world = 8
    model = uniform_model(world)
    strategy = single_chunk(Strategy.binary(world))
    nested = [list(range(world)), [0, 1, 2, 3, 4, 5], [0, 1, 2, 3], [0, 1]]
    times = [
        relay_latency(strategy, model, MB, active) for active in nested
    ]
    for wider, narrower in zip(times, times[1:]):
        assert narrower <= wider * (1 + 1e-9)


def test_degradation_ratio_monotone_in_slowdown():
    model = uniform_model(8)
    strategy = Strategy.ring(8)
    ratios = [
        predict_degradation(strategy, model, MB, [3], slowdown=s).ratio
        for s in (1.0, 2.0, 4.0, 8.0)
    ]
    assert ratios[0] == pytest.approx(1.0)
    for a, b in zip(ratios, ratios[1:]):
        assert b >= a - 1e-12
    # stretching links can never make the collective faster
    assert all(r >= 1.0 - 1e-12 for r in ratios)


def test_degradation_relay_gain_is_never_a_loss():
    """Under the same degraded wire, masking the stragglers prunes edges —
    the relay prediction can't exceed the unmasked degraded one."""
    model = uniform_model(8)
    rep = predict_degradation(
        Strategy.binary(8), model, MB, [6, 7], slowdown=8.0
    )
    assert rep.relay_seconds <= rep.degraded_seconds * (1 + 1e-9)
    assert rep.relay_gain >= 1.0 - 1e-9


def test_degraded_model_validates_slowdown():
    with pytest.raises(ValueError, match="slowdown"):
        uniform_model(4).degraded([0], 0.5)


# --------------------------------------------------------------------------- #
# the simulated bench and harness fallback
# --------------------------------------------------------------------------- #

def test_sim_collectives_sweep_deterministic_and_tagged():
    from benchmarks.sim_collectives import sweep

    kwargs = dict(world=8, sizes=[4096, MB], hosts=2, degree=2)
    rows_a = sweep(**kwargs)
    rows_b = sweep(**kwargs)
    assert rows_a == rows_b  # analytic: byte-identical reruns
    assert rows_a, "sweep produced no rows"
    for row in rows_a:
        assert row["mode"] == "simulated"
        assert "pred_time_us" in row and "time_us" not in row
        assert row["busbw_gbps"] > 0


def test_sim_collectives_cli_json(capsys):
    from benchmarks.sim_collectives import main

    assert main(["--world", "4", "--sizes", "4K", "--json",
                 "--collectives", "allreduce", "--strategies", "ring,binary"]
                ) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(rows) == 2
    assert {r["strategy"] for r in rows} == {"ring", "binary"}
    assert all(r["mode"] == "simulated" for r in rows)


def test_sim_collectives_rejects_unknown_axes():
    from benchmarks.sim_collectives import sweep

    with pytest.raises(ValueError, match="collective"):
        sweep(world=4, sizes=[4096], collectives=["gatherv"])
    with pytest.raises(ValueError, match="strategy"):
        sweep(world=4, sizes=[4096], strategies=["torus"])


# --------------------------------------------------------------------------- #
# synthesizer integration
# --------------------------------------------------------------------------- #

def test_synthesizer_sim_rank_policy_picks_predicted_winner():
    from adapcc_tpu.primitives import ALLREDUCE
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    ip = ["10.0.0.0"] * 4 + ["10.0.0.1"] * 4
    zeros = [[0.0] * 8 for _ in range(8)]
    syn = Synthesizer(None, ip, policy="sim-rank")
    winner = syn.synthesize(ALLREDUCE, 2, MB, zeros, zeros)
    assert winner.synthesis.endswith("+sim-rank")
    # the winner's prediction is the minimum over the candidate pool
    ranked = syn.rank(syn.candidates(2, zeros, zeros), MB)
    assert ranked[0].strategy.fingerprint() == winner.fingerprint()
    assert all(ranked[0].seconds <= r.seconds for r in ranked)


def test_synthesizer_rank_uses_profiled_matrices():
    """A profile that cripples one host's uplinks must steer the ranking."""
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    world = 4
    ip = [f"10.0.0.{r}" for r in range(world)]
    bw = [[0.0 if s == d else 40.0 for d in range(world)] for s in range(world)]
    lat = [[0.0 if s == d else 1e-6 for d in range(world)] for s in range(world)]
    syn = Synthesizer(None, ip)
    ranked = syn.rank(
        [("ring", Strategy.ring(world)), ("binary", Strategy.binary(world))],
        MB, bw, lat,
    )
    assert ranked[0].label == "binary"
    assert ranked[0].timeline.to_row()["mode"] == "simulated"


def test_sim_collectives_hosts_price_dcn_edges():
    """--hosts > 1 must actually slow cross-host edges (regression: the
    synthetic ip table once shaped candidates but never reached the model)."""
    from benchmarks.sim_collectives import sweep

    one = sweep(world=8, sizes=[MB], strategies=["ring"], hosts=1)
    four = sweep(world=8, sizes=[MB], strategies=["ring"], hosts=4)
    assert four[0]["pred_time_us"] > one[0]["pred_time_us"]


def test_load_or_default_resize_keeps_host_layout(tmp_path):
    """Resizing a calibration to a smaller world must keep the recorded ip
    table for the surviving ranks — cross-host edges stay classed DCN."""
    ips = {r: f"10.0.{r // 4}.1" for r in range(16)}  # 4 hosts x 4 ranks
    cal = calibrate_from_matrices(*probe_matrices(16), ips=ips)
    path = tmp_path / "c.json"
    cal.save(str(path))
    model = load_or_default(str(path), world=8)
    assert model.world == 8
    assert model.link_class_of(0, 1) == ICI
    assert model.link_class_of(0, 4) == DCN
    # in-range per-link fits survive the shrink; out-of-range links dropped
    assert (0, 1) in model.links and (0, 15) not in model.links
    full = calibrate_from_matrices(*probe_matrices(16), ips=ips).cost_model()
    assert model.coeffs(0, 1) == full.coeffs(0, 1)


def test_load_or_default_survives_malformed_artifact(tmp_path):
    """A structurally broken calibration file (hand-edited, partial tool)
    must fall back to defaults, not crash the simulated bench path."""
    bad = tmp_path / "calibration.json"
    bad.write_text(json.dumps({"version": 1, "classes": {"ici": {}}}))
    model = load_or_default(str(bad), world=4)
    assert model.source == "defaults"
    assert model.world == 4


def test_sweep_refuses_empty_grid():
    """Zero rows must raise, not exit clean: an explicitly requested
    strategy that failed to synthesize would otherwise read as a fine run
    with no data."""
    from benchmarks.sim_collectives import sweep

    with pytest.raises(ValueError, match="no rows"):
        sweep(world=4, sizes=[MB], collectives=["allreduce"], strategies=[])


def test_sim_collectives_hosts_conflicts_with_calibrated_layout():
    """A calibration that pins its own host layout can't be swept under a
    different synthetic --hosts split: shapes and pricing would diverge."""
    from benchmarks.sim_collectives import sweep

    model = LinkCostModel.uniform(
        8, ips={r: f"10.0.{r // 4}.{r}" for r in range(8)}, source="pinned"
    )
    with pytest.raises(ValueError, match="conflicts with the host layout"):
        sweep(world=8, sizes=[MB], strategies=["ring"], model=model, hosts=4)
    # without --hosts the calibrated layout itself drives the sweep
    rows = sweep(world=8, sizes=[MB], strategies=["ring"], model=model)
    assert rows and rows[0]["calibration"] == "pinned"


def test_synthesizer_fallback_model_prices_dcn():
    """With no profiled graphs (the bootstrap's first pass), sim-rank's
    fallback cost model must still class cross-host edges as DCN from the
    synthesizer's own ip table — not price the whole world as one slice."""
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    table = [f"10.0.{r // 4}.1" for r in range(8)]  # 2 hosts x 4 ranks
    syn = Synthesizer(None, table, policy="sim-rank")
    model = syn._cost_model(None, None)
    intra = model.coeffs(0, 1)
    cross = model.coeffs(0, 4)
    assert cross.alpha > intra.alpha
    assert cross.beta > intra.beta


def test_synthesizer_sim_rank_respects_prim():
    """Ranking must price the primitive being synthesized, not allreduce."""
    from adapcc_tpu.primitives import BROADCAST
    from adapcc_tpu.strategy.synthesizer import Synthesizer

    syn = Synthesizer(None, ["10.0.0.0"] * 8, policy="sim-rank")
    calls = []
    orig = syn.rank

    def spy(cands, nbytes, bw=None, lat=None, collective="allreduce"):
        calls.append(collective)
        return orig(cands, nbytes, bw, lat, collective=collective)

    syn.rank = spy
    zeros = [[0.0] * 8 for _ in range(8)]
    syn.synthesize(BROADCAST, 1, MB, zeros, zeros)
    assert calls == ["broadcast"]


# -- staged HBM-streaming ring pricing (docs/RING.md) -------------------------


def test_staged_ring_time_amortizes_alpha():
    """Predicted time falls as chunk_bytes grows (α amortized over fewer,
    larger tiles) and flattens — while the VMEM staging bound keeps growing.
    The sweep's knee is the tuning signal."""
    from adapcc_tpu.sim.cost_model import LinkCoeffs, staged_ring_allreduce_time

    coeffs = LinkCoeffs(alpha=1e-6, beta=1.0 / 45e9)
    nbytes = 128 << 20
    times = [
        staged_ring_allreduce_time(8, nbytes, coeffs, chunk)
        for chunk in (64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20)
    ]
    assert all(t > 0 for t in times)
    assert times == sorted(times, reverse=True)  # monotone improvement
    # diminishing returns: the last doubling buys far less than the first
    assert (times[0] - times[1]) > (times[-2] - times[-1])


def test_staged_ring_time_converges_to_wire_rate():
    """With α amortized, the staged prediction approaches wire + HBM cost:
    2(w−1)/w · β·n wire time is a hard lower bound."""
    from adapcc_tpu.sim.cost_model import LinkCoeffs, staged_ring_allreduce_time

    w, nbytes = 8, 128 << 20
    coeffs = LinkCoeffs(alpha=1e-6, beta=1.0 / 45e9)
    t = staged_ring_allreduce_time(w, nbytes, coeffs, 4 << 20)
    wire_floor = 2 * (w - 1) / w * coeffs.beta * nbytes
    assert t > wire_floor
    assert t < 3 * wire_floor  # HBM staging must not swamp the wire


def test_staged_ring_time_validates_inputs():
    from adapcc_tpu.sim.cost_model import LinkCoeffs, staged_ring_allreduce_time

    coeffs = LinkCoeffs(1e-6, 1e-10)
    assert staged_ring_allreduce_time(1, 1 << 20, coeffs, 4 << 20) == 0.0
    with pytest.raises(ValueError):
        staged_ring_allreduce_time(4, 1 << 20, coeffs, 0)


def test_ring_chunk_sweep_rows_are_deterministic():
    """make ring-sweep's artifact rows: simulated-mode stamped, planner-
    consistent (path/stage from the kernel's own planner), byte-identical
    across runs."""
    from benchmarks.sim_collectives import ring_chunk_sweep

    rows = ring_chunk_sweep(8, [16 << 20, 128 << 20], [1 << 20, 4 << 20])
    again = ring_chunk_sweep(8, [16 << 20, 128 << 20], [1 << 20, 4 << 20])
    assert rows == again
    assert len(rows) == 4
    for row in rows:
        assert row["mode"] == "simulated"
        assert row["impl"] == "pallas_ring"
        assert row["pred_time_us"] > 0
        assert row["ring_path"] in ("vmem", "hbm-stream")
        assert row["stage_bytes"] <= row["chunk_bytes"]
    # payloads above the staging budget stream
    assert all(
        r["ring_path"] == "hbm-stream"
        for r in rows
        if r["size_bytes"] > r["chunk_bytes"]
    )


def test_ring_chunk_sweep_refuses_empty_grid():
    from benchmarks.sim_collectives import ring_chunk_sweep

    with pytest.raises(ValueError):
        ring_chunk_sweep(8, [], [4 << 20])


def test_replay_pipelines_at_per_tree_chunks():
    """The solver's per-tree c_m is consumed by the replay: a finer per-tree
    chunk pipelines that tree's segment deeper, changing (improving) the
    predicted makespan vs the one-oversized-chunk default."""
    from adapcc_tpu.sim.cost_model import LinkCostModel
    from adapcc_tpu.sim.replay import lower_strategy, simulate_strategy
    from adapcc_tpu.strategy.ir import Strategy

    world, nbytes = 8, 32 << 20
    coarse = Strategy.ring(world)
    fine = Strategy.ring(world)
    fine.tree_chunk_bytes = [1 << 20]
    scheds = lower_strategy(fine, nbytes)
    assert scheds[0].chunk_bytes == 1 << 20          # c_m reached the schedule
    model = LinkCostModel.uniform(world)
    t_coarse = simulate_strategy(coarse, model, nbytes).seconds
    t_fine = simulate_strategy(fine, model, nbytes).seconds
    assert t_fine < t_coarse                         # deeper pipeline wins
