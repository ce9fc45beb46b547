"""adapcc_tpu/tuner: database, policy, harness, and end-to-end precedence.

The contracts under test mirror ISSUE 4's acceptance bar:

- database round-trip, corrupt/mixed-version skipping (loud, counted),
  deterministic concurrent-append merge;
- the policy converges to the analytically optimal (chunk_bytes,
  wire_dtype) cell on a deterministic synthetic timing surface within its
  exploration budget;
- hysteresis blocks single-sample plan flapping;
- env/arg precedence over the tuner holds end to end through
  ``engine.ring_allreduce`` dispatch traces.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.tuner import (
    CollectiveTuner,
    DispatchTimer,
    TuningDatabase,
    TuningKey,
    TuningPolicy,
    replay_trace,
    size_bucket,
    topology_fingerprint,
    tuner_mode,
)
from adapcc_tpu.tuner.db import SCHEMA_VERSION
from adapcc_tpu.utils.observability import CollectiveTrace


def _key(**kw) -> TuningKey:
    base = dict(
        primitive="allreduce", size_bucket=1 << 20, world=8,
        topology="test-fabric", path="hbm-stream", chunk_bytes=1 << 20,
        wire_dtype="off",
    )
    base.update(kw)
    return TuningKey(**base)


# --------------------------------------------------------------------------- #
# database
# --------------------------------------------------------------------------- #

def test_db_roundtrip_and_robust_stats(tmp_path):
    path = str(tmp_path / "tuning.jsonl")
    db = TuningDatabase(path)
    k = _key()
    for t in (10e-6, 30e-6, 20e-6, 1.0):  # one straggler outlier
        db.record(k, t)
    stats = db.stats(k)
    assert stats.count == 4
    # nearest-rank median of 4 sorted samples = the 2nd → 20us: the outlier
    # moved max, not the median (robustness is the point of median/IQR)
    assert stats.median_s == pytest.approx(20e-6)
    assert stats.max_s == 1.0

    db2 = TuningDatabase(path)  # fresh handle: full reload from disk
    assert db2.stats(k) == stats
    assert db2.keys() == [k]


def test_db_key_identity_separates_fabrics(tmp_path):
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    a = _key(topology="fabric-a")
    b = _key(topology="fabric-b")
    db.record(a, 1e-3)
    assert db.stats(b) is None  # a v5e median must not price a CPU run
    assert topology_fingerprint(8, platform="tpu:v5e") != topology_fingerprint(
        8, platform="cpu:cpu"
    )
    assert topology_fingerprint(8) == topology_fingerprint(8)  # stable


def test_db_skips_corrupt_and_mixed_version_records_loudly(tmp_path, capsys):
    path = str(tmp_path / "tuning.jsonl")
    db = TuningDatabase(path)
    k = _key()
    db.record(k, 5e-6)
    db.record(k, 7e-6)
    with open(path, "a") as f:
        f.write("this is not json\n")
        f.write(json.dumps({"v": SCHEMA_VERSION + 1, "key": k.to_dict(),
                            "t_s": 1e-6, "ts": 0.0}) + "\n")
        f.write(json.dumps({"v": SCHEMA_VERSION, "t_s": 1e-6}) + "\n")  # no key
    fresh = TuningDatabase(path)
    assert fresh.stats(k).count == 2  # the good records survived
    assert fresh.skipped_records == 3
    err = capsys.readouterr().err
    assert "WARNING" in err and "skipped 3" in err  # loud, never silent


def test_db_concurrent_append_merge_is_deterministic(tmp_path):
    """Two processes appending to the same JSONL in any interleaving must
    load to the same state — simulated here by writing the same records in
    two different orders."""
    k1, k2 = _key(chunk_bytes=1 << 20), _key(chunk_bytes=4 << 20)
    records = [(k1, 3e-6, 1.0), (k2, 9e-6, 2.0), (k1, 5e-6, 3.0),
               (k2, 7e-6, 4.0), (k1, 4e-6, 5.0)]

    def write(path, recs):
        db = TuningDatabase(str(path))
        for key, s, ts in recs:
            db.record(key, s, ts=ts)
        return str(path)

    p_fwd = write(tmp_path / "fwd.jsonl", records)
    p_rev = write(tmp_path / "rev.jsonl", list(reversed(records)))
    fwd, rev = TuningDatabase(p_fwd), TuningDatabase(p_rev)
    assert fwd.keys() == rev.keys()
    for key in fwd.keys():
        assert fwd.samples(key) == rev.samples(key)
        assert fwd.stats(key) == rev.stats(key)


def test_db_bounds_samples_newest_win(tmp_path):
    from adapcc_tpu.tuner.db import MAX_SAMPLES_PER_KEY

    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    k = _key()
    n = MAX_SAMPLES_PER_KEY + 50
    for i in range(n):
        db.record(k, float(i), ts=float(i))
    fresh = TuningDatabase(db.path)
    samples = fresh.samples(k)
    assert len(samples) == MAX_SAMPLES_PER_KEY
    # the retained window is the newest (a drifting fabric ages out)
    assert min(samples) == float(n - MAX_SAMPLES_PER_KEY)


def test_db_env_path_and_negative_duration(tmp_path, monkeypatch):
    from adapcc_tpu.tuner.db import TUNER_DB_ENV, resolve_db_path

    monkeypatch.setenv(TUNER_DB_ENV, str(tmp_path / "env.jsonl"))
    assert resolve_db_path() == str(tmp_path / "env.jsonl")
    assert resolve_db_path("/explicit/wins.jsonl") == "/explicit/wins.jsonl"
    db = TuningDatabase()
    assert db.path == str(tmp_path / "env.jsonl")
    with pytest.raises(ValueError, match="negative"):
        db.record(_key(), -1.0)


def test_size_bucket_pools_powers_of_two():
    assert size_bucket(1) == 1
    assert size_bucket((12 << 20) + 7) == 16 << 20
    assert size_bucket(16 << 20) == 16 << 20
    assert size_bucket((16 << 20) + 1) == 32 << 20


# --------------------------------------------------------------------------- #
# policy
# --------------------------------------------------------------------------- #

def _policy(db, **kw):
    kw.setdefault("world", 8)
    kw.setdefault("topology", "test-fabric")
    # these tests pin the pre-fused (chunk × quant-ring) grid semantics;
    # the fused-path cells have their own coverage in tests/test_fused_ring.py
    kw.setdefault("fused_paths", False)
    return TuningPolicy(db, **kw)


def test_candidates_cross_planner_and_codecs():
    db = TuningDatabase(persist=False)
    pol = _policy(db)
    cells = pol.candidates("allreduce", 16 << 20)
    offs = [c for c in cells if c.wire_dtype == "off"]
    quants = [c for c in cells if c.wire_dtype != "off"]
    # chunk cells carry the kernel planner's own path; codec cells are the
    # quantized ppermute ring (no chunk knob)
    assert all(c.path in ("vmem", "hbm-stream") for c in offs)
    assert {c.wire_dtype for c in quants} == {"bf16", "int8"}
    assert all(c.chunk_bytes == 0 and c.path == "quant-ring" for c in quants)
    # non-allreduce ring primitives keep only the chunk axis
    assert all(
        c.wire_dtype == "off" for c in pol.candidates("zero1_ring", 16 << 20)
    )


def test_policy_prior_ranks_without_measurements():
    db = TuningDatabase(persist=False)
    pol = _policy(db, epsilon=0.0)  # never explore: pure prior exploitation
    plan = pol.choose("allreduce", 16 << 20)
    assert plan.source == "prior"
    # the prior must agree with the sim cost model's own preference
    cells = pol.candidates("allreduce", 16 << 20)
    best = min(cells, key=lambda c: pol.prior_time(c, 16 << 20))
    assert plan.key == best


def test_policy_converges_to_optimal_cell_within_budget():
    """The acceptance-bar test: a deterministic synthetic timing surface
    whose optimum DISAGREES with the prior; the policy must find the true
    optimal (chunk_bytes, wire_dtype) within its exploration budget."""
    db = TuningDatabase(persist=False)
    budget = 3
    pol = _policy(db, epsilon=1.0, trial_budget=budget, seed=7)
    nbytes = 16 << 20
    cells = pol.candidates("allreduce", nbytes)
    # true optimum: the int8 quant ring — the prior prefers an "off" chunk
    # cell on healthy ICI, so convergence here PROVES measurement wins
    optimal = next(c for c in cells if c.wire_dtype == "int8")
    assert pol.prior_time(optimal, nbytes) > min(
        pol.prior_time(c, nbytes) for c in cells
    )

    def surface(cell):  # deterministic, no RNG
        return 10e-6 if cell == optimal else 100e-6 + 10e-6 * cells.index(cell)

    # drive: each choose() is answered with the surface's "measurement"
    for _ in range(budget * len(cells)):
        plan = pol.choose("allreduce", nbytes)
        db.record(plan.key, surface(plan.key))
    # budget filled: exploration is over, the posterior must pick optimal
    for _ in range(3):
        plan = pol.choose("allreduce", nbytes)
        assert plan.source == "measured"
        assert plan.key == optimal
        assert (plan.key.chunk_bytes, plan.key.wire_dtype) == (0, "int8")
    # and every cell respected the bounded per-key trial budget
    assert all(db.count(c) <= budget + 3 for c in cells)


def test_policy_exploration_stops_after_budget():
    db = TuningDatabase(persist=False)
    pol = _policy(db, epsilon=1.0, trial_budget=2)
    nbytes = 1 << 20
    cells = pol.candidates("allreduce", nbytes)
    for _ in range(2 * len(cells)):
        plan = pol.choose("allreduce", nbytes)
        assert plan.source == "explore"
        db.record(plan.key, 1e-3)
    assert pol.choose("allreduce", nbytes).source == "measured"


def test_hysteresis_blocks_single_sample_flapping():
    db = TuningDatabase(persist=False)
    pol = _policy(
        db, epsilon=0.0, min_samples=1,
        hysteresis_margin=0.10, hysteresis_min_samples=3,
    )
    nbytes = 16 << 20
    cells = pol.candidates("allreduce", nbytes)
    incumbent, challenger = cells[0], cells[1]
    for _ in range(5):
        db.record(incumbent, 100e-6)
    assert pol.choose("allreduce", nbytes).key == incumbent
    # one lucky sample, even a dramatic one, must not flip the plan
    db.record(challenger, 10e-6)
    plan = pol.choose("allreduce", nbytes)
    assert plan.key == incumbent, "single-sample flap got through hysteresis"
    # a second sample (still < hysteresis_min_samples=3): still blocked
    db.record(challenger, 10e-6)
    assert pol.choose("allreduce", nbytes).key == incumbent
    # sustained evidence over >= k samples beating the margin: promoted
    db.record(challenger, 10e-6)
    assert pol.choose("allreduce", nbytes).key == challenger


def test_hysteresis_margin_blocks_marginal_challengers():
    db = TuningDatabase(persist=False)
    pol = _policy(
        db, epsilon=0.0, min_samples=1,
        hysteresis_margin=0.10, hysteresis_min_samples=2,
    )
    nbytes = 16 << 20
    cells = pol.candidates("allreduce", nbytes)
    for _ in range(4):
        db.record(cells[0], 100e-6)
    assert pol.choose("allreduce", nbytes).key == cells[0]
    for _ in range(4):
        db.record(cells[1], 95e-6)  # better, but within the 10% margin
    assert pol.choose("allreduce", nbytes).key == cells[0]


def test_policy_determinism_same_seed_same_trajectory():
    def run():
        db = TuningDatabase(persist=False)
        pol = _policy(db, epsilon=0.5, trial_budget=2, seed=123)
        out = []
        for i in range(12):
            plan = pol.choose("allreduce", 4 << 20)
            db.record(plan.key, 1e-3 + 1e-5 * i)
            out.append((plan.key, plan.source))
        return out

    assert run() == run()


def test_policy_validates_parameters():
    db = TuningDatabase(persist=False)
    with pytest.raises(ValueError, match="epsilon"):
        _policy(db, epsilon=1.5)
    with pytest.raises(ValueError, match="trial_budget"):
        _policy(db, trial_budget=0)
    with pytest.raises(ValueError, match="chunk grid"):
        _policy(db, chunk_grid=(0,))


# --------------------------------------------------------------------------- #
# measure: warmup discard + trace replay
# --------------------------------------------------------------------------- #

def test_dispatch_timer_discards_compile_warmup():
    db = TuningDatabase(persist=False)
    timer = DispatchTimer(db)
    k = _key()
    assert timer.observe(k, ("prog", 1), 5.0) is False  # compile walltime
    assert timer.observe(k, ("prog", 1), 1e-3) is True
    assert timer.observe(k, ("prog", 2), 4.0) is False  # new program: again
    assert db.stats(k).count == 1
    assert db.stats(k).median_s == pytest.approx(1e-3)


def test_replay_trace_ingests_timed_ring_events():
    trace = CollectiveTrace()
    trace.record(
        "allreduce", "pallas_ring[hbm-stream]", 8 * (4 << 20),
        chunk_bytes=1 << 20, stage_bytes=1 << 20, duration_s=200e-6,
    )
    trace.record(
        "allreduce", "quant_ring[int8]", 8 * (4 << 20),
        wire_dtype="int8", duration_s=150e-6,
    )
    trace.record("allreduce", "xla", 4096)  # untunable: skipped, counted
    trace.record("allreduce", "pallas_ring[vmem]", 4096)  # untimed: skipped
    db = TuningDatabase(persist=False)
    ingested, skipped = replay_trace(trace, db, world=8, topology="tf")
    assert (ingested, skipped) == (2, 2)
    keys = db.keys()
    assert {k.path for k in keys} == {"hbm-stream", "quant-ring"}
    ring_key = next(k for k in keys if k.path == "hbm-stream")
    assert ring_key.size_bucket == 4 << 20  # per-rank bytes, not stacked
    assert ring_key.chunk_bytes == 1 << 20


def test_replay_trace_roundtrips_through_track_file(tmp_path):
    from adapcc_tpu.utils.observability import parse_track_log

    trace = CollectiveTrace()
    trace.record(
        "allreduce", "quant_ring[bf16]", 8 * (1 << 20),
        wire_dtype="bf16", duration_s=99e-6,
    )
    path = str(tmp_path / "track.txt")
    trace.dump(path)
    db = TuningDatabase(persist=False)
    ingested, _ = replay_trace(parse_track_log(path), db, 8, "tf")
    assert ingested == 1
    (k,) = db.keys()
    assert k.wire_dtype == "bf16"
    assert db.stats(k).median_s == pytest.approx(99e-6)


# --------------------------------------------------------------------------- #
# mode resolution
# --------------------------------------------------------------------------- #

def test_tuner_mode_env_and_malformed(monkeypatch):
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    assert tuner_mode() == "off"
    assert tuner_mode("choose") == "choose"  # explicit default, env unset
    monkeypatch.setenv(TUNER_MODE_ENV, "record")
    assert tuner_mode() == "record"
    assert tuner_mode("choose") == "record"  # env wins over explicit
    monkeypatch.setenv(TUNER_MODE_ENV, "chose")
    with pytest.raises(ValueError, match="ADAPCC_TUNER"):
        tuner_mode()


def test_engine_rejects_malformed_tuner_env(mesh8, monkeypatch):
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.setenv(TUNER_MODE_ENV, "on")
    with pytest.raises(ValueError, match="ADAPCC_TUNER"):
        CollectiveEngine(mesh8, Strategy.ring(8))


# --------------------------------------------------------------------------- #
# end to end: engine.ring_allreduce precedence + dispatch trace
# --------------------------------------------------------------------------- #

def _choose_engine(mesh8, tmp_path, monkeypatch, **tuner_kw):
    """Engine with a choosing tuner whose database says int8 is fastest —
    the quant ring runs on any backend, so the end-to-end path needs no
    Pallas support.  ADAPCC_FUSED_WIRE=off pins the unfused reroute so
    the quant_ring[...] impl assertions hold on fused-capable builds
    (jax >= 0.5 interpret / real TPU) too."""
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.pallas_ring import FUSED_WIRE_ENV
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.setenv(TUNER_MODE_ENV, "choose")
    monkeypatch.setenv(FUSED_WIRE_ENV, "off")
    db = TuningDatabase(str(tmp_path / "tuning.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="e2e", db=db, epsilon=0.0, min_samples=1,
        **tuner_kw,
    )
    trace = CollectiveTrace()
    engine = CollectiveEngine(mesh8, Strategy.ring(8), trace=trace, tuner=tuner)
    return engine, trace, db, tuner


def _seed_int8_fastest(db, tuner, nbytes):
    cells = tuner.policy.candidates("allreduce", nbytes)
    for c in cells:
        t = 10e-6 if c.wire_dtype == "int8" else 500e-6
        for _ in range(4):
            db.record(c, t)


def test_engine_adopts_measured_choice_and_traces_it(mesh8, tmp_path, monkeypatch):
    engine, trace, db, tuner = _choose_engine(mesh8, tmp_path, monkeypatch)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 2048)), jnp.float32
    )
    per_rank = 2048 * 4
    _seed_int8_fastest(db, tuner, per_rank)
    out = engine.ring_allreduce(x)  # nothing pinned: the tuner steers
    from adapcc_tpu.quant import ring_error_bound

    err = np.abs(np.asarray(out)[0] - np.asarray(x).sum(0))
    assert (err <= ring_error_bound(np.asarray(x)) + 1e-6).all()
    ev = trace.events()[-1]
    assert ev.impl == "quant_ring[int8]"
    assert ev.extra["tuner"]["source"] == "measured"
    assert ev.extra["tuner"]["applied"] is True
    assert ev.extra["tuner"]["chosen"]["wire_dtype"] == "int8"
    # record mode is live inside choose: the dispatch walltime was measured
    assert ev.extra["duration_s"] > 0


def test_engine_arg_overrides_tuner_visible_in_trace(mesh8, tmp_path, monkeypatch):
    engine, trace, db, tuner = _choose_engine(mesh8, tmp_path, monkeypatch)
    x = jnp.ones((8, 2048), jnp.float32)
    _seed_int8_fastest(db, tuner, 2048 * 4)
    engine.ring_allreduce(x, wire_dtype="bf16")  # explicit arg pins codec
    ev = trace.events()[-1]
    assert ev.impl == "quant_ring[bf16]"  # the arg ran, not the tuner
    assert ev.extra["wire_dtype"] == "bf16"
    assert ev.extra["tuner"]["chosen"]["wire_dtype"] == "int8"
    assert ev.extra["tuner"]["applied"] is False  # precedence in the trace


def test_engine_env_overrides_tuner_visible_in_trace(mesh8, tmp_path, monkeypatch):
    """An ADAPCC_WIRE_DTYPE pin collapses the tuner's codec axis to the
    pinned cell (every dispatch executes the pin, so any other codec's
    cell could never accrue samples — the chunk-pin collapse, codec
    flavor), and the executed dispatch runs the pinned codec."""
    from adapcc_tpu.quant import WIRE_DTYPE_ENV

    engine, trace, db, tuner = _choose_engine(mesh8, tmp_path, monkeypatch)
    x = jnp.ones((8, 2048), jnp.float32)
    _seed_int8_fastest(db, tuner, 2048 * 4)
    monkeypatch.setenv(WIRE_DTYPE_ENV, "bf16")
    engine.ring_allreduce(x)
    ev = trace.events()[-1]
    assert ev.impl == "quant_ring[bf16]"  # ADAPCC_WIRE_DTYPE beat the tuner
    # the grid collapsed: the policy's chosen cell carries the pin, so the
    # recorded walltime lands in the cell that actually ran
    assert ev.extra["tuner"]["chosen"]["wire_dtype"] == "bf16"
    cells = tuner.policy.candidates("allreduce", 2048 * 4)
    assert {c.wire_dtype for c in cells} == {"bf16"}


def test_engine_chunk_env_overrides_tuner_in_plan(mesh8, monkeypatch, tmp_path):
    """ADAPCC_RING_CHUNK_BYTES must beat a tuner-chosen chunk in the
    executed plan (planning only — no kernel run needed)."""
    from adapcc_tpu.comm.pallas_ring import RING_CHUNK_ENV
    from adapcc_tpu.quant import WIRE_DTYPE_ENV

    engine, trace, db, tuner = _choose_engine(mesh8, tmp_path, monkeypatch)
    nbytes = 2048 * 4
    # seed an "off" chunk cell as fastest so the tuner picks a chunk size
    cells = tuner.policy.candidates("allreduce", nbytes)
    off = [c for c in cells if c.wire_dtype == "off"]
    for c in cells:
        t = 10e-6 if c == off[0] else 500e-6
        for _ in range(4):
            db.record(c, t)
    plan_choice = tuner.choose("allreduce", nbytes)
    assert plan_choice.wire_dtype == "off"
    monkeypatch.setenv(RING_CHUNK_ENV, str(8 << 20))
    x = jnp.ones((8, 2048), jnp.float32)
    plan = engine._ring_plan(x, plan_choice.chunk_bytes, rs=True, ag=True)
    assert plan.chunk_bytes == 8 << 20  # env beat the tuner's choice
    monkeypatch.delenv(RING_CHUNK_ENV)
    plan = engine._ring_plan(x, plan_choice.chunk_bytes, rs=True, ag=True)
    assert plan.chunk_bytes == plan_choice.chunk_bytes


def test_engine_off_mode_is_inert(mesh8, tmp_path, monkeypatch):
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(world=8, topology="e2e", db=db)
    trace = CollectiveTrace()
    engine = CollectiveEngine(
        mesh8, Strategy.ring(8), trace=trace, tuner=tuner
    )
    engine.ring_allreduce(jnp.ones((8, 512), jnp.float32), wire_dtype="bf16")
    ev = trace.events()[-1]
    assert "tuner" not in ev.extra      # nothing consulted
    assert "duration_s" not in ev.extra  # nothing timed
    assert len(db) == 0                  # nothing recorded


def test_engine_record_mode_fills_db_with_warmup_discard(mesh8, tmp_path, monkeypatch):
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.setenv(TUNER_MODE_ENV, "record")
    from adapcc_tpu.comm.pallas_ring import FUSED_WIRE_ENV

    monkeypatch.setenv(FUSED_WIRE_ENV, "off")  # pin the quant-ring cell
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(world=8, topology="e2e", db=db)
    engine = CollectiveEngine(mesh8, Strategy.ring(8), tuner=tuner)
    x = jnp.ones((8, 2048), jnp.float32)
    for _ in range(4):
        engine.ring_allreduce(x, wire_dtype="int8")
    (key,) = db.keys()
    assert key == tuner.key_for("allreduce", 2048 * 4, "quant-ring", 0, "int8")
    assert db.stats(key).count == 3  # first dispatch = compile, discarded
    # record mode measures but never steers: no tuner consults happened
    assert tuner.policy.incumbent("allreduce", 2048 * 4) is None


def test_communicator_owns_tuner_and_engine_shares_it(tmp_path, monkeypatch):
    from adapcc_tpu.communicator import Communicator
    from adapcc_tpu.config import CommArgs
    from adapcc_tpu.primitives import ALLREDUCE

    monkeypatch.chdir(tmp_path)  # keep artifacts out of the repo
    args = CommArgs(
        strategy_file=str(tmp_path / "strategy.xml"),
        logical_graph=str(tmp_path / "logical_graph.xml"),
        topology_dir=str(tmp_path / "topology"),
    )
    comm = Communicator(args, world_size=8)
    assert comm.tuner.world == 8
    assert comm.tuner.db.path == str(tmp_path / "topology" / "tuning.jsonl")
    comm.init_threads(ALLREDUCE)
    engine = comm._engines[ALLREDUCE]
    assert engine.tuner is comm.tuner  # one database view per world
    comm.clear()


# --------------------------------------------------------------------------- #
# tune-bench artifact (benchmarks.sim_collectives --tune-replay)
# --------------------------------------------------------------------------- #

def test_tune_replay_rows_deterministic_and_flagged():
    from benchmarks.sim_collectives import tune_replay_sweep

    rows = tune_replay_sweep(8, [1 << 20, 16 << 20])
    again = tune_replay_sweep(8, [1 << 20, 16 << 20])
    assert rows == again  # byte-identical: the tier-1 determinism contract
    assert all(r["mode"] == "simulated" for r in rows)
    for size in (1 << 20, 16 << 20):
        per_size = [r for r in rows if r["size_bytes"] == size]
        assert sum(r["chosen"] for r in per_size) == 1  # one committed plan
        assert sum(r["surface_best"] for r in per_size) == 1
        (chosen,) = [r for r in per_size if r["chosen"]]
        # the replay's budget suffices: the policy found the true optimum
        assert chosen["surface_best"] and chosen["converged"]
        assert chosen["choice_source"] == "measured"
        # every cell was actually explored (the budget filled the grid)
        assert all(r["samples"] >= 4 for r in per_size)


def test_tune_replay_cli_exclusive_with_other_sweeps():
    from benchmarks.sim_collectives import main

    with pytest.raises(SystemExit):
        main(["--tune-replay", "--ring-sweep"])
    with pytest.raises(SystemExit):
        main(["--tune-replay", "--wire-dtype", "off,int8"])


def test_tune_replay_cli_json(capsys):
    from benchmarks.sim_collectives import main

    assert main(["--world", "8", "--sizes", "1M", "--tune-replay",
                 "--json"]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    rows = [json.loads(l) for l in lines]
    assert rows and all(r["impl"] == "tuner" for r in rows)
    assert sum(r["chosen"] for r in rows) == 1


# --------------------------------------------------------------------------- #
# trainer / zero1 integration
# --------------------------------------------------------------------------- #

def _mlp_loss():
    import optax

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.ones((16, 4), jnp.float32)}
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 16)), jnp.float32
    )
    y = jnp.zeros((8, 4), jnp.float32)
    return loss_fn, params, (x, y), optax.sgd(0.01)


def test_trainer_tune_records_step_walltimes(mesh8, tmp_path, monkeypatch):
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(world=8, topology="train", db=db, mode="choose")
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=tuner,
        tune_every=1000,  # no adoption inside this short run
    )
    state = TrainState.create(params, tx)
    for _ in range(4):
        state, _ = trainer.step(state, batch)
    keys = db.keys()
    assert len(keys) == 1
    (key,) = keys
    assert key.primitive == "ddp_step"
    assert key.path == "hook"
    assert key.wire_dtype == "off"
    # 4 steps, first discarded as the compiled program's warmup
    assert db.stats(key).count == 3


def test_trainer_tune_adopts_measured_codec(mesh8, tmp_path, monkeypatch):
    """Seed the database so bf16 steps measure fastest: the trainer must
    adopt it (recompile) at its next tune_every boundary, and hysteresis
    state must come from the policy, not ad-hoc flapping."""
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV
    from adapcc_tpu.tuner.policy import HOOK_PATH

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="train", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=tuner,
        tune_every=2,
    )
    state = TrainState.create(params, tx)
    import jax

    grad_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    )
    for wd in ("off", "bf16", "int8"):
        t = 1e-6 if wd == "bf16" else 1.0
        for _ in range(5):
            db.record(
                tuner.key_for("ddp_step", grad_bytes, HOOK_PATH, 0, wd), t
            )
    assert trainer.hook.effective_compress() == "off"
    for _ in range(4):
        state, _ = trainer.step(state, batch)
    assert trainer.hook.effective_compress() == "bf16"  # adopted + recompiled


def test_zero1_optimizer_adopts_tuned_chunk(mesh8, tmp_path, monkeypatch):
    from adapcc_tpu.parallel.fsdp import Zero1Optimizer
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    import optax

    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="z1", db=db, mode="choose", epsilon=0.0,
    )
    opt = Zero1Optimizer(
        optax.sgd(0.1), mesh8, ring=True, ring_interpret=True, tuner=tuner,
    )
    params = {"w": jnp.ones((1 << 14,), jnp.float32)}
    opt.init(params)
    assert opt.tuned_plan is not None
    assert opt.ring_chunk_bytes == opt.tuned_plan.chunk_bytes
    assert opt.tuned_plan.source in ("prior", "explore")

    # an explicit chunk wins over the tuner (arg > tuner precedence)
    pinned = Zero1Optimizer(
        optax.sgd(0.1), mesh8, ring=True, ring_interpret=True,
        ring_chunk_bytes=2 << 20, tuner=tuner,
    )
    pinned.init(params)
    assert pinned.tuned_plan is None
    assert pinned.ring_chunk_bytes == 2 << 20


def test_train_ddp_tune_flag_rejects_fsdp():
    from adapcc_tpu.workloads.train_ddp import main

    with pytest.raises(ValueError, match="--tune"):
        main(["--dp-mode", "fsdp", "--tune", "--steps", "1"])


def test_trainer_step_cell_stays_in_candidate_set_under_zero1_ring(
    mesh8, tmp_path, monkeypatch
):
    """The step cell the trainer records into must be one the policy's
    ddp_step candidate grid can rank — otherwise the posterior never forms
    and exploration never terminates (review finding: the zero1 ring chunk
    must NOT leak into the ddp_step key; it is tuned separately)."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(world=8, topology="train", db=db, mode="choose")
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=tuner,
        zero1=True, zero1_ring=True, zero1_ring_chunk_bytes=1 << 20,
    )
    cell = trainer._step_cell(4096)
    assert cell in tuner.policy.candidates("ddp_step", 4096)


def test_zero1_tuning_key_closes_the_loop_across_runs(mesh8, tmp_path, monkeypatch):
    """Step walltimes recorded under Zero1Optimizer.tuning_key() must land
    where the NEXT init()'s choose("zero1_ring", ...) looks, so the chunk
    choice converges across runs through the persisted database."""
    import optax

    from adapcc_tpu.parallel.fsdp import Zero1Optimizer
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    # large enough that the chunk grid yields DISTINCT cells (a tiny
    # payload is vmem-resident at every budget and dedupes to one cell)
    params = {"w": jnp.ones((1 << 22,), jnp.float32)}
    db = TuningDatabase(str(tmp_path / "t.jsonl"))

    def fresh_opt():
        tuner = CollectiveTuner(
            world=8, topology="z1", db=db, mode="choose",
            epsilon=1.0, trial_budget=2, min_samples=1, seed=0,
        )
        opt = Zero1Optimizer(
            optax.sgd(0.1), mesh8, ring=True, ring_interpret=True,
            tuner=tuner,
        )
        opt.init(params)
        return opt, tuner

    # "runs": each init() chooses a cell, the run's steps record into
    # tuning_key() — candidates() must be able to see every recorded cell
    for _ in range(16):
        opt, tuner = fresh_opt()
        key = opt.tuning_key()
        assert key is not None
        assert key in tuner.policy.candidates(
            "zero1_ring", opt._meta.padded * 4
        ), "recorded zero1 cell must be rankable by the next run's policy"
        db.record(key, 1e-6 if key.chunk_bytes == 4 << 20 else 1e-3)
    # the database converged the choice: a fresh run now exploits it
    opt, tuner = fresh_opt()
    assert opt.tuned_plan.source == "measured"
    assert opt.tuned_plan.key.chunk_bytes == 4 << 20

    # a pinned chunk still yields a recordable executed-configuration cell
    pinned = Zero1Optimizer(
        optax.sgd(0.1), mesh8, ring=True, ring_interpret=True,
        ring_chunk_bytes=2 << 20, tuner=tuner,
    )
    pinned.init(params)
    pkey = pinned.tuning_key()
    assert pkey is not None and pkey.chunk_bytes == 2 << 20


def test_vmem_recording_lands_in_candidate_cell(mesh8, tmp_path, monkeypatch):
    """Record-then-choose must close over the vmem boundary: a record-mode
    run keyed by the executed budget (e.g. the strategy default 4 MB) and
    the candidate grid must spell the SAME vmem cell — it is one physical
    configuration regardless of budget (review finding: keying vmem by
    budget orphaned every recorded sample from the grid)."""
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV
    from adapcc_tpu.tuner.policy import NO_CHUNK

    monkeypatch.setenv(TUNER_MODE_ENV, "record")
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(world=8, topology="e2e", db=db)
    engine = CollectiveEngine(mesh8, Strategy.ring(8), tuner=tuner)
    x = jnp.ones((8, 2048), jnp.float32)  # 8 KB payload: vmem at any budget
    # wire the recording through the quant-off path is impossible off-TPU
    # (Pallas), so drive the key production directly at the funnel the
    # engine uses: the executed plan + key_for canonicalization
    plan = engine._ring_plan(x, None, rs=True, ag=True)
    assert plan.path == "vmem"
    key = tuner.key_for(
        "allreduce", 2048 * 4, plan.path,
        NO_CHUNK if plan.path == "vmem" else plan.chunk_bytes, "off",
    )
    db.record(key, 123e-6)
    monkeypatch.setenv(TUNER_MODE_ENV, "choose")
    cells = tuner.policy.candidates("allreduce", 2048 * 4)
    assert key in cells, "recorded vmem cell must be rankable by choose"
    # and the committed plan carries an execution budget that realizes vmem
    pol = TuningPolicy(db, 8, "e2e", epsilon=0.0, min_samples=1)
    plan2 = pol.choose("allreduce", 2048 * 4)
    assert plan2.key == key and plan2.source == "measured"
    assert plan2.chunk_bytes is not None
    from adapcc_tpu.comm.pallas_ring import plan_ring_schedule

    assert plan_ring_schedule(2048, "float32", 8, plan2.chunk_bytes).path == "vmem"


def test_trainer_tune_view_chooses_without_env(mesh8, tmp_path, monkeypatch):
    """tune=True must actually tune BOTH knobs with ADAPCC_TUNER unset:
    the trainer wraps an env-default tuner in a choose-mode view so the
    Zero1Optimizer chunk gate (tuner.choosing) passes too."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    env_default = CollectiveTuner(world=8, topology="t", db=db)  # mode: env
    assert not env_default.choosing
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=env_default,
    )
    assert trainer.tuner.choosing           # the view chooses
    assert trainer.tuner.db is db           # same database
    assert trainer.tuner.policy is env_default.policy  # same hysteresis
    # env still overrides the view globally
    monkeypatch.setenv(TUNER_MODE_ENV, "off")
    assert not trainer.tuner.choosing
    # a caller-pinned mode is respected, not upgraded
    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    pinned = CollectiveTuner(world=8, topology="t", db=db, mode="record")
    t2 = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=pinned,
    )
    assert t2.tuner is pinned and not t2.tuner.choosing


def test_db_lazy_load_defers_parse_until_first_query(tmp_path):
    path = str(tmp_path / "t.jsonl")
    TuningDatabase(path).record(_key(), 1e-3)
    db = TuningDatabase(path)
    assert db._loaded is False      # construction did not parse the file
    assert db.count(_key()) == 1    # first query loads
    assert db._loaded is True


def test_chrome_trace_slice_starts_before_completion(tmp_path):
    """A timed event is recorded AFTER block_until_ready, so its record
    timestamp is the slice END; the exported slice must start earlier by
    its duration or timelines misrepresent ordering."""
    trace = CollectiveTrace()
    trace.record("allreduce", "quant_ring[int8]", 4096, duration_s=0.5)
    (ev,) = trace.events()
    path = str(tmp_path / "trace.json")
    trace.dump_chrome_trace(path)
    (slice_,) = [
        e for e in json.load(open(path))["traceEvents"]
        if e.get("cat") == "collective"
    ]
    assert slice_["dur"] == pytest.approx(0.5e6)
    assert slice_["ts"] == pytest.approx(ev.ts * 1e6 - 0.5e6)


def test_trainer_error_feedback_excludes_off_from_tuning_grid(
    mesh8, tmp_path, monkeypatch
):
    """With error feedback the 'off' codec is illegal (zero residual at
    world x params), so it must be excluded from the ddp_step candidate
    GRID — not just from adoption — or the explorer pins forever on a cell
    that can never accrue samples and the tuner goes inert."""
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV
    from adapcc_tpu.tuner.policy import HOOK_PATH

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="t", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=tuner,
        tune_every=2, grad_compress="int8", error_feedback=True,
    )
    state = TrainState.create(params, tx)
    import jax as _jax

    grad_bytes = sum(
        l.nbytes for l in _jax.tree_util.tree_leaves(params)
    )
    # bf16 measures fastest; 'off' would win if it were in the grid
    for wd, t in (("off", 1e-9), ("bf16", 1e-6), ("int8", 1.0)):
        for _ in range(5):
            db.record(
                tuner.key_for("ddp_step", grad_bytes, HOOK_PATH, 0, wd), t
            )
    for _ in range(4):
        state, _ = trainer.step(state, batch)
    # adopted the best LEGAL codec, not the illegal 'off'
    assert trainer.hook.effective_compress() == "bf16"


def test_trainer_env_pinned_codec_never_recompiles(mesh8, tmp_path, monkeypatch):
    """ADAPCC_WIRE_DTYPE pins the executed codec; a tuner 'adoption' under
    it would recompile the step for zero behavioral change, every
    tune_every boundary, forever — adoption must stand down."""
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.quant import WIRE_DTYPE_ENV
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.tuner import TUNER_MODE_ENV
    from adapcc_tpu.tuner.policy import HOOK_PATH

    monkeypatch.delenv(TUNER_MODE_ENV, raising=False)
    monkeypatch.setenv(WIRE_DTYPE_ENV, "int8")
    loss_fn, params, batch, tx = _mlp_loss()
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    tuner = CollectiveTuner(
        world=8, topology="t", db=db, mode="choose",
        epsilon=0.0, min_samples=1,
    )
    trainer = DDPTrainer(
        loss_fn, tx, mesh8, Strategy.ring(8), tune=True, tuner=tuner,
        tune_every=1,
    )
    state = TrainState.create(params, tx)
    import jax as _jax

    grad_bytes = sum(
        l.nbytes for l in _jax.tree_util.tree_leaves(params)
    )
    # make the policy prefer a codec that differs from the env pin
    for _ in range(5):
        db.record(
            tuner.key_for("ddp_step", grad_bytes, HOOK_PATH, 0, "bf16"), 1e-6
        )
    state, _ = trainer.step(state, batch)
    compiled = trainer._compiled
    assert compiled is not None
    for _ in range(3):  # every step crosses a tune boundary (tune_every=1)
        state, _ = trainer.step(state, batch)
    assert trainer._compiled is compiled  # no no-op recompiles
    # and the recorded samples landed in the env-pinned cell
    pinned = tuner.key_for("ddp_step", grad_bytes, HOOK_PATH, 0, "int8")
    assert db.stats(pinned) is not None


def test_db_record_after_save_compaction(tmp_path):
    db = TuningDatabase(str(tmp_path / "t.jsonl"))
    k = _key()
    db.record(k, 1e-3)
    db.save()  # compaction replaces the file the append handle points at
    db.record(k, 2e-3)
    fresh = TuningDatabase(db.path)
    assert fresh.stats(k).count == 2


def test_env_chunk_pin_keeps_grid_and_recording_in_one_cell(monkeypatch):
    """Under ADAPCC_RING_CHUNK_BYTES every candidate budget resolves to the
    pinned plan: the grid must collapse to ONE cell keyed exactly as the
    engine keys live recordings (the planner-resolved budget), or the off
    path can never form a posterior and the codec A/B is judged on bogus
    evidence."""
    from adapcc_tpu.comm.pallas_ring import RING_CHUNK_ENV, plan_ring_schedule

    pin = 2 << 20  # deliberately NOT in DEFAULT_CHUNK_GRID
    monkeypatch.setenv(RING_CHUNK_ENV, str(pin))
    db = TuningDatabase(persist=False)
    pol = _policy(db)
    nbytes = 16 << 20
    offs = [c for c in pol.candidates("allreduce", nbytes) if c.wire_dtype == "off"]
    assert len(offs) == 1
    (cell,) = offs
    plan = plan_ring_schedule(nbytes // 4, "float32", 8, None)  # env resolves
    executed_chunk = 0 if plan.path == "vmem" else plan.chunk_bytes
    assert (cell.path, cell.chunk_bytes) == (plan.path, executed_chunk)


def test_measured_nongrid_cell_competes_in_exploitation():
    """A record-only run under a solver-assigned chunk outside the grid
    produced honest medians for a plan the data plane actually ran; a
    later choose() must let that cell compete instead of re-exploring."""
    db = TuningDatabase(persist=False)
    pol = _policy(db, epsilon=0.0, min_samples=1, trial_budget=1)
    nbytes = 16 << 20
    pinned = _key(
        topology="test-fabric", size_bucket=size_bucket(nbytes),
        path="hbm-stream", chunk_bytes=3 << 20,  # not a grid value
    )
    for _ in range(4):
        db.record(pinned, 1e-6)  # measured fastest by far
    # fill the grid cells so exploitation (not budget-filling) decides
    for c in pol.candidates("allreduce", nbytes):
        if c != pinned:
            for _ in range(4):
                db.record(c, 1e-3)
    plan = pol.choose("allreduce", nbytes)
    assert plan.key == pinned and plan.source == "measured"
    assert plan.chunk_bytes == 3 << 20  # executable as-is


def test_with_mode_shares_policy_without_rebuilding():
    db = TuningDatabase(persist=False)
    base = CollectiveTuner(
        world=8, topology="t", db=db, chunk_grid=(1 << 20,), epsilon=0.5,
    )
    view = base.with_mode("choose")
    assert view.policy is base.policy      # hysteresis/grid/epsilon shared
    assert view.timer is base.timer        # warmup state shared
    assert view.db is db
    assert view.explicit_mode == "choose" and base.explicit_mode is None


def test_old_records_load_unchanged_next_to_new_primitives(tmp_path):
    """Satellite of the latency PR: adding the `all_to_all` primitive and
    the algo-in-path-slot keys (`rd`/`tree`) is a VOCABULARY extension,
    not a schema change — a pre-existing tuning.jsonl written before the
    extension must load byte-for-byte unchanged next to the new keys, and
    a mixed-version save/load round-trips losslessly."""
    from adapcc_tpu.tuner.policy import NO_CHUNK, RD_PATH, TREE_PATH

    path = str(tmp_path / "tuning.jsonl")
    # an "old" database: pre-PR vocabulary only, written raw (exactly the
    # lines an older build appended)
    old_keys = [
        _key(),                                    # hbm-stream chunk cell
        _key(path="vmem", chunk_bytes=0),
        _key(path="quant-ring", chunk_bytes=0, wire_dtype="int8"),
        _key(primitive="ddp_step", path="hook", chunk_bytes=0),
    ]
    with open(path, "w") as f:
        for i, k in enumerate(old_keys):
            f.write(json.dumps(
                {"v": SCHEMA_VERSION, "key": k.to_dict(),
                 "t_s": 1e-6 * (i + 1), "ts": float(i)},
                sort_keys=True,
            ) + "\n")
    db = TuningDatabase(path)
    assert db.skipped_records == 0
    for i, k in enumerate(old_keys):  # loaded unchanged, stats intact
        assert db.samples(k) == [1e-6 * (i + 1)]
    # new-vocabulary records append into the SAME file, same schema version
    new_keys = [
        _key(path=RD_PATH, chunk_bytes=NO_CHUNK),
        _key(path=TREE_PATH, chunk_bytes=NO_CHUNK),
        _key(primitive="all_to_all", path="xla", chunk_bytes=NO_CHUNK),
    ]
    for k in new_keys:
        db.record(k, 2e-6, ts=10.0)
    reloaded = TuningDatabase(path)
    assert reloaded.skipped_records == 0
    assert set(reloaded.keys()) == set(old_keys) | set(new_keys)
    for i, k in enumerate(old_keys):  # old records still byte-identical
        assert reloaded.samples(k) == [1e-6 * (i + 1)]
    # compaction round-trip keeps the mixed vocabulary lossless
    reloaded.save()
    again = TuningDatabase(path)
    assert set(again.keys()) == set(old_keys) | set(new_keys)
    assert again.samples(new_keys[0]) == [2e-6]
