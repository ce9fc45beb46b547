"""Benchmark harness tests on the virtual CPU pod (tiny sizes)."""

import json

import pytest

from benchmarks.collectives import (
    BUS_FACTORS,
    format_table,
    parse_size,
    run_sweep,
)


def test_parse_size():
    assert parse_size("4K") == 4096
    assert parse_size("1M") == 1024**2
    assert parse_size("2g") == 2 * 1024**3
    assert parse_size("512") == 512


def test_bus_factors_match_nccl_tests():
    # PERFORMANCE.md: AllReduce 2(n-1)/n, RS/AG (n-1)/n, Bcast/Reduce 1
    assert BUS_FACTORS["allreduce"](8) == pytest.approx(2 * 7 / 8)
    assert BUS_FACTORS["all_gather"](8) == pytest.approx(7 / 8)
    assert BUS_FACTORS["reduce_scatter"](4) == pytest.approx(3 / 4)
    assert BUS_FACTORS["broadcast"](16) == 1.0


@pytest.fixture(scope="module")
def engine(request):
    import jax

    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.strategy.ir import Strategy

    mesh = build_world_mesh(4, jax.devices()[:4])
    return CollectiveEngine(mesh, Strategy.binary(4))


def test_run_sweep_all_collectives(engine):
    results = run_sweep(engine, [256], iters=2, warmup=1)
    colls = {r.collective for r in results}
    assert colls == {
        "allreduce",
        "reduce",
        "broadcast",
        "all_gather",
        "reduce_scatter",
        "all_to_all",
    }
    for r in results:
        assert r.time_us > 0
        assert r.algbw_gbps > 0
        assert r.busbw_gbps == pytest.approx(
            r.algbw_gbps * BUS_FACTORS[r.collective](r.world)
        )


def test_run_sweep_filters(engine):
    results = run_sweep(
        engine, [128], collectives=["allreduce"], impls=["xla", "strategy"], iters=1, warmup=1
    )
    assert {r.collective for r in results} == {"allreduce"}
    assert {r.impl for r in results} == {"xla", "strategy"}


def test_format_table(engine):
    results = run_sweep(engine, [128], collectives=["broadcast"], iters=1, warmup=1)
    table = format_table(results)
    assert "busbw(GB/s)" in table
    assert "broadcast" in table


def test_json_roundtrip(engine):
    import json

    results = run_sweep(engine, [128], collectives=["reduce"], iters=1, warmup=1)
    rec = json.loads(results[0].to_json())
    assert rec["collective"] == "reduce"
    assert rec["world"] == 4


def test_committed_busbw_artifact_parses_and_is_consistent():
    """The round-3 virtual-pod sweep artifact (BASELINE.md table) must parse
    and satisfy the busbw = algbw x correction-factor accounting."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_virtual8_r03.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    assert len(rows) >= 20
    seen = set()
    for r in rows:
        assert r["world"] == 8
        factor = BUS_FACTORS[r["collective"]](r["world"])
        expect = r["algbw_gbps"] * factor
        assert abs(r["busbw_gbps"] - expect) < 1e-9 * max(1.0, expect), r
        assert r["time_us"] > 0 and r["size_bytes"] > 0
        seen.add((r["collective"], r["impl"]))
    # every engine surface appears: three allreduce impls + the rest
    assert ("allreduce", "xla") in seen
    assert ("allreduce", "strategy") in seen
    assert ("allreduce", "pallas_ring") in seen
    for coll in ("reduce", "broadcast", "all_gather", "reduce_scatter", "all_to_all"):
        assert any(c == coll for c, _ in seen), f"missing {coll}"


def test_committed_busbw_r04_artifact_merged_rounds_win():
    """Round-4 sweep artifact: accounting holds, rows are self-describing
    (strategy labels), the merged multi-tree executor beats the sequential
    per-tree chains on the same ring x8 strategy at every common size, and
    the Pallas ring rows cover the dtype tiling matrix."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_virtual8_r04.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    assert len(rows) >= 30
    merged, unmerged = {}, {}
    pallas_dtypes = set()
    for r in rows:
        assert r["world"] == 8
        factor = BUS_FACTORS[r["collective"]](r["world"])
        assert abs(r["busbw_gbps"] - r["algbw_gbps"] * factor) < 1e-9 * max(
            1.0, r["busbw_gbps"]
        ), r
        if r["impl"] == "strategy":
            assert r["strategy"], "strategy rows must be self-describing"
            if r["strategy"] == "ring x8 (merged)":
                merged[r["size_bytes"]] = r["busbw_gbps"]
            elif r["strategy"] == "ring x8":
                unmerged[r["size_bytes"]] = r["busbw_gbps"]
        if r["impl"] == "pallas_ring":
            pallas_dtypes.add(r["dtype"])
    common = set(merged) & set(unmerged)
    assert common, "artifact must carry the merged-vs-sequential A/B"
    for size in common:
        assert merged[size] > 1.5 * unmerged[size], (
            size, merged[size], unmerged[size],
        )
    assert {"float32", "bfloat16", "int8"} <= pallas_dtypes


def test_longcontext_sweep_tiny_and_artifact():
    """benchmarks/longcontext.py: a tiny live sweep plus the committed
    round-3 artifact parse (memory accounting must match the scheme)."""
    import json
    import os

    from benchmarks.longcontext import parse_size, run_sweep

    assert parse_size("4K") == 4096 and parse_size("64") == 64
    res = run_sweep(4, [64], heads=4, head_dim=8, iters=1, warmup=1,
                    schemes=("single", "ring"))
    by_scheme = {r.scheme: r for r in res}
    assert by_scheme["single"].score_bytes_per_device == 4 * 4 * 64 * 64
    # ring shards the sequence: [Tl, Tl] scores, world^2 smaller
    assert by_scheme["ring"].score_bytes_per_device == 4 * 4 * 16 * 16
    assert all(r.fwd_bwd_ms > 0 for r in res)

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "longcontext_virtual4_r03.jsonl",
    )
    rows = [json.loads(l) for l in open(path) if l.strip()]
    assert {r["scheme"] for r in rows} == {"single", "ring", "ulysses"}
    for r in rows:
        assert r["fwd_bwd_ms"] > 0 and r["score_bytes_per_device"] > 0
        if r["scheme"] == "ring":
            single = [
                s for s in rows
                if s["scheme"] == "single" and s["seq"] == r["seq"]
            ][0]
            # the memory story: ring is world^2 smaller than single-device
            assert r["score_bytes_per_device"] * r["world"] ** 2 == \
                single["score_bytes_per_device"]


def test_committed_twolevel_sweep_artifact_parses():
    """The committed two-level (2x4 dcn x ici) sweep artifact parses with the
    same busbw accounting; both engine surfaces appear for allreduce."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_twolevel2x4_r03.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    assert len(rows) >= 14
    seen = set()
    for r in rows:
        assert r["world"] == 8
        factor = BUS_FACTORS[r["collective"]](r["world"])
        assert abs(r["busbw_gbps"] - r["algbw_gbps"] * factor) < 1e-9 * max(
            1.0, r["busbw_gbps"]
        )
        seen.add((r["collective"], r["impl"]))
    assert ("allreduce", "xla") in seen and ("allreduce", "strategy") in seen
    assert ("allreduce", "pallas_ring") not in seen  # flat-mesh kernel
    # reduce/broadcast have no XLA fastpath on two-level meshes: an "xla"
    # row there would be a mislabeled copy of the schedule measurement
    assert ("reduce", "xla") not in seen and ("broadcast", "xla") not in seen
    assert ("reduce", "strategy") in seen and ("broadcast", "strategy") in seen


def test_committed_twolevel_r04_artifact_carries_merged_ab():
    """Round-4 two-level artifact: accounting holds and the multi-tree
    merged-vs-sequential A/B pair is present and distinguishable by label
    (the CPU-pod inversion it records is analyzed in BASELINE.md)."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_twolevel2x4_r04.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    labels = set()
    for r in rows:
        assert r["world"] == 8
        factor = BUS_FACTORS[r["collective"]](r["world"])
        assert abs(r["busbw_gbps"] - r["algbw_gbps"] * factor) < 1e-9 * max(
            1.0, r["busbw_gbps"]
        )
        if r["impl"] == "strategy":
            labels.add(r["strategy"])
    assert "partrees x2 (merged)" in labels and "partrees x2" in labels, labels


def test_collectives_cli_two_level(capsys):
    """--two-level DxI synthesizes the hierarchy and sweeps on the (dcn,
    ici) mesh end to end."""
    from benchmarks.collectives import main as coll_main

    coll_main(["--two-level", "2x4", "--sizes", "4K", "--iters", "1",
               "--warmup", "1", "--collectives", "allreduce"])
    out = capsys.readouterr().out
    assert "allreduce" in out and "strategy" in out


def test_collectives_dtype_sweep(capsys):
    """--dtype bf16/int8 payloads flow through the sweep, including the
    integer-payload branch and the Pallas ring's per-dtype tiling."""
    from benchmarks.collectives import main as coll_main

    coll_main(["--world", "4", "--sizes", "4K", "--iters", "1", "--warmup", "1",
               "--dtype", "bf16", "--collectives", "allreduce",
               "--impls", "xla,strategy"])
    out = capsys.readouterr().out
    assert "allreduce" in out and "dtype=bf16" in out

    coll_main(["--world", "4", "--sizes", "2K", "--iters", "1", "--warmup", "1",
               "--dtype", "int8", "--collectives", "allreduce",
               "--impls", "pallas_ring", "--json"])
    import json as _json

    rows = [
        _json.loads(l) for l in capsys.readouterr().out.splitlines() if l.strip()
    ]
    assert rows and all(r["dtype"] == "int8" for r in rows)
    assert any(r["impl"] == "pallas_ring" for r in rows)


def test_committed_hw_r04_artifacts_verified_tpu():
    """The two round-4 chip records the repo keeps (ROADMAP cites them; they
    predate PRs 1-20, so they say nothing about today's code): every
    healthy row carries a TPU platform stamp — a CPU run cannot be mistaken
    for a chip number — and the lever sweep holds its headline facts."""
    import json
    import os

    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results",
    )
    rows = [
        json.loads(l)
        for l in open(os.path.join(root, "hw_r04s4.jsonl")) if l.strip()
    ]
    probe = next(r for r in rows if r["phase"] == "probe")
    assert probe["parsed"]["platform"] == "tpu"
    prof = next(r for r in rows if r["phase"] == "profile")
    phases = prof["parsed"]["phases"]
    assert set(phases) == {"dispatch", "matmul", "forward", "grad", "train"}
    bench_row = next(r for r in rows if r["phase"] == "bench")["parsed"]
    assert bench_row["attention"] == "flash" and bench_row["mfu"] > 0.35

    levers = [
        json.loads(l)
        for l in open(os.path.join(root, "levers_tpu_r04.jsonl"))
        if l.strip()
    ]
    assert all("error" not in r for r in levers)
    assert all(r["device"] == "TPU v5 lite" for r in levers)
    by = {r["config"]: r for r in levers}
    assert by["base_xla_dense"]["mfu"] >= 0.4
    assert by["flash_dense"]["mfu"] >= 0.4
    # the flash long-context win: 1.5x+ over dense xla attention at T=2048
    assert by["flash_T2048_B4"]["tokens_per_s"] > 1.5 * by["xla_T2048_B4"]["tokens_per_s"]
    # reference-domain image DDP rows exist with sane throughput
    assert by["vgg16_b64_32px"]["images_per_s"] > 1000
    assert by["resnet18_b64_32px"]["images_per_s"] > 1000


def test_committed_twolevel_r05_artifact_has_hierarchical_rows():
    """Round-5 two-level sweep: the gather/scatter primitives ride the
    hierarchical (DCN-first/ICI-first) shards and the subset relay path,
    with the standard busbw accounting intact (VERDICT r4 item 3)."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_twolevel2x4_r05.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    assert len(rows) >= 20
    seen = set()
    for r in rows:
        assert r["world"] == 8
        factor = BUS_FACTORS[r["collective"]](r["world"])
        assert abs(r["busbw_gbps"] - r["algbw_gbps"] * factor) < 1e-9 * max(
            1.0, r["busbw_gbps"]
        )
        seen.add((r["collective"], r["impl"]))
    for coll in ("all_gather", "reduce_scatter", "all_to_all"):
        assert (coll, "two_level") in seen, f"{coll} lost its hierarchical row"
        assert (coll, "subset") in seen, f"{coll} lost its subset row"


def test_committed_busbw_r05_artifact_has_subset_and_ring_rows():
    """Round-5 flat sweep: subset relay rows + Pallas ring RS/AG rows are
    pinned alongside the round-4 surfaces."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "busbw_virtual8_r05.jsonl",
    )
    rows = [json.loads(line) for line in open(path) if line.strip()]
    seen = {(r["collective"], r["impl"]) for r in rows}
    for want in (
        ("all_gather", "subset"), ("reduce_scatter", "subset"),
        ("all_to_all", "subset"), ("reduce_scatter", "pallas_ring"),
        ("all_gather", "pallas_ring"), ("allreduce", "pallas_ring"),
    ):
        assert want in seen, f"busbw_virtual8_r05 lost {want}"


def test_longcontext_streams_rows_per_seq(capsys):
    """Rows flush per sequence length: an OOM at a later seq must not eat
    the earlier measurements (battery longcontext_single contract)."""
    import json as _json

    from benchmarks.longcontext import main as lc_main

    lc_main(["--world", "2", "--seqs", "128,256", "--heads", "2",
             "--head-dim", "8", "--batch", "1", "--iters", "1",
             "--schemes", "ring", "--json"])
    rows = [_json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [r["seq"] for r in rows] == [128, 256]


def test_committed_longcontext_r05_artifact_memory_story():
    """Round-5 SP sweep (virtual pod): ring-flash materializes a CONSTANT
    score footprint across sequence lengths while the dense path grows
    O(T^2), and wins on time at both sweep lengths even under the
    interpreter — the long-context story the reference has no analog for."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results", "longcontext_virtual4_r05.jsonl",
    )
    rows = [json.loads(l) for l in open(path) if l.strip()]
    by = {(r["scheme"], r["seq"]): r for r in rows}
    for seq in (1024, 4096):
        assert by[("ring-flash", seq)]["fwd_bwd_ms"] < by[("single", seq)]["fwd_bwd_ms"]
        assert by[("ring", seq)]["fwd_bwd_ms"] < by[("single", seq)]["fwd_bwd_ms"]
    # flash block tile footprint is T-independent; dense grows 16x for 4x T
    assert (
        by[("ring-flash", 4096)]["score_bytes_per_device"]
        == by[("ring-flash", 1024)]["score_bytes_per_device"]
    )
    assert (
        by[("single", 4096)]["score_bytes_per_device"]
        == 16 * by[("single", 1024)]["score_bytes_per_device"]
    )


# ------------------------------------------------- latency sweep (PR 8)


def test_latency_sweep_rows_byte_identical_and_decision_flagged():
    """The latency-bench artifact is deterministic to the byte, spans the
    crossover, and stamps the per-size decision + the crossover itself."""
    from benchmarks.sim_collectives import latency_sweep

    sizes = [1 << 10, 16 << 10, 256 << 10, 16 << 20]
    rows = latency_sweep(8, sizes)
    again = latency_sweep(8, sizes)
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert all(r["mode"] == "simulated" for r in rows)
    assert len(rows) == len(sizes) * 3  # ring, rd, tree per size
    by = {(r["size_bytes"], r["algo"]): r for r in rows}
    # the sized decision: rd below the crossover, ring above
    assert by[(1 << 10, "rd")]["chosen"] and by[(16 << 10, "rd")]["chosen"]
    assert by[(16 << 20, "ring")]["chosen"]
    assert all(isinstance(r["crossover_bytes"], int) for r in rows)
    x = rows[0]["crossover_bytes"]
    for r in rows:
        assert r["sub_crossover"] == (r["size_bytes"] < x)
        # exactly one chosen algorithm per size
    for s in sizes:
        assert sum(by[(s, a)]["chosen"] for a in ("ring", "rd", "tree")) == 1
    with pytest.raises(ValueError, match="unknown algorithm"):
        latency_sweep(8, sizes, algos=("rind",))


def test_latency_sweep_cli_mutually_exclusive(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--wire-dtype", "off,int8"],
    ):
        with pytest.raises(SystemExit):
            main(["--latency-sweep"] + other)
    capsys.readouterr()


def test_latency_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--latency-sweep", "--world", "8", "--sizes", "4K,1M", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "latency" for r in rows)
    assert {r["algo"] for r in rows} == {"ring", "rd", "tree"}


# ------------------------------------------------ schedule sweep (PR 15)


def test_schedule_sweep_rows_byte_identical_and_parity_pinned():
    """The compiler-bench artifact (docs/COMPILER.md §5) is deterministic
    to the byte, reproduces each legacy plane's own pricing term on the
    re-emitted programs, and stamps the pipelined program's
    beats-lockstep-ring flag at bandwidth-bound sizes."""
    from benchmarks.sim_collectives import SCHEDULE_PROGRAMS, schedule_sweep

    sizes = [64 << 10, 1 << 20, 128 << 20]
    rows = schedule_sweep(8, sizes)
    again = schedule_sweep(8, sizes)
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == len(sizes) * len(SCHEDULE_PROGRAMS)
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "ir"
        assert r["collective"] == "allreduce" and r["world"] == 8
        assert len(r["program_fingerprint"]) == 16
    by = {(r["size_bytes"], r["strategy"].split("-")[0]): r for r in rows}
    for s in sizes:
        # the ring re-emission reproduces the segmented-ring plane's own
        # term exactly — every hop is distance 1, so the fully-connected
        # IR abstraction and the ring embedding agree to the digit
        r = by[(s, "ring")]
        assert r["pred_time_us"] == r["legacy_pred_time_us"]
        # rd/tree legacy terms serialize each message over its ring-hop
        # distance; the IR price assumes full-duplex point-to-point links,
        # so it lower-bounds the plane term — the drift the row exposes
        for algo in ("rd", "tree"):
            r = by[(s, algo)]
            assert r["legacy_pred_time_us"] is not None
            assert r["pred_time_us"] <= r["legacy_pred_time_us"]
        # the pipelined program has no legacy plane — that is the point —
        # and beats the lockstep ring at every bandwidth-bound size
        p = by[(s, "pipelined")]
        assert p["legacy_pred_time_us"] is None
        assert p["beats_lockstep_ring"]
        assert p["pred_time_us"] < p["lockstep_ring_us"]
        # the optimizer gap rows (PR 20): recursive doubling coalesces to
        # one dispatch per round, so the launch-priced optimized plan is
        # a strict win; the segmented ring is already one-message-per-
        # peer-per-round, so optimization is identity there
        r = by[(s, "rd")]
        assert r["opt_dispatches"] < r["dispatches"]
        assert r["passes"] == ["coalesce"]
        assert r["opt_faster"] and r["opt_speedup"] > 1
        assert r["opt_pred_time_us"] < r["naive_launch_pred_time_us"]
        assert r["opt_fingerprint"] != r["program_fingerprint"]
        r = by[(s, "ring")]
        assert r["opt_dispatches"] == r["dispatches"]
        assert r["passes"] == [] and not r["opt_faster"]
        assert r["opt_fingerprint"] == r["program_fingerprint"]
    # priced optimized <= naive at EVERY size, every program (the
    # launch term can only shrink)
    for r in rows:
        assert r["opt_pred_time_us"] <= r["naive_launch_pred_time_us"]
    with pytest.raises(ValueError, match="unknown program"):
        schedule_sweep(8, sizes, programs=("rong",))


def test_schedule_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--hier-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--fabric-sweep"],
        ["--recovery-sweep"],
        ["--serve-sweep"],
        ["--wire-dtype", "off,int8"],
    ):
        with pytest.raises(SystemExit):
            main(["--schedule-sweep"] + other)
    # the programs price the flat --world mesh: --hosts is meaningless
    with pytest.raises(SystemExit):
        main(["--schedule-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_schedule_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--schedule-sweep", "--world", "8", "--sizes", "1M,128M",
        "--programs", "ring,pipelined", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "ir" for r in rows)
    assert {r["strategy"] for r in rows} == {
        "ring-seg-w8", "pipelined-bidir-w8",
    }
    assert all("program_fingerprint" in r for r in rows)


def test_hier_sweep_rows_byte_identical_and_decision_flagged():
    """The hier-bench artifact (docs/HIERARCHY.md §4) is deterministic to
    the byte over the (pods × pod_size × size) grid and stamps the
    two-level-vs-flat decision plus the pod-count crossover per row."""
    from benchmarks.sim_collectives import hier_sweep

    sizes = [64 << 10, 1 << 20, 128 << 20]
    rows = hier_sweep(sizes, pods=(2, 4), pod_sizes=(4, 8))
    again = hier_sweep(sizes, pods=(2, 4), pod_sizes=(4, 8))
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == len(sizes) * 2 * 2
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "two_level"
        assert r["world"] == r["pods"] * r["pod_size"]
        assert r["chosen"] in ("two_level", "flat")
        assert r["two_level_faster"] == (r["chosen"] == "two_level")
        # on the default (ICI-fast / DCN-slow) classes, one pod boundary
        # already pays: every multi-pod cell picks the composed plan
        assert r["chosen"] == "two_level"
        assert r["pred_two_level_us"] < r["pred_flat_us"]
        assert r["crossover_pods"] == 2
    with pytest.raises(ValueError, match="pods >= 2"):
        hier_sweep(sizes, pods=(1,), pod_sizes=(4,))
    with pytest.raises(ValueError, match="pod sizes >= 2"):
        hier_sweep(sizes, pods=(2,), pod_sizes=(1,))


def test_hier_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--wire-dtype", "off,int8"],
    ):
        with pytest.raises(SystemExit):
            main(["--hier-sweep"] + other)
    # the sweep grid names its own topologies: --hosts is meaningless
    with pytest.raises(SystemExit):
        main(["--hier-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_hier_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--hier-sweep", "--sizes", "1M,128M", "--pods", "2,4",
        "--pod-sizes", "4", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "two_level" for r in rows)
    assert {r["pods"] for r in rows} == {2, 4}
    assert all("pred_flat_us" in r and "chosen" in r for r in rows)


# ------------------------------------------------ fabric sweep (PR 12)


def test_fabric_sweep_rows_byte_identical_and_decision_flagged():
    """The fabric-bench artifact (docs/FABRIC.md §5) is deterministic to
    the byte over the (size × congestion intensity × priority mix) grid,
    and every coordinated high-low row stamps the acceptance flag: the
    high-priority job's sharing steady state beats the uncoordinated
    pile-up."""
    from benchmarks.sim_collectives import fabric_sweep

    sizes = [1 << 20, 16 << 20]
    rows = fabric_sweep(8, sizes, intensities=(1.0, 4.0))
    again = fabric_sweep(8, sizes, intensities=(1.0, 4.0))
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == len(sizes) * 2 * 2  # sizes x intensities x mixes
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "fabric"
        assert r["world"] == 8
        assert r["mix"] in ("high-low", "high-high")
        assert r["coordinated"] == (r["mix"] == "high-low")
        assert r["job0_us"] > 0 and r["job1_us"] > 0
        assert 0.0 < r["fairness"] <= 1.0
        if r["mix"] == "high-low":
            assert r["high_beats_uncoordinated"] is True, (
                "priority coordination must leave the high job strictly "
                "better off than the uncoordinated pile-up"
            )
            # yielding costs the low job, never the high job
            assert r["job0_us"] <= r["job1_us"]
        else:
            assert "high_beats_uncoordinated" not in r
    with pytest.raises(ValueError, match="even world"):
        fabric_sweep(7, sizes)
    with pytest.raises(ValueError, match="mixes"):
        fabric_sweep(8, sizes, mixes=("high-medium",))
    with pytest.raises(ValueError, match="intensities"):
        fabric_sweep(8, sizes, intensities=(0.5,))


def test_fabric_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
    ):
        with pytest.raises(SystemExit):
            main(["--fabric-sweep"] + other)
    # the sweep fixes its own two-pod split of --world: --hosts is
    # meaningless and silently accepting it would mislabel the artifact
    with pytest.raises(SystemExit):
        main(["--fabric-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_fabric_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--fabric-sweep", "--world", "8", "--sizes", "1M,16M",
        "--intensities", "1,4", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "fabric" for r in rows)
    assert {r["intensity"] for r in rows} == {1.0, 4.0}
    assert {r["mix"] for r in rows} == {"high-low", "high-high"}
    assert all(
        r["high_beats_uncoordinated"] for r in rows if r["mix"] == "high-low"
    )


# ---------------------------------------------- recovery sweep (PR 13)


def test_recovery_sweep_rows_byte_identical_and_bounds_stamped():
    """The recovery-bench artifact (docs/RECOVERY.md §4) is deterministic
    to the byte over the (world × payload) grid, every default-config row
    from world=32 up stamps the acceptance bound (replication wire
    overhead < 5 % of baseline step comm), and the in-fabric repair beats
    the checkpoint reload on every cell — the reason the replica path
    owns the hot path."""
    from benchmarks.sim_collectives import recovery_sweep

    sizes = [1 << 20, 64 << 20]
    rows = recovery_sweep(sizes, worlds=(8, 32, 64))
    again = recovery_sweep(sizes, worlds=(8, 32, 64))
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == 3 * len(sizes)
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "recovery"
        assert r["replicas"] == 1
        assert r["state_bytes"] == 3 * r["size_bytes"]
        assert r["replication_overhead_us"] > 0
        assert r["overhead_ok"] == (r["replication_overhead_ratio"] < 0.05)
        if r["world"] >= 32:
            # the acceptance pin: k=1 upkeep stays inside 5% of step comm
            # at the default config (the shard shrinks as 1/world)
            assert r["overhead_ok"] is True
        # zero lost steps + one hop vs full-state read + replayed work
        assert r["repair_speedup"] > 1.0
        assert r["replica_repair_us"] < r["ckpt_reload_us"]
    with pytest.raises(ValueError, match="worlds >= 2"):
        recovery_sweep(sizes, worlds=(1,))
    with pytest.raises(ValueError, match="replicas >= 1"):
        recovery_sweep(sizes, replicas=0)
    # an unreplicable cell (k >= world) is skipped loudly in-band
    skip = [
        r for r in recovery_sweep(sizes, worlds=(2,), replicas=2)
        if "skipped" in r
    ]
    assert len(skip) == 1 and "replicas=2" in skip[0]["skipped"]


def test_recovery_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
        ["--fabric-sweep"],
    ):
        with pytest.raises(SystemExit):
            main(["--recovery-sweep"] + other)
    # the grid names its own worlds and prices the ICI class alone:
    # --hosts is meaningless and silently accepting it would mislabel
    # the artifact
    with pytest.raises(SystemExit):
        main(["--recovery-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_recovery_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--recovery-sweep", "--sizes", "1M,64M", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "recovery" for r in rows)
    assert {r["world"] for r in rows} == {8, 32, 64}
    assert all(r["overhead_ok"] for r in rows if r["world"] >= 32)


# ------------------------------------------------- serve sweep (PR 14)


def test_serve_sweep_rows_byte_identical_and_frontier_shaped():
    """The serve-bench artifact (docs/SERVING.md §5) is deterministic to
    the byte over the (arrival rate × decode slots) grid, every cell runs
    the small-message algorithm the selector's crossover picks at serving
    payloads, and the frontier has its load-bearing shape: more slots
    never fatten the p99 sojourn at a fixed rate."""
    from benchmarks.sim_collectives import serve_sweep

    rows = serve_sweep(8, rates=(0.1, 0.25), slots_grid=(1, 2, 4),
                       slo_ms=2.0)
    again = serve_sweep(8, rates=(0.1, 0.25), slots_grid=(1, 2, 4),
                        slo_ms=2.0)
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == 2 * 3
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "serve"
        assert r["world"] == 8 and r["requests"] == 64
        # slots x d_model fp32 sits far below the crossover: rd wins
        assert r["algo"] == "rd"
        assert r["collective_bytes"] == r["slots"] * r["d_model"] * 4
        assert r["pred_step_us"] > 0
        assert r["p99_sojourn_steps"] >= r["p50_sojourn_steps"]
        assert 0.0 < r["utilization"] <= 1.0
        assert 0.0 <= r["slo_attainment"] <= 1.0
    for rate in (0.1, 0.25):
        tails = [
            r["p99_sojourn_steps"] for r in rows
            if r["rate_req_per_step"] == rate
        ]
        assert tails == sorted(tails, reverse=True)
    with pytest.raises(ValueError, match="rates"):
        serve_sweep(8, rates=(0.0,))
    with pytest.raises(ValueError, match="slot"):
        serve_sweep(8, slots_grid=(0,))
    with pytest.raises(ValueError, match="num_requests"):
        serve_sweep(8, num_requests=0)


def test_serve_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
        ["--fabric-sweep"],
        ["--recovery-sweep"],
    ):
        with pytest.raises(SystemExit):
            main(["--serve-sweep"] + other)
    # the frontier prices the TP decode mesh of --world: --hosts is
    # meaningless and silently accepting it would mislabel the artifact
    with pytest.raises(SystemExit):
        main(["--serve-sweep", "--hosts", "2"])
    with pytest.raises(SystemExit):
        main(["--serve-sweep", "--slo-ms", "-1"])
    capsys.readouterr()


def test_serve_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--serve-sweep", "--world", "8", "--rates", "0.1,0.25",
        "--serve-slots", "1,4", "--slo-ms", "2", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "serve" for r in rows)
    assert {r["rate_req_per_step"] for r in rows} == {0.1, 0.25}
    assert {r["slots"] for r in rows} == {1, 4}
    assert all("slo_attainment" in r for r in rows)
    # --slo-ms 0 drops the attainment column instead of faking a bound
    assert main([
        "--serve-sweep", "--world", "8", "--rates", "0.1",
        "--serve-slots", "2", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all("slo_attainment" not in r for r in rows)


def test_disagg_sweep_rows_byte_identical_and_frontier_shaped():
    """The disagg-bench artifact (docs/SERVING.md §7) is deterministic
    to the byte over (mix × split × d_model) at equal chip count, every
    row carries both the two-pool tandem and the colocated baseline, and
    the frontier has its load-bearing cell: a prefill-heavy mix at the
    3:1 chip split strictly beats the colocated p99 TTFT."""
    from benchmarks.sim_collectives import disagg_sweep

    rows = disagg_sweep(8)
    again = disagg_sweep(8)
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == 3 * 2 * 2  # mixes x splits x dims
    for r in rows:
        assert r["mode"] == "simulated" and r["impl"] == "disagg"
        assert r["world"] == 8
        assert r["prefill_world"] + r["decode_world"] == 8
        assert r["prefill_slots"] + r["decode_slots"] == r["coloc_slots"]
        assert r["transfer_steps"] >= 1  # DCN is never free
        assert r["p99_ttft_ms"] > 0 and r["coloc_p99_ttft_ms"] > 0
        assert r["p99_ttft_ms"] >= r["p50_ttft_ms"]
        assert r["p99_sojourn_ms"] > 0 and r["throughput_tok_s"] > 0
        assert r["disagg_beats_colocated_p99_ttft"] == (
            r["p99_ttft_ms"] < r["coloc_p99_ttft_ms"]
        )
    # the acceptance cell: prefill-heavy traffic, 3:1 chips to prefill
    wins = [r for r in rows
            if r["mix"] == "prefill-heavy" and r["split"] == "3:1"]
    assert wins and all(r["disagg_beats_colocated_p99_ttft"] for r in wins)
    # ... and it is a frontier, not a universal win: some cell prefers
    # colocation (decode-heavy traffic pays for the idle prefill pod)
    assert any(not r["disagg_beats_colocated_p99_ttft"] for r in rows)

    with pytest.raises(ValueError, match="even|divide"):
        disagg_sweep(7)
    with pytest.raises(ValueError, match="mix"):
        disagg_sweep(8, mixes=("bursty",))
    with pytest.raises(ValueError, match="split"):
        disagg_sweep(8, splits=("5:1",))
    with pytest.raises(ValueError):
        disagg_sweep(8, total_slots=1)


def test_disagg_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
        ["--fabric-sweep"],
        ["--recovery-sweep"],
        ["--serve-sweep"],
        ["--scale-sweep"],
    ):
        with pytest.raises(SystemExit):
            main(["--disagg-sweep"] + other)
    # the sweep splits --world into its own prefill/decode pods: --hosts
    # is meaningless and silently accepting it would mislabel the artifact
    with pytest.raises(SystemExit):
        main(["--disagg-sweep", "--hosts", "2"])
    with pytest.raises(SystemExit):
        main(["--disagg-sweep", "--slo-ms", "-1"])
    capsys.readouterr()


def test_disagg_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--disagg-sweep", "--world", "8",
        "--disagg-mixes", "prefill-heavy,decode-heavy",
        "--disagg-splits", "1:1", "--disagg-dims", "128", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["impl"] == "disagg" for r in rows)
    assert {r["mix"] for r in rows} == {"prefill-heavy", "decode-heavy"}
    assert all(r["split"] == "1:1" and r["d_model"] == 128 for r in rows)


def test_scale_sweep_rows_deterministic_and_gap_certified():
    """The simscale-bench artifact (docs/SIMULATION.md §7) is byte-
    identical across runs — it carries predictions and certified gaps,
    never wall-clock — and every priced row's gap is non-negative."""
    from benchmarks.sim_collectives import scale_sweep

    worlds, sizes = [32, 64, 512], [1 << 20, 16 << 20]
    rows = scale_sweep(worlds, sizes)
    again = scale_sweep(worlds, sizes)
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    priced = [r for r in rows if "skipped" not in r]
    assert len(priced) == len(worlds) * len(sizes) * 2  # binary + ring
    for r in priced:
        assert r["mode"] == "simulated" and r["impl"] == "sim"
        assert "pred_time_us" in r and "time_us" not in r
        assert r["optimality_gap"] >= 0.0
        assert r["pred_time_us"] >= r["lower_bound_us"]
        assert r["calibration"] == "synthetic"
        # the engine stamp follows the auto rule: event below the
        # vector floor, vector at and above it
        from adapcc_tpu.sim import VECTOR_MIN_WORLD

        want = "vector" if r["world"] >= VECTOR_MIN_WORLD else "event"
        assert r["engine"] == want
    with pytest.raises(ValueError, match="no rows"):
        scale_sweep([], sizes)
    with pytest.raises(ValueError, match=">= 2"):
        scale_sweep([1], sizes)
    with pytest.raises(ValueError, match="unknown collective"):
        scale_sweep(worlds, sizes, collective="alltoall")


def test_scale_sweep_skips_ring_past_depth_cap_loudly():
    from benchmarks.sim_collectives import RING_SCALE_MAX_WORLD, scale_sweep

    big = RING_SCALE_MAX_WORLD * 2
    rows = scale_sweep([big], [1 << 20])
    ring = [r for r in rows if r["strategy"] == "ring"]
    assert ring and all("skipped" in r for r in ring)
    assert all(str(RING_SCALE_MAX_WORLD) in r["skipped"] for r in ring)
    binary = [r for r in rows if r["strategy"] == "binary"]
    assert binary and all("skipped" not in r for r in binary)


def test_scale_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--schedule-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
        ["--fabric-sweep"],
        ["--recovery-sweep"],
        ["--serve-sweep"],
        ["--wire-dtype", "off,int8"],
    ):
        with pytest.raises(SystemExit):
            main(["--scale-sweep"] + other)
    # each world prices its own uniform synthetic topology: --hosts is
    # meaningless and silently accepting it would mislabel the artifact
    with pytest.raises(SystemExit):
        main(["--scale-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_scale_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--scale-sweep", "--scale-worlds", "32,512",
        "--sizes", "1M", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["mode"] == "simulated" for r in rows)
    assert {r["world"] for r in rows} == {32, 512}
    assert all("optimality_gap" in r for r in rows if "skipped" not in r)


# --------------------------------------------------------------------------- #
# pipe sweep (make pipe-bench, docs/PIPELINE.md)
# --------------------------------------------------------------------------- #

def test_pipe_sweep_rows_byte_identical_and_frontier_shaped():
    """The pipe-bench artifact is deterministic to the byte and carries
    the frontier's two invariants per row: the bubble shrinks as
    microbatches grow at fixed stages, and 1F1B stamps its memory win
    exactly where its stash bound is strictly below GPipe's."""
    from benchmarks.sim_collectives import pipe_sweep

    sizes = [1 << 20, 16 << 20]
    rows = pipe_sweep(sizes, stages_grid=(2, 4), microbatch_grid=(2, 4, 8))
    again = pipe_sweep(sizes, stages_grid=(2, 4), microbatch_grid=(2, 4, 8))
    assert [json.dumps(r, sort_keys=True) for r in rows] == [
        json.dumps(r, sort_keys=True) for r in again
    ]
    assert len(rows) == 2 * 3 * 2 * 2  # stages x microbatches x schedules x sizes
    for r in rows:
        assert r["mode"] == "simulated" and r["collective"] == "pipeline"
        assert r["impl"] == f"pipe-{r['schedule']}"
        assert r["ticks"] == 2 * (r["microbatches"] + r["stages"] - 1)
        assert len(r["program_fingerprint"]) == 16
        assert r["pred_step_us"] > 0 and r["hop_program_us"] > 0

    # bubble shrinks with m at fixed stages — schedule-independent
    for stages in (2, 4):
        for schedule in ("gpipe", "1f1b"):
            bubbles = [
                r["bubble_fraction"] for r in rows
                if r["stages"] == stages and r["schedule"] == schedule
                and r["size_bytes"] == sizes[0]
            ]
            assert bubbles == sorted(bubbles, reverse=True)
            assert bubbles[0] > bubbles[-1]

    # the memory win stamps exactly the strict-stash-win cells
    gpipe = {
        (r["stages"], r["microbatches"], r["size_bytes"]): r["stash_bytes"]
        for r in rows if r["schedule"] == "gpipe"
    }
    for r in rows:
        if r["schedule"] != "1f1b":
            assert "memory_win_vs_gpipe" not in r
            continue
        key = (r["stages"], r["microbatches"], r["size_bytes"])
        assert r["memory_win_vs_gpipe"] == (r["stash_bytes"] < gpipe[key])
        # stash_bytes is the max over stages: 1F1B's worst stage holds
        # min(m, stages), so the win appears exactly at m > stages
        assert r["memory_win_vs_gpipe"] == (r["microbatches"] > r["stages"])

    with pytest.raises(ValueError, match="stages"):
        pipe_sweep(sizes, stages_grid=(1,))
    with pytest.raises(ValueError, match="microbatches"):
        pipe_sweep(sizes, microbatch_grid=(0,))
    with pytest.raises(ValueError, match="fwd_us"):
        pipe_sweep(sizes, fwd_us=-1.0)


def test_pipe_sweep_cli_mutually_exclusive_and_rejects_hosts(capsys):
    from benchmarks.sim_collectives import main

    for other in (
        ["--ring-sweep"],
        ["--tune-replay"],
        ["--fused-sweep"],
        ["--overlap-sweep"],
        ["--fault-sweep"],
        ["--latency-sweep"],
        ["--schedule-sweep"],
        ["--adapt-sweep"],
        ["--chaos-sweep"],
        ["--hier-sweep"],
        ["--fabric-sweep"],
        ["--recovery-sweep"],
        ["--serve-sweep"],
        ["--disagg-sweep"],
        ["--scale-sweep"],
        ["--wire-dtype", "off,int8"],
    ):
        with pytest.raises(SystemExit):
            main(["--pipe-sweep"] + other)
    # each stage chain prices on the calibration's bottleneck link class:
    # --hosts is meaningless and silently accepting it would mislabel rows
    with pytest.raises(SystemExit):
        main(["--pipe-sweep", "--hosts", "2"])
    capsys.readouterr()


def test_pipe_sweep_cli_emits_json(capsys):
    from benchmarks.sim_collectives import main

    assert main([
        "--pipe-sweep", "--pipe-stages", "2", "--pipe-microbatches", "2,4",
        "--sizes", "1M", "--json",
    ]) == 0
    rows = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rows and all(r["collective"] == "pipeline" for r in rows)
    assert {r["impl"] for r in rows} == {"pipe-gpipe", "pipe-1f1b"}
    assert {r["microbatches"] for r in rows} == {2, 4}
    assert all("program_fingerprint" in r for r in rows)
