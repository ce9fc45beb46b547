"""``chipbench/trace_reduce.py``: interval arithmetic and the reduction, on
hand-made traces with hand-computed values and on a fixture cut from a real
run of ``gpt2-small-train`` on a TPU v5e (two steps; PR 23)."""

import pytest

from chipbench_tiny import ROOT

from chipbench import trace_reduce as tr

FIXTURE = ROOT / "chipbench/fixtures/small_train_2steps.json.gz"

FUSION = "%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop, calls=%fused_computation.3"
AR_START = "%all-reduce-start.4 = f32[1024]{0} all-reduce-start(f32[1024]{0} %fusion.9), channel_id=1, replica_groups={{0,1,2,3}}"
AR_DONE = "%all-reduce-done.4 = f32[1024]{0} all-reduce-done(f32[1024]{0} %all-reduce-start.4)"
FWD = ('%attn.3 = (bf16[192,1024,64]{2,1,0}, f32[192,1024,8]{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %a.1, '
       'bf16[192,1024,64]{2,1,0} %b.2, bf16[192,1024,64]{2,1,0} %c.3), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
DQ = ('%attn.5 = bf16[192,1024,64]{2,1,0} custom-call(bf16[192,1024,64]{2,1,0} %a.1, bf16[192,1024,64]{2,1,0} %b.2, '
      'bf16[192,1024,64]{2,1,0} %c.3, bf16[192,1024,64]{2,1,0} %d.4, f32[192,1024,8]{2,1,0} %e.5, f32[192,1024,8]{2,1,0} %f.6), '
      'custom_call_target="tpu_custom_call"')
DKV = DQ.replace("%attn.5 = bf16[192,1024,64]{2,1,0} ", "%attn.7 = (bf16[192,1024,64]{2,1,0}, bf16[192,1024,64]{2,1,0}) ")


def test_union_subtract_clip_by_hand():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == [(0, 4), (5, 12)]
    assert tr.total([(0, 4), (5, 12)]) == 11
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (5, 7), (9, 22), (29, 40)]) == [
        (0, 2), (3, 5), (7, 9), (22, 29),
    ]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []
    assert tr.clip([(0, 4), (5, 12), (20, 30)], (3, 21)) == [(3, 4), (5, 12), (20, 21)]


def test_operations_are_told_apart_by_their_hlo_text():
    assert tr.flash_kernel(FWD) == "flash_fwd"
    assert tr.flash_kernel(DQ) == "flash_bwd_dq"
    assert tr.flash_kernel(DKV) == "flash_bwd_dkv"
    assert tr.flash_kernel(FUSION) is None
    assert tr.is_collective(AR_START) and tr.is_collective(AR_DONE) and not tr.is_collective(FUSION)
    assert tr.stable_name(FUSION) == "fusion bf16[8,128]"
    assert tr.stable_name(FUSION.replace("fusion.12", "fusion.977")) == "fusion bf16[8,128]"
    assert tr.stable_name(AR_DONE) == tr.stable_name(AR_START) == "all-reduce"
    assert tr.stable_name(DKV) == "flash_bwd_dkv"
    assert tr.stable_name("not hlo at all") == "not hlo at all"


def _two_chip_trace():
    """Window 0..1000 ns (first span starts at 0, last op ends at 1000).
    Chip 0: fusion 100..400, all-reduce 350..700 (50 under the fusion), flash
    fwd 700..900, fusion 950..1000.  Chip 1: fusion 0..500, all-reduce
    500..600, dq 600..1000."""
    chip0 = [[FUSION, 100, 300], [AR_DONE, 350, 350], [FWD, 700, 200], [FUSION, 950, 50]]
    chip1 = [[FUSION, 0, 500], [AR_DONE, 500, 100], [DQ, 600, 400]]
    spans = [["chipbench.input_wait", 0, 100], ["chipbench.step_dispatch", 100, 50],
             ["chipbench.step_dispatch", 880, 90], ["unrelated", 0, 1000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": chip0},
                                            {"name": "XLA Modules", "events": [["jit_step(1)", 100, 900]]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": chip1}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
        {"name": "#Chip0 Misc", "lines": []},
    ]}


def test_reduction_of_a_hand_made_two_chip_trace():
    r = tr.reduce_trace(_two_chip_trace())
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy: 100..900 and 950..1000 = 850; chip 1: 0..1000 = 1000
    assert r["busy_s"] == pytest.approx((850 + 1000) / 2 * 1e-9)
    # a collective runs and nothing else does: chip 0 400..700 = 300, chip 1 500..600 = 100
    assert r["exposed_collective_s"] == pytest.approx((300 + 100) / 2 * 1e-9)
    assert r["kernel_s"] == {
        "flash_fwd": pytest.approx(200 / 2 * 1e-9), "flash_bwd_dq": pytest.approx(400 / 2 * 1e-9),
        "flash_bwd_dkv": 0.0,
    }
    ops = dict(r["device_ops"])
    assert ops["fusion bf16[8,128]"] == pytest.approx((300 + 50 + 500) / 2 * 1e-9)
    assert ops["all-reduce"] == pytest.approx((350 + 100) / 2 * 1e-9)
    # chip 0 idles 0..100 under input_wait and 900..950 under step_dispatch
    assert dict(r["idle_gaps"]) == {
        "input_wait": pytest.approx(100 / 2 * 1e-9), "step_dispatch": pytest.approx(50 / 2 * 1e-9),
    }


def test_a_trace_with_no_device_operation_says_so():
    trace = _two_chip_trace()
    trace["planes"] = trace["planes"][2:]
    assert tr.reduce_trace(trace) == {"devices": 0}


def test_recorded_fixture_of_two_steps_on_the_chip():
    trace = tr.load_json(str(FIXTURE))
    ops = tr.device_ops(trace)
    assert list(ops) == [0] and len(ops[0]) > 10_000
    modules = [e for p in trace["planes"] if p["name"] == "/device:TPU:0"
               for l in p["lines"] if l["name"] == "XLA Modules" for e in l["events"]]
    assert len(modules) == 2 and all(e[0].startswith("jit_per_shard") for e in modules)
    r = tr.reduce_trace(trace)
    # the busy union against the executed programs: operations run back to
    # back inside a program, so the union is about the programs' length (the
    # window opens at the first host span in the cut, a little after the
    # first program started)
    in_programs = sum(e[2] for e in modules) / 1e9
    assert r["busy_s"] == pytest.approx(in_programs, rel=0.02)
    # a brute-force union, microsecond by microsecond
    lo = min(e[1] for e in ops[0])
    covered = set()
    for _, start, dur in ops[0]:
        covered.update(range((start - lo) // 1000, (start - lo + dur) // 1000 + 1))
    assert r["busy_s"] == pytest.approx(len(covered) * 1e-6, rel=0.02)
    # 12 layers x 2 steps of each flash kernel, and their summed durations
    counts = {}
    sums = {}
    for name, _, dur in ops[0]:
        k = tr.flash_kernel(name)
        if k:
            counts[k] = counts.get(k, 0) + 1
            sums[k] = sums.get(k, 0) + dur
    assert counts == {"flash_fwd": 24, "flash_bwd_dq": 24, "flash_bwd_dkv": 24}
    for k in tr.FLASH_KERNELS:
        assert r["kernel_s"][k] == pytest.approx(sums[k] / 1e9)
    assert r["exposed_collective_s"] == 0.0  # one chip: no collective in the step
    assert [n for n, _ in r["device_ops"][:3]] == ["flash_bwd_dkv", "flash_fwd", "flash_bwd_dq"]
    # the device idles while the host is inside trainer.step (PERF.md, PR 23)
    assert r["idle_gaps"][0][0] == "step_dispatch"
    assert r["busy_s"] + sum(s for _, s in r["idle_gaps"]) == pytest.approx(r["window_s"], rel=1e-3)


def test_the_readers_take_their_numbers_from_the_reduction():
    from chipbench import run

    def reader(name):
        return run.load_reader(name, ROOT / "chipbench/metrics")

    r = tr.reduce_trace(tr.load_json(str(FIXTURE)))
    facts = {
        "trace": r, "steps": 2, "world": 1, "platform": "tpu", "device_kind": "TPU v5 lite",
        "config": {"n_layer": 12, "n_head": 12, "d_model": 768, "vocab_size": 50257},
        "mix": {"batch_per_chip": 16, "seq_len": 1024},
        "tokens_per_s": 60000.0, "spans": {"input_wait": [1e-4, 3e-4], "step_dispatch": []},
        "window_compiles": 0,
    }
    spent = sum(r["kernel_s"].values())
    assert reader("flash_time_share").read(facts) == pytest.approx(100 * spent / r["window_s"])
    # 12 layers x 2 steps x (fwd + bwd FLOPs) / 197 TFLOP/s, over the kernels' time
    need = 12 * 2 * 3.5 * (2 * 2 * 16 * 12 * 1024 * 1024 / 2 * 64) / 197e12
    assert reader("flash_roofline").read(facts) == pytest.approx(100 * need / spent)
    assert 5 < reader("flash_roofline").read(facts) < 100
    assert reader("grad_sync_exposed_ms").read(facts) is None
    assert reader("grad_sync_exposed_ms").read(dict(facts, world=4)) == 0.0
    assert reader("input_wait_ms").read(facts) == pytest.approx(0.2)
    assert reader("step_dispatch_ms").read(facts) is None
    assert reader("train_mfu").read(facts) == pytest.approx(100 * 60000 * 797.8e6 / 197e12, rel=1e-3)
    assert reader("train_mfu").read(dict(facts, platform="cpu")) is None
    assert reader("window_compiles").read(facts) == 0.0
    for name in ("flash_time_share", "flash_roofline", "grad_sync_exposed_ms"):
        assert reader(name).read(dict(facts, trace=None)) is None
