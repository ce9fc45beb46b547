"""bench.py harness: one process, fails loudly without a chip (error field +
nonzero rc + ``value: null``), keeps partial results when a late phase dies."""

import json
import os
import subprocess
import sys

import numpy as np

sys.path.insert(0, "/root/repo")
import bench  # noqa: E402


def test_train_flops_per_token_scales_with_depth():
    from adapcc_tpu.models.gpt2 import GPT2Config

    def flops(n_layer):
        return bench.train_flops_per_token(
            GPT2Config(vocab_size=512, max_seq=64, n_layer=n_layer, n_head=2, d_model=64)
        )

    f0, f2, f4 = flops(0), flops(2), flops(4)
    assert f4 > f2 > f0 > 0  # f0 = the logits matmul term alone
    # the per-layer share is linear in depth: doubling depth doubles it
    np.testing.assert_allclose(f4 - f0, 2 * (f2 - f0), rtol=1e-9)


def test_no_chip_emits_error_json_and_rc2():
    """bench.py measures on a TPU: on the CPU it names the platform it found
    and exits nonzero with no value — in one process, no probing child."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "/root/repo/bench.py"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 2, out.stderr
    parsed = json.loads(out.stdout.strip().splitlines()[-1])
    assert parsed["value"] is None
    assert parsed["error"].startswith("device:") and "'cpu'" in parsed["error"]
    assert parsed["device"]["platform"] == "cpu"
    assert "last_live_bench" not in parsed
    assert parsed["metric"] == "gpt2_ddp_train_throughput"


def test_watchdog_deadline_emits_partial_json():
    # a phase that hangs past BENCH_DEADLINE must still leave an artifact
    code = (
        "import os, sys; sys.path.insert(0, '/root/repo'); "
        "os.environ['BENCH_DEADLINE'] = '2'; "
        "import bench, time; bench._arm_watchdog(); "
        "bench._phase_begin('framework'); time.sleep(30)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert out.returncode == 3
    parsed = json.loads(out.stdout.strip().splitlines()[-1])
    assert "watchdog" in parsed["error"] and "framework" in parsed["error"]


def test_flash_block_for_resolution(monkeypatch):
    """Tile resolution: largest 8-aligned divisor of seq <= the knob, with
    the full-sequence fallback when no aligned divisor exists — no
    knob/seq combination may silently downgrade flash to xla."""
    import bench

    monkeypatch.delenv("BENCH_FLASH_BLOCK", raising=False)
    assert bench.flash_block_for(512) == 256   # default, divides
    assert bench.flash_block_for(384) == 192   # 256 doesn't divide: clamp
    assert bench.flash_block_for(300) == 300   # no aligned divisor: full seq
    assert bench.flash_block_for(8) == 8
    monkeypatch.setenv("BENCH_FLASH_BLOCK", "100")
    assert bench.flash_block_for(512) == 64    # 8-aligned (96) then divisor
    monkeypatch.setenv("BENCH_FLASH_BLOCK", "128")
    assert bench.flash_block_for(512) == 128


def test_flash_autotune_resolution_and_cpu_skip(monkeypatch):
    """Off-TPU the autotuner must skip timing entirely (interpreter timings
    say nothing about Mosaic) and return the static default resolution."""
    from adapcc_tpu.ops import flash_autotune as fa

    fa._cache.clear()
    assert fa.resolve_block(512, 256) == 256
    assert fa.resolve_block(384, 256) == 192
    assert fa.resolve_block(300, 256) == 300  # no aligned divisor: full seq
    best = fa.autotune_flash_block(512)
    assert best == fa.resolve_block(512, fa.DEFAULT_BLOCK) == fa.default_blocks(512, 64, "bfloat16")[0]
    assert fa.last_timings(512) == {}  # swept-off marker, not None
    # cached: a second call must not re-enter the sweep
    assert fa.autotune_flash_block(512) == best


def test_bench_flash_block_auto_env(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_FLASH_BLOCK", "auto")
    monkeypatch.setitem(bench._RESULT, "flash_autotune", None)
    import jax.numpy as jnp

    from adapcc_tpu.ops.flash_attention import default_blocks

    b = bench.flash_block_for(512)
    # the cpu skip path resolves the static default: the one table's tile
    assert b == default_blocks(512, 64, jnp.bfloat16)[0]
    assert bench._RESULT["flash_autotune"]["best"] == b


def test_bench_rejects_bad_opt_moments_env():
    env = dict(os.environ)
    env["BENCH_OPT_MOMENTS"] = "fp8"
    env["JAX_PLATFORMS"] = "cpu"
    env.update({"BENCH_LAYERS": "1", "BENCH_DMODEL": "32", "BENCH_HEADS": "2",
                "BENCH_SEQ": "32", "BENCH_BATCH": "2", "BENCH_STEPS": "1",
                "BENCH_ATTN": "xla"})
    out = subprocess.run(
        [sys.executable, "/root/repo/bench.py"],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert out.returncode != 0
    line = out.stdout.strip().splitlines()[-1]
    assert "BENCH_OPT_MOMENTS" in json.loads(line)["error"]


def test_chip_hbm_gbps_env_override_and_table(monkeypatch):
    import bench

    monkeypatch.setenv("BENCH_HBM_GBPS", "1234.5")
    assert bench.chip_hbm_gbps() == 1234.5
    monkeypatch.delenv("BENCH_HBM_GBPS")

    # table path without touching a backend: fake the device_kind lookup
    class _Dev:
        device_kind = "TPU v5 lite"

    import jax

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    assert bench.chip_hbm_gbps() == 819.0
    assert bench.chip_peak_tflops() == 197.0


def test_flash_autotune_sweep_selection_logic(monkeypatch, capsys):
    """The sweep picks the fastest candidate and treats a per-candidate
    failure (e.g. VMEM overflow at 512) as infinitely slow, naming the block
    and the error on stderr — exercised with a fake platform + fake kernel
    so no TPU is needed."""
    import jax

    import adapcc_tpu.ops as ops
    from adapcc_tpu.ops import flash_autotune as fa

    class _Dev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])

    calls = []

    def fake_flash(q, k, v, causal=True, block_q=128, block_k=128):
        calls.append(block_q)
        if block_q == 512:
            raise RuntimeError("VMEM overflow")
        # "time" is simulated by work volume: block 256 does the least
        import jax.numpy as jnp

        # a ~200x work gap keeps the winner stable even when the suite
        # runs under load and per-call dispatch overhead is noisy
        reps = {128: 200, 256: 1}[block_q]
        out = q
        for _ in range(reps):
            out = out + q * 1e-6
        return out

    monkeypatch.setattr(ops, "flash_attention", fake_flash)
    fa._cache.clear()
    try:
        best = fa.autotune_flash_block(
            512, d_head=8, batch=1, heads=1, warmup=2, iters=2
        )
        timings = fa.last_timings(512, d_head=8, batch=1, heads=1)
        assert best == 256, timings
        assert timings[512] == float("inf")  # failed candidate marked slow
        err = capsys.readouterr().err
        assert "block 512 refused" in err and "VMEM overflow" in err
        assert {128, 256, 512} <= set(calls)  # all candidates attempted
        # cached: no new kernel calls on the second query
        n = len(calls)
        assert fa.autotune_flash_block(512, d_head=8, batch=1, heads=1) == 256
        assert len(calls) == n
        # a different batch/heads is a different problem: it re-sweeps
        # rather than reusing the first shape's winner, and keeps separate
        # timings (ADVICE r5)
        fa.autotune_flash_block(512, d_head=8, batch=2, heads=4, warmup=2, iters=2)
        assert len(calls) > n
        assert fa.last_timings(512, d_head=8, batch=2, heads=4) is not None
        assert fa.last_timings(512, d_head=8, batch=3, heads=1) is None
    finally:
        fa._cache.clear()
