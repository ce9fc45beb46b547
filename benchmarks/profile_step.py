"""Attribute the GPT-2 train-step time to components on the chip.

It times, on the same device and sizes as bench.py:

1. ``dispatch``   — a trivial jitted op in a loop: per-call host→device
                    dispatch latency;
2. ``matmul``     — a large bf16 matmul chain: achievable MXU TFLOP/s
                    (the realistic ceiling, vs the advertised peak);
3. ``forward``    — GPT-2 forward only;
4. ``grad``       — value_and_grad (forward + backward);
5. ``train``      — the full DDPTrainer step (grad + allreduce + adamw).

Each phase prints one line immediately (a later phase may die); the
final JSON line carries the whole breakdown plus derived MFU per phase.
Optionally dumps a Perfetto/XPlane trace: ``PROFILE_TRACE_DIR=/tmp/trace``.

Usage::

    python -m benchmarks.profile_step            # bench.py default sizes
    BENCH_LAYERS=8 BENCH_DMODEL=512 python -m benchmarks.profile_step
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time


def _progress(msg: str) -> None:
    print(f"[profile] {msg}", file=sys.stderr, flush=True)


def _first_scalar(out):
    """A scalar host read of one output element — closes the timing
    window."""
    import jax
    import jax.numpy as jnp

    leaf = jax.tree_util.tree_leaves(out)[0]
    return float(jax.device_get(jnp.ravel(leaf)[0]))


def _timed(fn, iters: int = 10, warmup: int = 2) -> float:
    """Mean seconds per call over one timed window, compile excluded; the
    window is closed by a scalar device_get (not block_until_ready)."""
    for _ in range(warmup):
        _first_scalar(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    _first_scalar(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import bench as bench_mod
    from bench import _env_int  # shared env knob parsing
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from adapcc_tpu.strategy.ir import Strategy

    out = {"device": str(jax.devices()[0]), "phases": {}}
    trace_dir = os.environ.get("PROFILE_TRACE_DIR")
    trace = (
        jax.profiler.trace(trace_dir) if trace_dir else contextlib.nullcontext()
    )

    world = _env_int("BENCH_WORLD", 0) or len(jax.devices())
    mesh = build_world_mesh(world)
    cfg = GPT2Config(
        vocab_size=16384,
        max_seq=_env_int("BENCH_SEQ", 512),
        n_layer=_env_int("BENCH_LAYERS", 12),
        n_head=_env_int("BENCH_HEADS", 16),
        d_model=_env_int("BENCH_DMODEL", 1024),
        attention=os.environ.get("BENCH_ATTN", "xla"),
    )
    batch = _env_int("BENCH_BATCH", 16) * world
    tokens_per_step = batch * cfg.max_seq
    # phases 1-4 run unsharded on ONE device (the whole global batch), so
    # their utilization divides by the single-chip peak; only the sharded
    # train phase sees the world-scaled peak
    chip_peak = bench_mod.chip_peak_tflops() * 1e12
    peak = chip_peak * world
    flops_tok = bench_mod.train_flops_per_token(cfg)

    with trace:
        # 1. dispatch latency: the per-call floor every step pays
        one = jnp.ones((8, 8))
        tiny = jax.jit(lambda a: a + 1.0)
        t = _timed(lambda: tiny(one), iters=20)
        out["phases"]["dispatch"] = {"ms": round(t * 1e3, 3)}
        _progress(f"dispatch floor {t * 1e3:.2f} ms/call")

        # 2. achievable MXU rate: 8 chained 4096^3 bf16 matmuls
        n, chain = 4096, 8
        a = jnp.ones((n, n), jnp.bfloat16)

        @jax.jit
        def mm(a):
            x = a
            for _ in range(chain):
                x = x @ a
            return x

        t = _timed(lambda: mm(a), iters=5)
        mm_tflops = chain * 2 * n**3 / t / 1e12
        out["phases"]["matmul"] = {
            "ms": round(t * 1e3, 2),
            "tflops": round(mm_tflops, 1),
            "fraction_of_peak": round(mm_tflops * 1e12 / chip_peak, 3),
        }
        _progress(
            f"matmul {mm_tflops:.0f} TFLOP/s "
            f"({mm_tflops * 1e12 / chip_peak:.0%} of one-chip peak)"
        )

        # model + data (bench.py sizes)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, cfg.max_seq)), jnp.int32
        )
        model = GPT2(cfg)
        params = model.init(jax.random.PRNGKey(0), toks[:1])
        if os.environ.get("BENCH_PARAM_DTYPE", "bf16") == "bf16":
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

        # 3. forward only (1/3 of the analytic train FLOPs)
        fwd = jax.jit(loss_fn)
        t = _timed(lambda: fwd(params, toks), iters=5)
        out["phases"]["forward"] = {
            "ms": round(t * 1e3, 1),
            "mfu": round(tokens_per_step * (flops_tok / 3) / t / chip_peak, 4),
        }
        _progress(f"forward {t * 1e3:.0f} ms (mfu {out['phases']['forward']['mfu']:.3f})")

        # 4. forward + backward
        vg = jax.jit(lambda p, b: jax.value_and_grad(loss_fn)(p, b))
        t = _timed(lambda: vg(params, toks), iters=5)
        out["phases"]["grad"] = {
            "ms": round(t * 1e3, 1),
            "mfu": round(tokens_per_step * flops_tok / t / chip_peak, 4),
        }
        _progress(f"grad {t * 1e3:.0f} ms (mfu {out['phases']['grad']['mfu']:.3f})")

        # 5. full framework step
        tx = optax.adamw(3e-4)
        trainer = DDPTrainer(
            loss_fn, tx, mesh, Strategy.ring(world),
            donate_state=False, use_xla_fastpath=True,
        )
        state = TrainState.create(params, tx)
        t = _timed(lambda: trainer.step(state, toks), iters=5)
        train_s = t
        out["phases"]["train"] = {
            "ms": round(t * 1e3, 1),
            "mfu": round(tokens_per_step * flops_tok / t / peak, 4),
            "tokens_per_s": round(tokens_per_step / t, 1),
        }
        _progress(f"train {t * 1e3:.0f} ms (mfu {out['phases']['train']['mfu']:.3f})")

        # 6. roofline attribution from XLA's own cost model: where does the
        # gap between measured step time and the hardware bound actually
        # live?  cost_analysis() counts the compiled program's real FLOPs
        # and HBM bytes; the roofline lower bound is
        # max(flops/peak, bytes/bandwidth), and (measured - bound) is the
        # residual no analytic MFU number can attribute (VERDICT r4 weak #2)
        try:
            # AOT lower+compile does NOT reuse the jit cache, so this pays a
            # second compile of the step — acceptable inside the battery's
            # profile phase (900 s budget), and the only documented way to
            # read the partitioned module's cost model
            compiled = trainer._compiled.lower(state, toks).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            # post-SPMD cost_analysis counts are PER DEVICE (hence ca[0]):
            # the bound divides by single-chip peak/bandwidth — each device
            # runs its 1/world share in the same wall-clock window
            xla_flops = float(ca.get("flops", 0.0))
            xla_bytes = float(ca.get("bytes accessed", 0.0))
            if xla_flops <= 0.0 and xla_bytes <= 0.0:
                raise RuntimeError(
                    "cost_analysis returned no flops/bytes counts on this "
                    "backend — refusing to emit a bogus all-overhead roofline"
                )
            hbm_bw = bench_mod.chip_hbm_gbps() * 1e9
            t_mxu = xla_flops / chip_peak
            t_hbm = xla_bytes / hbm_bw
            bound_s = max(t_mxu, t_hbm)
            out["phases"]["roofline"] = {
                "xla_tflops_counted": round(xla_flops / 1e12, 2),
                # same per-device basis as the XLA counts
                "analytic_tflops": round(
                    tokens_per_step * flops_tok / world / 1e12, 2
                ),
                "hbm_gbytes": round(xla_bytes / 1e9, 2),
                "mxu_bound_ms": round(t_mxu * 1e3, 2),
                "hbm_bound_ms": round(t_hbm * 1e3, 2),
                "bound": "mxu" if t_mxu >= t_hbm else "hbm",
                "roofline_ms": round(bound_s * 1e3, 2),
                "measured_ms": round(train_s * 1e3, 1),
                "residual_ms": round((train_s - bound_s) * 1e3, 1),
                "roofline_fraction": round(bound_s / train_s, 3),
            }
            _progress(
                f"roofline: {out['phases']['roofline']['bound']}-bound "
                f"{bound_s * 1e3:.1f} ms of {train_s * 1e3:.0f} ms measured "
                f"({bound_s / train_s:.0%} of step is hardware-bound)"
            )
        except Exception as e:  # noqa: BLE001 — cost model varies by backend
            out["phases"]["roofline"] = {"error": f"{type(e).__name__}: {e}"[:200]}

    if trace_dir:
        out["trace_dir"] = trace_dir
    print(json.dumps(out))


if __name__ == "__main__":
    main()
