"""Headline benchmark: GPT-2 DDP training throughput with the adaptive stack.

Prints ONE JSON line: ``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
"mfu": ..., "step_ms": ..., ...}``.

The flagship workload (GPT-2 under data parallelism with the AdapCC gradient
hook — the reference's train_ddp GPT-2 configuration, BASELINE.md north star)
is timed against a plain-JAX DDP baseline (jit + psum gradient mean, no
framework) on the same devices.  ``vs_baseline`` = framework tokens/s ÷
plain-JAX tokens/s: ≥1.0 means the adaptive machinery costs nothing.

``mfu`` is analytic model FLOPs (matmuls + attention, ×3 for the backward)
per wall-second over the chip's advertised bf16 peak — the utilization
statement the raw tokens/s number lacks.  ``block_until_ready`` closes every
measured window.

One process, one chip: the script takes the TPU itself (no child probes it
first) and fails — ``error`` field, nonzero exit, ``value: null`` — where
JAX finds no TPU, where the chip's ``device_kind`` is not in the peak table,
or where the flash kernel does not compile; nothing falls back to the CPU,
to XLA attention, or to an older run's number.  A *watchdog* emits whatever
was measured plus an ``error`` field if a phase hangs past
``BENCH_DEADLINE``; each phase records its partial results as soon as they
exist, so a late failure (e.g. in the baseline path) still leaves the
framework numbers in the JSON with ``error`` naming the dead phase.

Size knobs via env (defaults target a single v5e chip):
    BENCH_LAYERS, BENCH_DMODEL, BENCH_HEADS, BENCH_SEQ, BENCH_BATCH,
    BENCH_STEPS, BENCH_WORLD, BENCH_PEAK_TFLOPS, BENCH_HBM_GBPS,
    BENCH_ATTN (flash|xla),
    BENCH_PARAM_DTYPE (bf16|f32), BENCH_LOSS (dense|chunked),
    BENCH_REMAT (off|full|dots|dots_no_batch), BENCH_SCAN (1|0), BENCH_ACCUM,
    BENCH_FLASH_BLOCK (flash tile edge, default 256 — measured best on v5e;
    "auto" runs the measured tile sweep, ops/flash_autotune.py),
    BENCH_OPT_MOMENTS (f32|bf16 adam first-moment dtype),
    BENCH_GRAD_COMPRESS (off|bf16 gradient-sync wire dtype),
    BENCH_DEADLINE
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

_RESULT = {
    "metric": "gpt2_ddp_train_throughput",
    "value": None,
    "unit": "tokens/s",
    "vs_baseline": None,
}
_PHASE = {"name": "startup"}


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _progress(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _phase_begin(name: str) -> None:
    _PHASE["name"] = name
    _progress(f"phase: {name}")


def _emit(rc: int) -> None:
    print(json.dumps(_RESULT), flush=True)
    sys.exit(rc)


def _arm_watchdog() -> None:
    """Emit partial JSON and die if the bench hangs past its deadline —
    a hung phase must still leave an attributable artifact."""
    deadline = _env_int("BENCH_DEADLINE", 1500)

    def fire() -> None:
        _RESULT["error"] = f"watchdog: deadline {deadline}s exceeded in phase {_PHASE['name']}"
        print(json.dumps(_RESULT), flush=True)
        os._exit(3)

    t = threading.Timer(deadline, fire)
    t.daemon = True
    t.start()


#: published peaks of one TPU v5e chip (Google Cloud documentation, "TPU
#: v5e": 197 TFLOP/s bf16, 819 GB/s HBM), by ``device_kind`` substring —
#: JAX reports the chip as "TPU v5 lite".  The one chip this repo has run
#: on; any other device is an error, not a default.
_PEAK_TFLOPS = (("v5 lite", 197.0), ("v5e", 197.0))
_HBM_GBPS = (("v5 lite", 819.0), ("v5e", 819.0))


def _chip_lookup(env_var: str, table) -> float:
    """Env override, else the ``device_kind`` table; an unknown device is an
    error — a utilization against an assumed peak is not a measurement."""
    import jax

    env = os.environ.get(env_var)
    if env:
        return float(env)
    kind = getattr(jax.devices()[0], "device_kind", "")
    for sub, value in table:
        if sub in kind.lower():
            return value
    raise ValueError(
        f"device_kind {kind!r} is not in bench.py's peak table "
        f"({', '.join(sub for sub, _ in table)}); add its published peak "
        f"with a source, or set {env_var}"
    )


def chip_peak_tflops() -> float:
    return _chip_lookup("BENCH_PEAK_TFLOPS", _PEAK_TFLOPS)


def chip_hbm_gbps() -> float:
    return _chip_lookup("BENCH_HBM_GBPS", _HBM_GBPS)


def train_flops_per_token(cfg) -> float:
    """Analytic matmul+attention FLOPs per trained token (fwd + 2×bwd)."""
    d, L, T, V = cfg.d_model, cfg.n_layer, cfg.max_seq, cfg.vocab_size
    per_layer = (
        2 * d * 3 * d        # qkv projection
        + 2 * d * d          # output projection
        + 2 * 2 * d * 4 * d  # mlp up + down
        + 2 * 2 * T * d      # attention scores + values (2·T·d each per token)
    )
    fwd = L * per_layer + 2 * d * V  # + logits matmul
    return 3.0 * fwd


#: the round-4 pick on v5e at T=512 (benchmarks/results/hw_r04s4.jsonl ran
#: it); not re-measured on today's code
_DEFAULT_FLASH_BLOCK = 256


def flash_block_for(seq: int) -> int:
    """Largest 8-aligned tile <= BENCH_FLASH_BLOCK that divides ``seq`` —
    flash requires T %% block == 0, so an indivisible seq (384, 640, ...)
    clamps to a compatible tile instead of silently downgrading to xla
    attention.  When no aligned divisor exists (seq itself not a multiple
    of 8, or a pathological knob value), fall back to the full sequence as
    one block — always kernel-legal.

    ``BENCH_FLASH_BLOCK=auto`` runs the measured tile sweep instead
    (ops/flash_autotune.py): each candidate is timed on the chip and the
    per-candidate seconds land in the artifact under ``flash_autotune``."""
    raw = os.environ.get("BENCH_FLASH_BLOCK", "").strip().lower()
    if raw == "auto":
        import jax.numpy as jnp

        from adapcc_tpu.ops.flash_autotune import autotune_flash_block, last_timings

        d_head = _env_int("BENCH_DMODEL", 1024) // _env_int("BENCH_HEADS", 16)
        # sweep at the bench's REAL shape: per-rank batch, head count, and
        # the activation dtype (GPT2Config.dtype — bf16 regardless of the
        # BENCH_PARAM_DTYPE param cast), so the crowned tile's VMEM
        # footprint matches what the flagship step actually runs
        batch = _env_int("BENCH_BATCH", 16)
        heads = _env_int("BENCH_HEADS", 16)
        best = autotune_flash_block(
            seq, d_head=d_head, dtype=jnp.bfloat16, batch=batch, heads=heads
        )
        timings = last_timings(
            seq, d_head=d_head, dtype=jnp.bfloat16, batch=batch, heads=heads
        )
        _RESULT["flash_autotune"] = {
            "best": best,
            "timings_ms": {
                str(b): (round(t * 1e3, 3) if t != float("inf") else None)
                for b, t in (timings or {}).items()
            },
        }
        _progress(f"flash autotune: best block {best} of {timings}")
        return best
    want = _env_int("BENCH_FLASH_BLOCK", _DEFAULT_FLASH_BLOCK)
    b = min(max(8, want - want % 8), seq)
    while b >= 8 and seq % b:
        b -= 8
    return b if b >= 8 and seq % b == 0 else seq


def _parse_remat_env() -> "str | None":
    """Validate BENCH_REMAT before any slow phase — a typo must fail fast,
    not after a multi-minute compile."""
    remat_env = os.environ.get("BENCH_REMAT", "").strip().lower()
    if remat_env in ("", "0", "off", "false", "no", "none"):
        return None
    if remat_env in ("1", "on", "yes", "true", "full"):
        return "full"
    if remat_env in ("dots", "dots_no_batch"):
        return remat_env
    raise ValueError(
        f"BENCH_REMAT={remat_env!r}: expected off/full/dots/dots_no_batch"
    )


def main() -> None:
    _arm_watchdog()
    _phase_begin("config")
    try:
        remat_policy = _parse_remat_env()
        grad_compress = os.environ.get("BENCH_GRAD_COMPRESS", "off")
        if grad_compress not in ("off", "bf16"):
            raise ValueError(
                f"BENCH_GRAD_COMPRESS={grad_compress!r}: expected off/bf16"
            )
        # BENCH_OPT_MOMENTS=bf16 stores adam's first moment in bf16 — a
        # third less optimizer HBM traffic per step for ~bf16-eps update
        # noise (the second moment stays fp32: optax's mu_dtype knob)
        opt_moments = os.environ.get("BENCH_OPT_MOMENTS", "f32")
        if opt_moments not in ("f32", "bf16"):
            raise ValueError(
                f"BENCH_OPT_MOMENTS={opt_moments!r}: expected f32/bf16"
            )
        attention = os.environ.get("BENCH_ATTN", "flash")
        if attention not in ("flash", "xla"):
            raise ValueError(f"BENCH_ATTN={attention!r}: expected flash/xla")
    except ValueError as e:
        _RESULT["error"] = str(e)
        _emit(2)

    _phase_begin("device")
    import jax

    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    _RESULT["compile_cache"] = enable_compile_cache()
    try:
        dev = jax.devices()[0]
        _RESULT["device"] = {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        }
        if dev.platform != "tpu":
            raise RuntimeError(
                f"bench.py measures on a TPU; JAX found platform "
                f"{dev.platform!r} ({dev.device_kind})"
            )
        peak_tflops = chip_peak_tflops()  # unknown device_kind: error here
    except Exception as e:  # noqa: BLE001
        _RESULT["error"] = f"device: {type(e).__name__}: {e}"[:500]
        _emit(2)

    _phase_begin("setup")
    try:
        import jax.numpy as jnp
        import numpy as np
        import optax

        from adapcc_tpu.comm.mesh import build_world_mesh
        from adapcc_tpu.ddp import DDPTrainer, TrainState
        from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
        from adapcc_tpu.strategy.ir import Strategy

        world = _env_int("BENCH_WORLD", 0) or len(jax.devices())
        mesh = build_world_mesh(world)

        cfg = GPT2Config(
            vocab_size=16384,
            max_seq=_env_int("BENCH_SEQ", 512),
            n_layer=_env_int("BENCH_LAYERS", 12),
            n_head=_env_int("BENCH_HEADS", 16),
            d_model=_env_int("BENCH_DMODEL", 1024),
            attention=attention,
            # flash tile: largest seq-compatible tile <= BENCH_FLASH_BLOCK
            flash_block=flash_block_for(_env_int("BENCH_SEQ", 512)),
            # BENCH_REMAT: unset/""/"0"/"off" = no remat; "dots" |
            # "dots_no_batch" pick a policy; any other truthy value = "full"
            remat=remat_policy is not None,
            remat_policy=remat_policy or "full",
        )
        _RESULT["remat"] = remat_policy or "off"
        _RESULT["flash_block"] = cfg.flash_block
        per_rank_batch = _env_int("BENCH_BATCH", 16)
        accum = _env_int("BENCH_ACCUM", 1)
        _RESULT["accum"] = accum
        batch = per_rank_batch * world
        steps = _env_int("BENCH_STEPS", 10)

        model = GPT2(cfg)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, cfg.max_seq)), jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), tokens[:1])
        param_dtype = os.environ.get("BENCH_PARAM_DTYPE", "bf16")
        if param_dtype == "bf16":
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
        _RESULT["attention"] = attention
        _RESULT["param_dtype"] = param_dtype

        # BENCH_LOSS=chunked fuses the LM head into an online-softmax scan
        # (ops/chunked_ce.py): no [B,T,V] logits in HBM, one recompute in bwd
        loss_impl = os.environ.get("BENCH_LOSS", "dense")
        _RESULT["loss_impl"] = loss_impl
        if loss_impl == "chunked":
            from adapcc_tpu.models.gpt2 import lm_loss_chunked

            def loss_fn(p, b):
                return lm_loss_chunked(model, p, b, block=2048)
        else:

            def loss_fn(p, b):
                return lm_loss(model.apply(p, b), b)

        _RESULT["opt_moments"] = opt_moments
        tx = optax.adamw(
            3e-4,
            mu_dtype=jnp.bfloat16 if opt_moments == "bf16" else None,
        )

        use_scan = _env_int("BENCH_SCAN", 1)
        _RESULT["dispatch"] = "scan" if use_scan else "loop"

        def time_steps(step_fn, state, label):
            """Mean steady-state step seconds.

            ``step_fn`` runs either one step per call (loop mode: every call
            pays the host→device dispatch) or all ``steps`` in one scanned
            dispatch (BENCH_SCAN=1, default: the device-side throughput
            number).  One untimed call compiles and warms the program; its
            seconds land in the JSON as ``warmup_ms_<label>``."""
            t0 = time.perf_counter()
            state, loss = step_fn(state)
            jax.block_until_ready(loss)
            _RESULT[f"warmup_ms_{label}"] = round(
                (time.perf_counter() - t0) * 1e3, 1
            )
            t0 = time.perf_counter()
            if use_scan:
                state, loss = step_fn(state)
            else:
                for _i in range(steps):
                    state, loss = step_fn(state)
            jax.block_until_ready(loss)
            return (time.perf_counter() - t0) / steps

        tokens_per_step = batch * cfg.max_seq
        flops_per_tok = train_flops_per_token(cfg)
        _RESULT["model_flops_per_token"] = round(flops_per_tok / 1e6, 1)
        _RESULT["world"] = world
    except Exception as e:  # noqa: BLE001
        _RESULT["error"] = f"setup: {type(e).__name__}: {e}"[:500]
        _emit(1)

    # --- framework path: DDPTrainer with the adaptive gradient hook ---------
    _phase_begin("framework")
    try:
        trainer = DDPTrainer(
            loss_fn, tx, mesh, Strategy.ring(world),
            donate_state=True, use_xla_fastpath=True,
            # BENCH_ACCUM>1 scans microbatches inside the step: activation
            # memory / accum at unchanged math — the HBM headroom knob
            accum_steps=accum,
            # BENCH_GRAD_COMPRESS=bf16 halves gradient-sync wire bytes
            grad_compress=grad_compress,
        )
        _RESULT["grad_compress"] = grad_compress
        # both paths donate their state; give each its own param buffers
        fw_state = TrainState.create(jax.tree_util.tree_map(jnp.array, params), tx)
        if use_scan:
            fw_time = time_steps(
                lambda s: trainer.scan_steps(s, tokens, steps), fw_state, "framework"
            )
        else:
            fw_time = time_steps(
                lambda s: trainer.step(s, tokens), fw_state, "framework"
            )

        value = tokens_per_step / fw_time
        peak = peak_tflops * 1e12 * world
        _RESULT["value"] = round(value, 1)
        _RESULT["step_ms"] = round(fw_time * 1e3, 2)
        _RESULT["mfu"] = round(value * flops_per_tok / peak, 4)
        _progress(
            f"framework: {value:,.0f} tok/s, {fw_time * 1e3:.1f} ms/step, "
            f"mfu {_RESULT['mfu']:.3f}"
        )
    except Exception as e:  # noqa: BLE001
        _RESULT["error"] = f"framework: {type(e).__name__}: {e}"[:500]
        _emit(1)

    # --- baseline: plain jit + psum DDP (no framework) ----------------------
    _phase_begin("baseline")
    try:
        from jax.sharding import PartitionSpec as P

        def base_step_shard(state, b):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, b)
            grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "ranks"), grads)
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params2 = optax.apply_updates(state.params, updates)
            return (
                TrainState(params=params2, opt_state=opt_state, step=state.step + 1),
                loss[None],
            )

        if use_scan:

            def base_scan_shard(state, b):
                def body(st, _):
                    st2, loss = base_step_shard(st, b)
                    return st2, loss[0]

                st, losses = jax.lax.scan(body, state, None, length=steps)
                return st, losses[None]

            base_inner = base_scan_shard
        else:
            base_inner = base_step_shard
        base_fn = jax.jit(
            jax.shard_map(
                base_inner,
                mesh=mesh,
                in_specs=(P(), P("ranks")),
                out_specs=(P(), P("ranks")),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
        base_state = TrainState.create(jax.tree_util.tree_map(jnp.array, params), tx)
        base_time = time_steps(lambda s: base_fn(s, tokens), base_state, "baseline")
        baseline = tokens_per_step / base_time
        _RESULT["baseline_step_ms"] = round(base_time * 1e3, 2)
        _RESULT["vs_baseline"] = round(_RESULT["value"] / baseline, 4)
        _progress(f"baseline: {baseline:,.0f} tok/s, {base_time * 1e3:.1f} ms/step")
    except Exception as e:  # noqa: BLE001
        # the framework numbers above are already recorded — keep them
        _RESULT["error"] = f"baseline: {type(e).__name__}: {e}"[:500]
        _emit(1)

    _emit(0)


if __name__ == "__main__":
    main()
