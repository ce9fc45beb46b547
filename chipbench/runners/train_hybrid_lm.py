"""The hybrid language-model training runner: ``adapcc_tpu``'s Kimi-Linear
block (``models/kimi_linear.py``: KDA and latent-attention mixers, sparse
experts) under ``DDPTrainer.step``, built the way
``adapcc_tpu/workloads/train_kimi_linear.run`` builds it, fed by
``adapcc_tpu.data.device_batches``.

It is ``runners/train_moe_lm.py`` for another model: the same set-up (ONE
trainer, ONE state, three checked steps through the window's own call and
feed), the same window (``train.measure``), the same corpus
(``train_moe_lm.packed_rows``), the same record of the routing counts
(``train_moe_lm.Recording``), the same facts for the readers that have no
``workloads`` list.  What differs: the configuration file's keys are
Kimi-Linear's ``config.json``'s, the weights come from
``chipbench/weights_hybrid_lm.py``, the plain reference is
``chipbench/reference/kimi_linear_ref.py``, both mixers' kernels have to have
gone through Mosaic, and the trace is also reduced to the five kernels'
seconds (``chipbench/trace_hybrid_lm.py``).
"""

from __future__ import annotations

import gc
import shutil
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arithmetic_hybrid_lm, correct, trace_hybrid_lm, trace_reduce, weights_hybrid_lm
from chipbench.reference import kimi_linear_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import (
    CHECK_STEPS, SETTLE_STEPS, SPAN_PREFIX, TRACE_SECONDS, CompileLog, Spans,
    epochs_of_batches, measure, peak_bytes, percentile, step_samples_ms,
)
from chipbench.runners.train_moe_lm import Recording, packed_rows


def model_config(config: Dict[str, Any]):
    """``KimiLinearConfig`` from the configuration file: the ``config.json``
    keys it states, and the cut (layers here, experts held)."""
    from adapcc_tpu.models.kimi_linear import KimiLinearConfig

    program = config["assumed"]["program"]
    return KimiLinearConfig.from_config(
        config, experts_held=int(config["num_experts_held"]), remat=program["remat"],
        dtype=jnp.dtype(program["activations"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_kimi_linear.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_kimi_linear import build_trainer

    opt = config["assumed"]["optimizer"]
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
        ),
    )
    program = config["assumed"]["program"]
    mesh = build_world_mesh(world)
    trainer, _ = build_trainer(
        model_config(config), tx, mesh, loss=program["loss"], donate_state=bool(program["donate_state"])
    )
    return trainer, mesh


def fresh_state(trainer, mesh, config: Dict[str, Any], seed: int):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adapcc_tpu.models.trinity import initial_model_state

    params = weights_hybrid_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, initial_model_state(model_config(config)))


def drive_first_steps(trainer, state, batches, config, seed: int):
    """The checked steps, through ``trainer.step`` on ``next(batches)``; the
    program's side of the comparison as ``train.drive_first_steps`` gives it."""
    b1 = config["assumed"]["optimizer"]["b1"]
    rows, losses, grad_norms = [], [], None
    for i in range(CHECK_STEPS):
        batch = next(batches)
        rows.append(np.asarray(batch))
        state, loss = trainer.step(state, batch)
        losses.append(float(jnp.mean(loss)))
        if i == 0:
            grad_norms = np.asarray(jax.jit(leaf_norms)(train._first_moment(state.opt_state))) / (1.0 - b1)
    moved = weights_hybrid_lm.moved_norms(state.params, seed, config)
    program = {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}
    return state, np.stack(rows), program


def reference_numbers(config, rows: np.ndarray, seed: int, precision: str = "float32"):
    """The reference's side, on one device, from weights made anew by the seed."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_hybrid_lm.make_params(seed, config)  # noqa: E731
    out = kimi_linear_ref.train_steps(make(), rows, config, opt, make, precision)
    return {k: np.asarray(v) for k, v in out.items()}


def kernels_through_mosaic() -> None:
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    if decided.get("kda") is not False or decided.get("flash_attention") is not False:
        raise SystemExit(f"chipbench: a mixer's kernel did not run through Mosaic: {decided}")


def run(spec) -> Dict[str, Any]:
    config, mix, say = spec.config, spec.mix, spec.say
    world = int(spec.cell["chips"])
    seq_len, per_chip = arithmetic_hybrid_lm.row_tokens(mix), int(mix["batch_per_chip"])
    global_batch = per_chip * world
    compiles = CompileLog()
    spans = Spans(on=spec.trace)

    def stamp(what: str) -> None:
        say(f"set-up: {what} at {time.perf_counter() - spec.t0:.1f} s")

    stamp("imports and device")
    rows = packed_rows(mix, config["vocab_size"], spec.seed)
    stamp("corpus")
    trainer, mesh = build(config, world)
    state = fresh_state(trainer, mesh, config, spec.seed)
    jax.block_until_ready(state)
    stamp("trainer and state")
    batches = epochs_of_batches(rows, global_batch, mesh, spec.seed, int(mix.get("prefetch", 2)))
    recording = Recording(trainer)
    try:
        state, checked_rows, program = drive_first_steps(recording, state, batches, config, spec.seed)
        stamp(f"first {CHECK_STEPS} steps and the program's side of the check")
        for _ in range(SETTLE_STEPS):
            state, loss = recording.step(state, next(batches))
        loss.block_until_ready()
        if spec.require_chip:
            kernels_through_mosaic()
        before_window = len(recording.sizes)

        seconds = min(spec.seconds, TRACE_SECONDS) if spec.trace else spec.seconds
        trace_dir = spec.out_dir / "trace"
        if spec.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        mark = compiles.mark()
        setup_s = time.perf_counter() - spec.t0
        state, win = measure(recording, state, batches, seconds, spans)
        window_compiles = compiles.mark() - mark
        if spec.trace:
            from adapcc_tpu.models.moe import record_routing

            # the program's own samples, from what the window's steps returned
            # (read here, after the steps, so that no step waits for the host)
            for sizes in recording.read()[before_window:]:
                record_routing(sizes)
            jax.profiler.stop_trace()
    finally:
        batches.close()

    steps = len(win["done"])
    window_s = win["done"][-1] - win["start"]
    tokens_per_s = steps * global_batch * seq_len / window_s
    samples = step_samples_ms(win["start"], win["done"], int(mix.get("steps_per_sample", 1)))
    losses = np.asarray(jax.device_get([jnp.mean(x) for x in win["losses"]]))
    failed = int(np.sum(~np.isfinite(losses)))
    sizes = recording.read()
    bound = global_batch * seq_len * min(int(config["num_experts_per_token"]), int(config["num_experts_held"]))
    # every assignment of a held expert has a row: the counts can never pass the bound
    dropped = int(np.sum(np.maximum(sizes.sum(axis=-1) - bound, 0)))
    say(f"window: {steps} steps in {window_s:.3f} s, {len(samples)} step-time samples, "
        f"median {percentile(samples, 50):.3f} ms, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    say(f"window: step-time samples ms min {min(samples):.3f} p5 {percentile(samples, 5):.3f} "
        f"p95 {percentile(samples, 95):.3f} max {max(samples):.3f}")
    window_sizes = sizes[before_window:]
    say(f"routing: assignments here a layer-step mean {window_sizes.sum(axis=-1).mean():.1f} "
        f"(bound {bound}), fullest/mean {np.mean(window_sizes.max(axis=-1) / np.maximum(window_sizes.mean(axis=-1), 1e-9)):.3f}")
    peak = max(peak_bytes(d) for d in mesh.devices.flat)
    say(f"memory: {mesh.devices.flat[0].memory_stats()}")

    # free the program's state AND its loaded step, then the reference on the checked rows
    del state, trainer, recording, batches
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    reference = reference_numbers(config, checked_rows, spec.seed)
    rows_cmp = correct.compare(program, reference, config["limits"])
    correct.show(rows_cmp, say)
    say(f"correct: losses program {program['losses']} reference {reference['losses'].tolist()}")
    say(f"correct: non-finite losses in the window = {failed}  limit 0")
    say(f"correct: assignments of held experts dropped = {dropped}  limit 0")
    say(f"reference took {time.perf_counter() - t_ref:.1f} s (not in setup_s)")

    facts: Dict[str, Any] = {
        "config": config, "mix": mix, "world": world, "steps": steps,
        "window_s": window_s, "tokens_per_s": tokens_per_s,
        "window_compiles": window_compiles, "spans": dict(spans.seconds),
        "platform": jax.devices()[0].platform, "device_kind": jax.devices()[0].device_kind,
        "trace": None,
        "hybrid": {
            "assignments_per_layer_step": float(window_sizes.sum(axis=-1).mean()),
            "assignment_bound": bound, "dropped": dropped,
        },
    }
    device_extra: Dict[str, Any] = {"memory_peak_bytes": int(peak)}
    breakdown: Optional[Dict[str, Any]] = None
    if spec.trace:
        path = trace_reduce.find_xplane(str(trace_dir))
        if path is None:
            raise SystemExit(f"chipbench: the profiler left no trace under {trace_dir}")
        trace = trace_reduce.load_xplane(str(path))
        reduced = trace_reduce.reduce_trace(trace, SPAN_PREFIX)
        shutil.rmtree(trace_dir)
        if reduced.get("devices"):
            reduced["hybrid_kernel_s"] = trace_hybrid_lm.kernel_seconds(trace)
            say(f"trace: kernel seconds {reduced['hybrid_kernel_s']}")
            for name, seconds in trace_hybrid_lm.top_operations(trace, 40):
                say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")
            facts["trace"] = reduced
            device_extra.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        elif spec.require_chip:
            raise SystemExit("chipbench: no operation ran on a device in the traced window")

    return {
        "correct": bool(correct.verdict(rows_cmp) and failed == 0 and dropped == 0),
        "attempted": steps,
        "failed": failed,
        "end_to_end": {
            "train_tokens_per_s": tokens_per_s,
            "train_step_p95_ms": percentile(samples, 95),
            "setup_s": setup_s,
        },
        "facts": facts,
        "device": device_extra,
        "breakdown": breakdown,
    }
