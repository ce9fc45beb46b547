"""The sparse-expert language-model training runner: ``adapcc_tpu``'s
Trinity block (``models/trinity.py``) under ``DDPTrainer.step``, built the
way ``adapcc_tpu/workloads/train_trinity.run`` builds it (stateful loss that
hands the routing counts out beside the loss, donating step), fed by
``adapcc_tpu.data.device_batches``.

It is ``runners/train.py`` for another model: the same set-up (ONE trainer,
ONE state, three checked steps through the window's own call and feed), the
same window (``train.measure``), the same facts for the readers that have no
``workloads`` list.  What differs: the configuration file's keys are
``config.json``'s, the weights come from ``chipbench/weights_moe_lm.py``, the
plain reference is ``chipbench/reference/trinity_ref.py``, and ``correct``
also wants every assignment of a held expert computed (``moe.dropped`` 0).
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arithmetic_moe_lm, correct, trace_moe_lm, trace_reduce, weights_moe_lm
from chipbench.reference import trinity_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import (
    CHECK_STEPS, SETTLE_STEPS, SPAN_PREFIX, TRACE_SECONDS, CompileLog, Spans,
    epochs_of_batches, measure, peak_bytes, percentile, step_samples_ms,
)
from chipbench.traffic import generator


def packed_rows(mix: Dict[str, Any], vocab_size: int, seed: int) -> np.ndarray:
    """The cell's corpus: the generator's walks, ``walks_per_row`` of them
    packed end to end to a row."""
    walks = generator.make_rows(mix, vocab_size, seed)
    return walks.reshape(-1, arithmetic_moe_lm.row_tokens(mix))


def model_config(config: Dict[str, Any]):
    """``TrinityConfig`` from the configuration file: the ``config.json``
    keys it states, and the cut (layers here, experts held)."""
    from adapcc_tpu.models.trinity import TrinityConfig

    program = config["assumed"]["program"]
    stated = {f.name for f in dataclasses.fields(TrinityConfig)} & set(config)
    fields = {k: config[k] for k in sorted(stated) if k != "layer_types"}
    return TrinityConfig(
        **fields,
        layer_types=weights_moe_lm.layer_kinds(config),
        experts_held=int(config["num_experts_held"]),
        attention=program["attention"], remat=program["remat"],
        dtype=jnp.dtype(program["activations"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_trinity.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_trinity import build_trainer

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

    params = weights_moe_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, initial_model_state(model_config(config)))


class Recording:
    """``trainer.step`` that also keeps what each step returned beside its
    loss: a copy of the routing counts (the state itself is donated to the
    next step), read after the window."""

    def __init__(self, trainer) -> None:
        self.trainer = trainer
        self.sizes: List[Any] = []

    def step(self, state, batch):
        state, loss = self.trainer.step(state, batch)
        self.sizes.append(jnp.copy(state.model_state["moe_sizes"]))
        return state, loss

    def read(self) -> np.ndarray:
        """``[steps, expert layers, held]``."""
        return np.asarray(jax.device_get(self.sizes))


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
    moved = weights_moe_lm.moved_norms(state.params, seed, config)
    program = {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}
    return state, np.stack(rows), program


def reference_numbers(config, rows: np.ndarray, seed: int, precision: str = "float32"):
    """The reference's side, on one device, from weights made anew by the seed."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_moe_lm.make_params(seed, config)  # noqa: E731
    out = trinity_ref.train_steps(make(), rows, config, opt, make, precision)
    return {k: np.asarray(v) for k, v in out.items()}


def run(spec) -> Dict[str, Any]:
    config, mix, say = spec.config, spec.mix, spec.say
    world = int(spec.cell["chips"])
    seq_len, per_chip = arithmetic_moe_lm.row_tokens(mix), int(mix["batch_per_chip"])
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
        if spec.require_chip and config["assumed"]["program"]["attention"] == "flash":
            from adapcc_tpu.ops.kernel_mode import interpret_decisions

            if interpret_decisions().get("flash_attention") is not False:
                raise SystemExit(
                    f"chipbench: the flash kernel did not run through Mosaic: {interpret_decisions()}"
                )
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
    bound = global_batch * seq_len * min(int(config["num_experts_per_tok"]), int(config["num_experts_held"]))
    # every assignment of a held expert has a row: the counts can never pass the bound
    dropped = int(np.sum(np.maximum(sizes.sum(axis=-1) - bound, 0)))
    say(f"window: {steps} steps in {window_s:.3f} s, {len(samples)} step-time samples, "
        f"median {percentile(samples, 50):.3f} ms, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    say(f"window: step-time samples ms min {min(samples):.3f} p5 {percentile(samples, 5):.3f} "
        f"p95 {percentile(samples, 95):.3f} max {max(samples):.3f}")
    window_sizes = sizes[before_window:]
    say(f"routing: assignments here a layer-step mean {window_sizes.sum(axis=-1).mean():.1f} "
        f"(bound {bound}), fullest/mean {np.mean(window_sizes.max(axis=-1) / np.maximum(window_sizes.mean(axis=-1), 1e-9)):.3f}")
    tenths = np.array_split(window_sizes.sum(axis=-1).mean(axis=-1), 10)
    say(f"routing: assignments here a layer, by tenth of the window {[int(t.mean()) for t in tenths if t.size]}")
    peak = max(peak_bytes(d) for d in mesh.devices.flat)
    say(f"memory: {mesh.devices.flat[0].memory_stats()}")

    # free the program's state AND its loaded step (7 GB of temporaries stay
    # reserved while it is), then the reference on the checked rows
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
        "moe": {
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
            reduced["expert_s"] = trace_moe_lm.expert_seconds(trace, config, global_batch * seq_len)
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
