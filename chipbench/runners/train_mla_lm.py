"""The latent-attention language-model training runner: ``adapcc_tpu``'s
JoyAI-LLM-Flash block (``models/joyai_flash.py``: rotated latent attention
with a query rank on every layer, sparse experts, a multi-token-prediction
module whose loss term rides beside the trunk's) under ``DDPTrainer.step``,
built the way ``adapcc_tpu/workloads/train_joyai_flash.run`` builds it, fed by
``adapcc_tpu.data.device_batches``.

It is ``runners/train_hybrid_lm.py`` for another model: the same set-up (ONE
trainer, ONE state, three checked steps through the window's own call and
feed), the same window (``train.measure``), the same corpus
(``train_moe_lm.packed_rows``), the same facts for the readers that have no
``workloads`` list.  **What differs between the language-model runners is one
object, :class:`Parts`**: the model's build and first state, its weight maker
and reference, what a step hands out beside its loss, the comparison's rows,
what has to be true of the program, and what the trace is also reduced to;
:func:`run_parts` is the rest, written once (the ``benchmark`` PR that PERF.md
section 7 queues can hand it the other two runners' parts and delete their
copies).  Here: the configuration file's keys are JoyAI-LLM-Flash's
``config.json``'s, the weights come from ``chipbench/weights_mla_lm.py``, the
plain reference is ``chipbench/reference/joyai_flash_ref.py``, ``correct``
compares the loss's two terms each beside their sum, and wants the flash
kernels through Mosaic and the gauge ``mtp.depth`` at 1.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models import joyai_flash  # a program without the model fails here, at once
from chipbench import arithmetic_mla_lm, correct, trace_hybrid_lm, trace_reduce, weights_mla_lm
from chipbench.reference import joyai_flash_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import (
    CHECK_STEPS, SETTLE_STEPS, SPAN_PREFIX, TRACE_SECONDS, CompileLog, Spans,
    epochs_of_batches, measure, peak_bytes, percentile, step_samples_ms,
)
from chipbench.runners.train_moe_lm import Recording, packed_rows  # noqa: F401  (packed_rows is this module's too)

LOSS_TERMS = ("main", "mtp")
#: the reference computed in a lower precision, or with a part of the mathematics left out, in the program's place
CONTROLS = ("bfloat16", "float8") + tuple(f for f in joyai_flash_ref.FAULTS if f)


@dataclasses.dataclass(frozen=True)
class Parts:
    """What one language-model runner kind brings to :func:`run_parts`."""

    facts_key: str                     # where the result's facts keep the routing counts, for the kind's readers
    top_k_key: str                     # the configuration file's key for the experts a token chooses
    build: Callable[[Dict[str, Any], int], Any]              # (config, world) -> (trainer, mesh)
    fresh_state: Callable[..., Any]                          # (trainer, mesh, config, seed) -> state
    recording: Callable[[Any], Recording]                    # trainer -> its recording step
    drive_first_steps: Callable[..., Any]                    # (recording, state, batches, config, seed)
    reference_numbers: Callable[..., Dict[str, Any]]         # (config, rows, seed)
    compare: Callable[..., List[Dict[str, Any]]]             # (program, reference, limits) -> rows
    check_program: Callable[[], None]                        # exits where the program is not the one to measure
    also_correct: Callable[[Callable[[str], None]], bool]    # what else ``correct`` wants, said as it is found
    record_window: Callable[[Recording, int], None]          # the program's own samples, after the traced window
    reduce_trace: Callable[..., None]                        # (trace, reduced, steps, say): more of the device trace


def model_config(config: Dict[str, Any]):
    """``JoyAIFlashConfig`` from the configuration file: the ``config.json``
    keys it states, the cut (layers here, experts held) and what it assumes."""
    program = config["assumed"]["program"]
    return joyai_flash.JoyAIFlashConfig.from_config(
        config, experts_held=int(config["num_experts_held"]), remat=program["remat"],
        dtype=jnp.dtype(program["activations"]), mtp_loss_weight=float(config["assumed"]["mtp_loss_weight"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_joyai_flash.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_joyai_flash import build_trainer

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

    params = weights_mla_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, joyai_flash.initial_model_state(model_config(config)))


class RecordingTerms(Recording):
    """``Recording`` that also keeps a copy of the loss's two terms a step."""

    def __init__(self, trainer) -> None:
        super().__init__(trainer)
        self.terms: List[Any] = []

    def step(self, state, batch):
        state, loss = super().step(state, batch)
        self.terms.append(jnp.stack([state.model_state[f"loss_{term}"] for term in LOSS_TERMS]))
        return state, loss

    def read_terms(self) -> np.ndarray:
        """``[steps, 2]``: ``L_main``, ``L_mtp``."""
        return np.asarray(jax.device_get(self.terms)).reshape(-1, len(LOSS_TERMS))


def drive_first_steps(recording: RecordingTerms, state, batches, config, seed: int):
    """The checked steps, through ``trainer.step`` on ``next(batches)``; the
    program's side of the comparison as ``train.drive_first_steps`` gives it,
    and each step's two loss terms as the step itself handed them out."""
    b1 = config["assumed"]["optimizer"]["b1"]
    rows, losses, grad_norms = [], [], None
    first = len(recording.terms)
    for i in range(CHECK_STEPS):
        batch = next(batches)
        rows.append(np.asarray(batch))
        state, loss = recording.step(state, batch)
        losses.append(float(jnp.mean(loss)))
        if i == 0:
            grad_norms = np.asarray(jax.jit(leaf_norms)(train._first_moment(state.opt_state))) / (1.0 - b1)
    moved = weights_mla_lm.moved_norms(state.params, seed, config)
    terms = recording.read_terms()[first:]
    program = {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}
    program.update({f"losses_{term}": terms[:, i].tolist() for i, term in enumerate(LOSS_TERMS)})
    return state, np.stack(rows), program


def reference_numbers(config, rows: np.ndarray, seed: int, control: str = "float32"):
    """The reference's side, on one device, from weights made anew by the
    seed; ``control`` one of :data:`CONTROLS` makes the reference that stands
    in the program's place."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_mla_lm.make_params(seed, config)  # noqa: E731
    precision, fault = ("float32", control) if control in joyai_flash_ref.FAULTS else (control, "")
    out = joyai_flash_ref.train_steps(make(), rows, config, opt, make, precision, fault)
    return {k: np.asarray(v) for k, v in out.items()}


def compare(program: Dict[str, Any], reference: Dict[str, Any], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """``correct.compare``'s rows, and each step's ``L_main`` and ``L_mtp``
    compared as the loss is, under the same limit."""
    rows = correct.compare(program, reference, limits)
    for term in LOSS_TERMS:
        swap = lambda side: {**side, "losses": side[f"losses_{term}"]}  # noqa: E731
        rows += [
            dict(r, name=r["name"].replace("loss_gap", f"loss_{term}_gap"))
            for r in correct.compare(swap(program), swap(reference), limits) if r["name"].startswith("loss_gap")
        ]
    return rows


def kernels_through_mosaic() -> None:
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    if decided.get("flash_attention") is not False:
        raise SystemExit(f"chipbench: the flash kernels did not run through Mosaic: {decided}")


def module_is_there(say) -> bool:
    from chipbench import program_registry

    depth = program_registry.gauge("mtp.depth")
    say(f"correct: gauge mtp.depth = {depth}  wanted 1")
    return depth == 1


def record_window(recording: RecordingTerms, before_window: int) -> None:
    """What the window's steps returned beside their loss, as the program's
    own samples (read after the steps, so that no step waits for the host)."""
    sizes, terms = recording.read()[before_window:], recording.read_terms()[before_window:]
    for step_sizes, (main, mtp) in zip(sizes, terms):
        joyai_flash.record_step({"moe_sizes": step_sizes, "loss_main": main, "loss_mtp": mtp})


def reduce_trace(trace, reduced: Dict[str, Any], steps: int, say) -> None:
    reduced["mla_kernel_s"] = trace_hybrid_lm.kernel_seconds(trace)
    say(f"trace: kernel seconds {reduced['mla_kernel_s']}")
    for name, seconds in trace_hybrid_lm.top_operations(trace, 40):
        say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")


PARTS = Parts(
    facts_key="mla_lm", top_k_key="num_experts_per_tok", build=build, fresh_state=fresh_state, recording=RecordingTerms, drive_first_steps=drive_first_steps,
    reference_numbers=reference_numbers, compare=compare, check_program=kernels_through_mosaic,
    also_correct=module_is_there, record_window=record_window, reduce_trace=reduce_trace,
)


def run(spec) -> Dict[str, Any]:
    return run_parts(spec, PARTS)


def run_parts(spec, parts: Parts) -> Dict[str, Any]:
    """A language-model training cell: set-up, the checked steps, the window,
    the reference after it, the facts for the readers."""
    config, mix, say = spec.config, spec.mix, spec.say
    world = int(spec.cell["chips"])
    seq_len, per_chip = arithmetic_mla_lm.row_tokens(mix), int(mix["batch_per_chip"])
    global_batch = per_chip * world
    compiles = CompileLog()
    spans = Spans(on=spec.trace)

    def stamp(what: str) -> None:
        say(f"set-up: {what} at {time.perf_counter() - spec.t0:.1f} s")

    stamp("imports and device")
    rows = packed_rows(mix, config["vocab_size"], spec.seed)
    stamp("corpus")
    trainer, mesh = parts.build(config, world)
    state = parts.fresh_state(trainer, mesh, config, spec.seed)
    jax.block_until_ready(state)
    stamp("trainer and state")
    batches = epochs_of_batches(rows, global_batch, mesh, spec.seed, int(mix.get("prefetch", 2)))
    recording = parts.recording(trainer)
    try:
        state, checked_rows, program = parts.drive_first_steps(recording, state, batches, config, spec.seed)
        stamp(f"first {CHECK_STEPS} steps and the program's side of the check")
        for _ in range(SETTLE_STEPS):
            state, loss = recording.step(state, next(batches))
        loss.block_until_ready()
        if spec.require_chip:
            parts.check_program()
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
            parts.record_window(recording, before_window)
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
    bound = global_batch * seq_len * min(int(config[parts.top_k_key]), int(config["num_experts_held"]))
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
    reference = parts.reference_numbers(config, checked_rows, spec.seed)
    rows_cmp = parts.compare(program, reference, config["limits"])
    correct.show(rows_cmp, say)
    say(f"correct: losses program {program['losses']} reference {reference['losses'].tolist()}")
    say(f"correct: non-finite losses in the window = {failed}  limit 0")
    say(f"correct: assignments of held experts dropped = {dropped}  limit 0")
    also = parts.also_correct(say)
    say(f"reference took {time.perf_counter() - t_ref:.1f} s (not in setup_s)")

    facts: Dict[str, Any] = {
        "config": config, "mix": mix, "world": world, "steps": steps,
        "window_s": window_s, "tokens_per_s": tokens_per_s,
        "window_compiles": window_compiles, "spans": dict(spans.seconds),
        "platform": jax.devices()[0].platform, "device_kind": jax.devices()[0].device_kind,
        "trace": None,
        parts.facts_key: {
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
            parts.reduce_trace(trace, reduced, steps, say)
            facts["trace"] = reduced
            device_extra.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        elif spec.require_chip:
            raise SystemExit("chipbench: no operation ran on a device in the traced window")

    return {
        "correct": bool(correct.verdict(rows_cmp) and failed == 0 and dropped == 0 and also),
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
