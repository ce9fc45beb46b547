"""The state-space language-model training runner: ``adapcc_tpu``'s Granite
4.0-H block (``models/granite_hybrid.py``: Mamba-2 scans nine to one with
grouped-query attention without positions, a dense gated MLP in every layer,
scaled residuals, embedding and logits over a tied head) under
``DDPTrainer.step``, built the way
``adapcc_tpu/workloads/train_granite_hybrid.run`` builds it, fed by
``adapcc_tpu.data.device_batches``.

It is :class:`chipbench.runners.train_mla_lm.Parts` for another model, handed
to :func:`chipbench.runners.train_mla_lm.run_parts`: the same set-up, window,
corpus and facts as the other language-model cells.  Here: the configuration
file's keys are ``granitemoehybrid``'s ``config.json``'s, the weights come
from ``chipbench/weights_ssm_lm.py``, the plain reference is
``chipbench/reference/granite_hybrid_ref.py``, ``correct`` wants the scan's
and the flash kernels through Mosaic and the gauge ``ssd.chunk`` recorded,
what a step hands out beside its loss is the scans' decay floor, and the
trace is also reduced to the seconds of ``ssd_fwd`` / ``ssd_bwd`` and of the
three flash kernels, each told by its name.  The model has no experts: the window's record of the
routing counts is empty, and ``num_experts_held`` reads 0 for ``run_parts``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models import granite_hybrid  # a program without the model fails here, at once
from chipbench import correct, trace_reduce, weights_ssm_lm
from chipbench.arithmetic_ssm_lm import SSD_KERNELS
from chipbench.reference import granite_hybrid_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import CHECK_STEPS
from chipbench.runners.train_mla_lm import Parts, run_parts
from chipbench.runners.train_moe_lm import packed_rows  # noqa: F401  (packed_rows is this module's too)

KERNELS = SSD_KERNELS + trace_reduce.FLASH_KERNELS
#: the reference computed in a lower precision, or with a part of the mathematics changed, in the program's place
CONTROLS = ("bfloat16", "float8") + tuple(f for f in granite_hybrid_ref.FAULTS if f)


def model_config(config: Dict[str, Any]):
    """``GraniteHybridConfig`` from the configuration file: the ``config.json``
    keys it states, the cut (layers here) and what it assumes."""
    program = config["assumed"]["program"]
    return granite_hybrid.GraniteHybridConfig.from_config(
        config, remat=program["remat"], dtype=jnp.dtype(program["activations"])
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_granite_hybrid.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_granite_hybrid import build_trainer

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

    params = weights_ssm_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, granite_hybrid.initial_model_state())


class RecordingFloor:
    """``trainer.step`` that also keeps a copy of the decay floor each step
    handed out beside its loss (the state itself is donated to the next
    step), read after the window.  ``sizes`` and ``read`` are what
    ``run_parts`` asks of a recording: a model without experts has routed
    nothing, a step at a time."""

    def __init__(self, trainer) -> None:
        self.trainer = trainer
        self.sizes: List[Any] = []

    def step(self, state, batch):
        state, loss = self.trainer.step(state, batch)
        self.sizes.append(jnp.copy(state.model_state["ssd_decay_floor"]))
        return state, loss

    def read(self) -> np.ndarray:
        """``[steps, 1, 1]`` zeros: no expert layer, no assignment."""
        return np.zeros((len(self.sizes), 1, 1))

    def read_floors(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.sizes), np.float64).reshape(-1)


def drive_first_steps(recording: RecordingFloor, state, batches, config, seed: int):
    """The checked steps, through ``trainer.step`` on ``next(batches)``; the
    program's side of the comparison as ``train.drive_first_steps`` gives it."""
    b1 = config["assumed"]["optimizer"]["b1"]
    rows, losses, grad_norms = [], [], None
    for i in range(CHECK_STEPS):
        batch = next(batches)
        rows.append(np.asarray(batch))
        state, loss = recording.step(state, batch)
        losses.append(float(jnp.mean(loss)))
        if i == 0:
            grad_norms = np.asarray(jax.jit(leaf_norms)(train._first_moment(state.opt_state))) / (1.0 - b1)
    moved = weights_ssm_lm.moved_norms(state.params, seed, config)
    return state, np.stack(rows), {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}


def reference_numbers(config, rows: np.ndarray, seed: int, control: str = "float32"):
    """The reference's side, on one device, from weights made anew by the
    seed; ``control`` one of :data:`CONTROLS` makes the reference that stands
    in the program's place."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_ssm_lm.make_params(seed, config)  # noqa: E731
    precision, fault = ("float32", control) if control in granite_hybrid_ref.FAULTS else (control, "")
    out = granite_hybrid_ref.train_steps(make(), rows, config, opt, make, precision, fault)
    return {k: np.asarray(v) for k, v in out.items()}


def kernels_through_mosaic() -> None:
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    if decided.get("ssd") is not False or decided.get("flash_attention") is not False:
        raise SystemExit(f"chipbench: a mixer's kernel did not run through Mosaic: {decided}")


def scan_was_traced(say) -> bool:
    from chipbench import program_registry

    chunk = program_registry.gauge("ssd.chunk")
    say(f"correct: gauge ssd.chunk = {chunk}  wanted a chunk")
    return bool(chunk)


def record_window(recording: RecordingFloor, before_window: int) -> None:
    """What the window's steps returned beside their loss, as the program's
    own samples (read after the steps, so that no step waits for the host)."""
    for floor in recording.read_floors()[before_window:]:
        granite_hybrid.record_step({"ssd_decay_floor": floor})


def kernel_of(name: str) -> Optional[str]:
    """Which of the five kernels an operation is, by the name its HLO
    instruction carries (``%ssd_fwd.3``, ``%flash_bwd_dq.1``), or None.  By
    name alone: ``ssd_fwd`` takes six arrays and gives two, which is the
    signature ``trace_reduce.flash_kernel`` knows ``flash_bwd_dkv`` by."""
    if trace_reduce.MOSAIC not in name:
        return None
    op = trace_reduce.parse_op(name)
    return next((k for k in sorted(KERNELS, key=len, reverse=True) if op["name"].startswith(k)), None)


def _by_label(trace: Dict[str, Any], label) -> Dict[str, float]:
    """Summed device time over the traced window by ``label(operation)``
    (None: left out), mean over the chips that ran something."""
    ops = {d: evs for d, evs in trace_reduce.device_ops(trace).items() if evs}
    out: Dict[str, float] = {}
    labels: Dict[str, Optional[str]] = {}
    for evs in ops.values():
        for name, _, dur in evs:
            if name not in labels:
                labels[name] = label(name)
            if labels[name]:
                out[labels[name]] = out.get(labels[name], 0.0) + dur / 1e9 / len(ops)
    return out


def kernel_seconds(trace: Dict[str, Any]) -> Dict[str, float]:
    """Seconds of each of the five kernels."""
    return {**{k: 0.0 for k in KERNELS}, **_by_label(trace, kernel_of)}


def reduce_trace(trace, reduced: Dict[str, Any], steps: int, say) -> None:
    reduced["ssm_kernel_s"] = kernel_seconds(trace)
    say(f"trace: kernel seconds {reduced['ssm_kernel_s']}")
    by_name = _by_label(trace, lambda name: kernel_of(name) or trace_reduce.stable_name(name))
    for name, seconds in sorted(by_name.items(), key=lambda kv: -kv[1])[:40]:
        say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")


PARTS = Parts(
    facts_key="ssm_lm", top_k_key="num_experts_per_tok", build=build, fresh_state=fresh_state, recording=RecordingFloor,
    drive_first_steps=drive_first_steps, reference_numbers=reference_numbers, compare=correct.compare,
    check_program=kernels_through_mosaic, also_correct=scan_was_traced, record_window=record_window,
    reduce_trace=reduce_trace,
)


def run(spec) -> Dict[str, Any]:
    # no experts: ``run_parts`` reads how many are held, and none are
    return run_parts(dataclasses.replace(spec, config={**spec.config, "num_experts_held": 0}), PARTS)
