"""The early-router language-model training runner: ``adapcc_tpu``'s
SmallThinker-21BA3B-Instruct block (``models/smallthinker.py``: a router that
reads the layer's input before attention, a softmax over the chosen top-6 of
64 ReGLU experts with no shared one, window-4,096 rotated and global
position-free grouped-query attention at 28 query heads on 4 K/V heads, an
untied head) under ``DDPTrainer.step``, built the way
``adapcc_tpu/workloads/train_smallthinker.run`` builds it, fed by
``adapcc_tpu.data.device_batches``.

It is :class:`chipbench.runners.train_mla_lm.Parts` for another model, handed
to :func:`chipbench.runners.train_mla_lm.run_parts`: the same set-up, window,
corpus and facts as the other language-model cells.  Here: the configuration
file's keys are SmallThinker's ``config.json``'s, the weights come from
``chipbench/weights_smallthinker_lm.py``, the plain reference is
``chipbench/reference/smallthinker_ref.py``, what a step hands out beside its
loss is the routing counts (``train_moe_lm.Recording``), ``correct`` wants the
flash kernels through Mosaic and the early router traced once for each layer,
and the trace is also reduced to the seconds of the three flash kernels
(``trace_hybrid_lm.kernel_seconds``), to the expert layers' operations with
each event counted once, and to the routing's own operations
(``chipbench/trace_smallthinker_lm.py``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models import smallthinker  # a program without the model fails here, at once
from chipbench import correct, trace_hybrid_lm, trace_reduce, trace_smallthinker_lm, weights_smallthinker_lm
from chipbench.arithmetic_smallthinker_lm import row_tokens
from chipbench.reference import smallthinker_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import CHECK_STEPS
from chipbench.runners.train_lfm2_lm import record_window
from chipbench.runners.train_mla_lm import Parts, kernels_through_mosaic, run_parts
from chipbench.runners.train_moe_lm import Recording, packed_rows  # noqa: F401  (packed_rows is this module's too)
from chipbench.runners.train_ssm_lm import _by_label

ROUTE_COUNTER = "smallthinker.early_route_calls"
#: the reference computed in a lower precision, or with a piece of the mathematics changed, in the program's place
CONTROLS = ("bfloat16", "float8") + tuple(f for f in smallthinker_ref.FAULTS if f)


def model_config(config: Dict[str, Any]):
    """``SmallThinkerConfig`` from the configuration file: the ``config.json``
    keys it states, the published depth, the layers and experts held and what
    it assumes."""
    program = config["assumed"]["program"]
    held = [int(l) for l in config["layers_held"]]
    if len(held) != int(config["num_hidden_layers"]):
        raise SystemExit(f"chipbench: {config['num_hidden_layers']} layers stated, {held} held")
    return smallthinker.SmallThinkerConfig.from_config(
        config, num_hidden_layers=int(config["published"]["num_hidden_layers"]), layers_held=held,
        experts_held=int(config["num_experts_held"]), expert_offset=int(config.get("expert_offset", 0)),
        remat=program["remat"], dtype=jnp.dtype(program["activations"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_smallthinker.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_smallthinker import build_trainer

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

    params = weights_smallthinker_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, smallthinker.initial_model_state(model_config(config)))


def drive_first_steps(recording: Recording, state, batches, config, seed: int):
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
    moved = weights_smallthinker_lm.moved_norms(state.params, seed, config)
    return state, np.stack(rows), {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}


def reference_numbers(config, rows: np.ndarray, seed: int, control: str = "float32"):
    """The reference's side, on one device, from weights made anew by the
    seed; ``control`` one of :data:`CONTROLS` makes the reference that stands
    in the program's place."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_smallthinker_lm.make_params(seed, config)  # noqa: E731
    precision, fault = ("float32", control) if control in smallthinker_ref.FAULTS else (control, "")
    out = smallthinker_ref.train_steps(make(), rows, config, opt, make, precision, fault)
    return {k: np.asarray(v) for k, v in out.items()}


def route_calls() -> float:
    from chipbench import program_registry

    return float(program_registry._entry("counters", ROUTE_COUNTER) or 0.0)


def early_router_traced(config: Dict[str, Any], before: Dict[str, float]):
    """``also_correct`` for this configuration: since the runner built its
    trainer (``before``: the process's count then) the step's traces met the
    early router once for each layer a trace (the counter counts the call
    sites JAX traced: 4 a trace of the cell's step)."""

    def check(say) -> bool:
        calls, want = route_calls() - before["calls"], len(config["layers_held"])
        say(f"correct: counter {ROUTE_COUNTER} = {calls}  wanted {want} a traced step")
        return calls > 0 and calls % want == 0

    return check


def reduce_trace_for(config: Dict[str, Any], tokens: int):
    def reduce_trace(trace, reduced: Dict[str, Any], steps: int, say) -> None:
        kernels = trace_hybrid_lm.kernel_seconds(trace)
        parts = trace_smallthinker_lm.part_seconds(trace, config, tokens)
        reduced["smallthinker_kernel_s"] = {k: kernels[k] for k in trace_reduce.FLASH_KERNELS}
        reduced["smallthinker_expert_s"] = {part: parts[part] for part in ("grouped_products", "rows")}
        reduced["smallthinker_route_s"] = {"route": parts["route"]}
        say(f"trace: kernel seconds {reduced['smallthinker_kernel_s']}")
        say(f"trace: expert layers' seconds, each event once {reduced['smallthinker_expert_s']}")
        say(f"trace: routing's seconds, each event once {reduced['smallthinker_route_s']}")
        label = trace_smallthinker_lm.labeller(config, tokens)
        for name, seconds in sorted(_by_label(trace, label).items(), key=lambda kv: -kv[1])[:40]:
            say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")

    return reduce_trace


def parts_for(config: Dict[str, Any], tokens: int) -> Parts:
    before = {"calls": 0.0}

    def build_counted(config, world):
        before["calls"] = route_calls()      # the registry is the process's: count this run's traces alone
        return build(config, world)

    return Parts(
        facts_key="smallthinker_lm", top_k_key="moe_num_active_primary_experts", build=build_counted, fresh_state=fresh_state,
        recording=Recording, drive_first_steps=drive_first_steps, reference_numbers=reference_numbers,
        compare=correct.compare, check_program=kernels_through_mosaic, also_correct=early_router_traced(config, before),
        record_window=record_window, reduce_trace=reduce_trace_for(config, tokens),
    )


def run(spec) -> Dict[str, Any]:
    tokens = int(spec.mix["batch_per_chip"]) * row_tokens(spec.mix)
    return run_parts(spec, parts_for(spec.config, tokens))
