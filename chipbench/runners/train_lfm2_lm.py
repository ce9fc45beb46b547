"""The gated-convolution language-model training runner: ``adapcc_tpu``'s
LFM2-24B-A2B block (``models/lfm2_moe.py``: a double-gated short convolution
as the mixer of three layers in four, rotated grouped-query attention with
per-head q/k norms on the fourth, a dense gated MLP in the leading layer and
sigmoid top-4 experts with no shared one after it, a tied head) under
``DDPTrainer.step``, built the way ``adapcc_tpu/workloads/train_lfm2_moe.run``
builds it, fed by ``adapcc_tpu.data.device_batches``.

It is :class:`chipbench.runners.train_mla_lm.Parts` for another model, handed
to :func:`chipbench.runners.train_mla_lm.run_parts`: the same set-up, window,
corpus and facts as the other language-model cells.  Here: the configuration
file's keys are ``lfm2_moe``'s ``config.json``'s, the weights come from
``chipbench/weights_lfm2_lm.py``, the plain reference is
``chipbench/reference/lfm2_moe_ref.py``, what a step hands out beside its loss
is the routing counts (``train_moe_lm.Recording``), ``correct`` wants the
gated convolution's and the flash kernels through Mosaic and the gated
convolution traced once for each convolution layer, and the trace is also
reduced to the seconds of ``gated_conv_fwd`` / ``gated_conv_bwd`` and of the
three flash kernels, each told by its name, and to the expert layers'
operations with each event counted once (``chipbench/trace_lfm2_lm.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models import lfm2_moe  # a program without the model fails here, at once
from chipbench import correct, trace_lfm2_lm, trace_reduce, weights_lfm2_lm
from chipbench.arithmetic_lfm2_lm import GCONV_KERNELS, row_tokens
from chipbench.reference import lfm2_moe_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import CHECK_STEPS
from chipbench.runners.train_mla_lm import Parts, run_parts
from chipbench.runners.train_moe_lm import Recording, packed_rows  # noqa: F401  (packed_rows is this module's too)
from chipbench.runners.train_ssm_lm import _by_label

KERNELS = GCONV_KERNELS + trace_reduce.FLASH_KERNELS
#: the reference computed in a lower precision, or with a piece of the mathematics changed, in the program's place
CONTROLS = ("bfloat16", "float8") + tuple(f for f in lfm2_moe_ref.FAULTS if f)


def model_config(config: Dict[str, Any]):
    """``Lfm2MoeConfig`` from the configuration file: the ``config.json`` keys
    it states, the published depth and dense layers, the layers and experts
    held and what it assumes."""
    program, published = config["assumed"]["program"], config["published"]
    held = [int(l) for l in config["layers_held"]]
    dense_here = sum(l < int(published["num_dense_layers"]) for l in held)
    if len(held) != int(config["num_hidden_layers"]) or dense_here != int(config["num_dense_layers"]):
        raise SystemExit(
            f"chipbench: {config['num_hidden_layers']} layers of which {config['num_dense_layers']} dense stated, "
            f"{held} held of which {dense_here} dense"
        )
    return lfm2_moe.Lfm2MoeConfig.from_config(
        config, num_hidden_layers=int(published["num_hidden_layers"]),
        num_dense_layers=int(published["num_dense_layers"]), layers_held=held,
        head_dim=config["assumed"].get("head_dim"), tie_word_embeddings=bool(config["assumed"]["tie_word_embeddings"]),
        experts_held=int(config["num_experts_held"]), expert_offset=int(config.get("expert_offset", 0)),
        remat=program["remat"], dtype=jnp.dtype(program["activations"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_lfm2_moe.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_lfm2_moe import build_trainer

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

    params = weights_lfm2_lm.make_params(seed, config, NamedSharding(mesh, P()))
    return trainer.init_state(params, lfm2_moe.initial_model_state(model_config(config)))


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
    moved = weights_lfm2_lm.moved_norms(state.params, seed, config)
    return state, np.stack(rows), {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}


def reference_numbers(config, rows: np.ndarray, seed: int, control: str = "float32"):
    """The reference's side, on one device, from weights made anew by the
    seed; ``control`` one of :data:`CONTROLS` makes the reference that stands
    in the program's place."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_lfm2_lm.make_params(seed, config)  # noqa: E731
    precision, fault = ("float32", control) if control in lfm2_moe_ref.FAULTS else (control, "")
    out = lfm2_moe_ref.train_steps(make(), rows, config, opt, make, precision, fault)
    return {k: np.asarray(v) for k, v in out.items()}


def kernels_through_mosaic() -> None:
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    if decided.get("short_conv") is not False or decided.get("flash_attention") is not False:
        raise SystemExit(f"chipbench: a mixer's kernel did not run through Mosaic: {decided}")


def conv_layers(config: Dict[str, Any]) -> int:
    return weights_lfm2_lm.layer_kinds(config).count("conv")


def gated_calls() -> float:
    from chipbench import program_registry

    return float(program_registry._entry("counters", "conv.gated_calls") or 0.0)


def gated_calls_traced(config: Dict[str, Any], before: Dict[str, float]):
    """``also_correct`` for this configuration: since the runner built its
    trainer (``before``: the process's count then) the step's traces met the
    gated convolution once for each convolution layer a trace (the counter
    counts the call sites JAX traced: 4 a trace of the cell's step), on a
    block the plan chose."""

    def check(say) -> bool:
        from chipbench import program_registry

        calls, want = gated_calls() - before["calls"], conv_layers(config)
        rows = program_registry.gauge("gconv.block_rows")
        say(f"correct: counter conv.gated_calls = {calls}  wanted {want} a traced step; gauge gconv.block_rows = {rows}")
        return bool(rows) and calls > 0 and calls % want == 0

    return check


def record_window(recording: Recording, before_window: int) -> None:
    """What the window's steps returned beside their loss, as the program's
    own samples (read after the steps, so that no step waits for the host)."""
    from adapcc_tpu.models.moe import record_routing

    for sizes in recording.read()[before_window:]:
        record_routing(sizes)


def kernel_of(name: str) -> Optional[str]:
    """Which of the five kernels an operation is, by the name its HLO
    instruction carries (``%gated_conv_fwd.3``, ``%flash_bwd_dq.1``), or None.
    By name alone: no other kernel's operand signature is mistaken for one."""
    if trace_reduce.MOSAIC not in name:
        return None
    op = trace_reduce.parse_op(name)
    return next((k for k in sorted(KERNELS, key=len, reverse=True) if op["name"].startswith(k)), None)


def kernel_seconds(trace: Dict[str, Any]) -> Dict[str, float]:
    """Seconds of each of the five kernels."""
    return {**{k: 0.0 for k in KERNELS}, **_by_label(trace, kernel_of)}


def reduce_trace_for(config: Dict[str, Any], tokens: int):
    def reduce_trace(trace, reduced: Dict[str, Any], steps: int, say) -> None:
        reduced["lfm2_kernel_s"] = kernel_seconds(trace)
        reduced["lfm2_expert_s"] = trace_lfm2_lm.expert_seconds(trace, config, tokens)
        say(f"trace: kernel seconds {reduced['lfm2_kernel_s']}")
        say(f"trace: expert layers' seconds, each event once {reduced['lfm2_expert_s']}")
        by_name = _by_label(trace, lambda name: kernel_of(name) or trace_lfm2_lm.label(name))
        for name, seconds in sorted(by_name.items(), key=lambda kv: -kv[1])[:40]:
            say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")

    return reduce_trace


def parts_for(config: Dict[str, Any], tokens: int) -> Parts:
    before = {"calls": 0.0}

    def build_counted(config, world):
        before["calls"] = gated_calls()      # the registry is the process's: count this run's traces alone
        return build(config, world)

    return Parts(
        facts_key="lfm2_lm", top_k_key="num_experts_per_tok", build=build_counted, fresh_state=fresh_state,
        recording=Recording, drive_first_steps=drive_first_steps, reference_numbers=reference_numbers,
        compare=correct.compare, check_program=kernels_through_mosaic, also_correct=gated_calls_traced(config, before),
        record_window=record_window, reduce_trace=reduce_trace_for(config, tokens),
    )


def run(spec) -> Dict[str, Any]:
    tokens = int(spec.mix["batch_per_chip"]) * row_tokens(spec.mix)
    return run_parts(spec, parts_for(spec.config, tokens))
