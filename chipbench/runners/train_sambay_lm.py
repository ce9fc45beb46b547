"""The SambaY language-model training runner: ``adapcc_tpu``'s
Phi-4-mini-flash-reasoning decoder (``models/phi4_flash.py``: Mamba-1
selective scans alternating with differential attention, gated memory units
and cross-attention that read an earlier layer's tensors, LayerNorm with
bias, a tied head) under ``DDPTrainer.step``, built the way
``adapcc_tpu/workloads/train_phi4_flash.run`` builds it, fed by
``adapcc_tpu.data.device_batches``.

It is :class:`chipbench.runners.train_mla_lm.Parts` for another model, handed
to :func:`chipbench.runners.train_mla_lm.run_parts`: the same set-up, window,
corpus and facts as the other language-model cells.  Here: the configuration
file's keys are ``phi4flash``'s ``config.json``'s, the weights come from
``chipbench/weights_sambay_lm.py`` (the reference is handed them with the
attention projections' columns in the published order), the plain reference
is ``chipbench/reference/phi4_flash_ref.py``, ``correct`` wants the scan's and
the flash kernels through Mosaic and the gauge ``sscan.chunk`` recorded, the
comparison leaves out what bfloat16 cannot hold to the reference (:func:`compare`
says which two pieces, and why), and the trace is also reduced to the seconds
of ``sscan_fwd`` / ``sscan_bwd`` and of the three flash kernels, each told by
its name.  The model has no experts and a step hands out nothing beside its
loss: the window's record is empty, and ``num_experts_held`` reads 0 for
``run_parts``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models import phi4_flash  # a program without the model fails here, at once
from chipbench import correct, trace_reduce, weights_sambay_lm
from chipbench.arithmetic_sambay_lm import SSCAN_KERNELS
from chipbench.reference import phi4_flash_ref
from chipbench.reference.gpt2_ref import leaf_norms
from chipbench.runners import train
from chipbench.runners.train import CHECK_STEPS
from chipbench.runners.train_mla_lm import Parts, run_parts
from chipbench.runners.train_moe_lm import packed_rows  # noqa: F401  (packed_rows is this module's too)
from chipbench.runners.train_ssm_lm import _by_label

KERNELS = SSCAN_KERNELS + trace_reduce.FLASH_KERNELS
#: the reference computed in a lower precision, or with a piece of the mathematics changed, in the program's place
CONTROLS = ("bfloat16", "float8") + tuple(f for f in phi4_flash_ref.FAULTS if f)


def model_config(config: Dict[str, Any]):
    """``Phi4FlashConfig`` from the configuration file: the ``config.json``
    keys it states, the published depth, the layers held and what it assumes."""
    program, mamba = config["assumed"]["program"], config["assumed"]["mamba"]
    if len(config["layers_held"]) != int(config["num_hidden_layers"]):
        raise SystemExit(f"chipbench: {config['num_hidden_layers']} layers stated, {config['layers_held']} held")
    return phi4_flash.Phi4FlashConfig.from_config(
        config, num_hidden_layers=int(config["published"]["num_hidden_layers"]), layers_held=config["layers_held"],
        mamba_d_state=int(mamba["d_state"]), mamba_d_conv=int(mamba["d_conv"]), mamba_expand=int(mamba["expand"]),
        mamba_dt_rank=int(mamba["dt_rank"]), remat=program["remat"], dtype=jnp.dtype(program["activations"]),
    )


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_phi4_flash.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.workloads.train_phi4_flash import build_trainer

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

    return trainer.init_state(weights_sambay_lm.make_params(seed, config, NamedSharding(mesh, P())))


class NoRecording:
    """``trainer.step`` as it is: a step of this model hands out nothing
    beside its loss.  ``sizes`` and ``read`` are what ``run_parts`` asks of a
    recording: a model without experts has routed nothing, a step at a time."""

    def __init__(self, trainer) -> None:
        self.trainer = trainer
        self.sizes: List[None] = []

    def step(self, state, batch):
        self.sizes.append(None)
        return self.trainer.step(state, batch)

    def read(self) -> np.ndarray:
        """``[steps, 1, 1]`` zeros: no expert layer, no assignment."""
        return np.zeros((len(self.sizes), 1, 1))


def drive_first_steps(recording: NoRecording, state, batches, config, seed: int):
    """The checked steps, through ``trainer.step`` on ``next(batches)``; the
    program's side of the comparison as ``train.drive_first_steps`` gives it,
    the norms leaf by leaf with each key's bias a leaf of its own."""
    b1 = config["assumed"]["optimizer"]["b1"]
    apart = jax.jit(lambda tree: leaf_norms(weights_sambay_lm.key_bias_apart(tree, config)))
    rows, losses, grad_norms = [], [], None
    for i in range(CHECK_STEPS):
        batch = next(batches)
        rows.append(np.asarray(batch))
        state, loss = recording.step(state, batch)
        losses.append(float(jnp.mean(loss)))
        if i == 0:
            grad_norms = np.asarray(apart(train._first_moment(state.opt_state))) / (1.0 - b1)
    moved = weights_sambay_lm.moved_norms(state.params, seed, config)
    program = {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}
    return state, np.stack(rows), dict(program, lambda_leaves=lambda_leaves(config))


def reference_numbers(config, rows: np.ndarray, seed: int, control: str = "float32"):
    """The reference's side, on one device, from weights made anew by the
    seed and handed over in the published column order; ``control`` one of
    :data:`CONTROLS` makes the reference that stands in the program's place."""
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    make = lambda: weights_sambay_lm.published_order(weights_sambay_lm.make_params(seed, config), config)  # noqa: E731
    precision, fault = ("float32", control) if control in phi4_flash_ref.FAULTS else (control, "")
    out = phi4_flash_ref.train_steps(make(), rows, config, opt, make, precision, fault)
    return {k: np.asarray(v) for k, v in out.items()}


def leaf_names(config) -> List[str]:
    """The compared leaves by name, in the order of both sides' norms."""
    from chipbench.weights import _is_leaf

    table = weights_sambay_lm.leaf_table(config)
    names = [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(table, is_leaf=_is_leaf)]
    return [part for name in names for part in (
        [f"{name}[{i}]" for i in range(3)] if "['qkv_proj']['bias']" in name else [name]
    )]


def lambda_leaves(config) -> List[int]:
    """Where the attention layers' ``lambda`` vectors stand among the leaves."""
    return [i for i, name in enumerate(leaf_names(config)) if "lambda_" in name]


#: a leaf whose first gradient in the reference is under this share of the median leaf's has none but rounding: the
#: key's biases read 1.0e-7 to 1.6e-7 of it at the cell's size, the smallest leaf with a gradient 4.2e-3 (PERF.md, 2)
NOUGHT = 1e-4


def compare(program: Dict[str, Any], reference: Dict[str, Any], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    """``correct.compare``'s rows, less two pieces that bfloat16 cannot hold
    to a float32 reference, each left out by a rule and not by a wider limit.

    **``update_norm_gap`` leaves out a leaf whose first gradient in the
    reference is nought to rounding** (under :data:`NOUGHT` of the median
    leaf's): each key's bias, which the softmax is blind to.  AdamW divides a
    gradient by its own size, so it steps such a leaf by the sign of rounding
    noise, at the full learning rate under bfloat16 and by a part of it in
    float32 (the noise there is of ``eps``'s size): neither side's change says
    anything of the other's.  The program's *gradient* there stays under
    ``grad_norm_gap``, against the median leaf's: it has to be as good as none.

    **Neither norm's row reads the attention layers' ``lambda`` vectors.**  A
    layer's four vectors take their whole gradient from one scalar, ``dL / d
    lambda = -sum(G * a2)`` over every position, pair and channel, a sum whose
    terms all but cancel; the flash kernels hand ``a1`` and ``a2`` over in
    bfloat16, and that rounding, ahead of the sum, costs the scalar 1-44% of
    itself where every other leaf's norm moves by 0.1-0.4% (docs/PHI4_FLASH.md
    has the experiment: with the outputs kept in float32 the error falls to
    the bfloat16 reference's).  A leaf's norm averages nothing out of one
    scalar, so no limit stands between the sound runs and a fault there, and
    where the scalar is smaller than its error AdamW steps the vectors by the
    error's sign.  Their gradient is held in float32 at a small size
    (tests/test_phi4_flash.py), what ``lambda`` does to the loss and to every
    other leaf's gradient by the other rows (``no_lambda`` fails three), and
    ``chipbench/readings_sambay_lm.py`` prints what the vectors read."""
    want = np.asarray(reference["grad_norms"], np.float64)
    held = np.ones(len(want), bool)
    held[program["lambda_leaves"]] = False
    with_gradient = held & (want >= NOUGHT * np.median(want))
    rows = lambda side: {  # noqa: E731
        **side, "grad_norms": np.asarray(side["grad_norms"], np.float64)[held],
        "update_norms": np.asarray(side["update_norms"], np.float64)[with_gradient],
    }
    return correct.compare(rows(program), rows(reference), limits)


def kernels_through_mosaic() -> None:
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    if decided.get("selective_scan") is not False or decided.get("flash_attention") is not False:
        raise SystemExit(f"chipbench: a mixer's kernel did not run through Mosaic: {decided}")


def scan_was_traced(say) -> bool:
    from chipbench import program_registry

    chunk = program_registry.gauge("sscan.chunk")
    say(f"correct: gauge sscan.chunk = {chunk}  wanted a chunk")
    return bool(chunk)


def kernel_of(name: str) -> Optional[str]:
    """Which of the five kernels an operation is, by the name its HLO
    instruction carries (``%sscan_fwd.3``, ``%flash_bwd_dq.1``), or None.  By
    name alone: no other kernel's operand signature is mistaken for one."""
    if trace_reduce.MOSAIC not in name:
        return None
    op = trace_reduce.parse_op(name)
    return next((k for k in sorted(KERNELS, key=len, reverse=True) if op["name"].startswith(k)), None)


def kernel_seconds(trace: Dict[str, Any]) -> Dict[str, float]:
    """Seconds of each of the five kernels."""
    return {**{k: 0.0 for k in KERNELS}, **_by_label(trace, kernel_of)}


def reduce_trace(trace, reduced: Dict[str, Any], steps: int, say) -> None:
    reduced["sambay_kernel_s"] = kernel_seconds(trace)
    say(f"trace: kernel seconds {reduced['sambay_kernel_s']}")
    by_name = _by_label(trace, lambda name: kernel_of(name) or trace_reduce.stable_name(name))
    for name, seconds in sorted(by_name.items(), key=lambda kv: -kv[1])[:40]:
        say(f"trace: {seconds / steps * 1e3:8.3f} ms a step  {name}")


PARTS = Parts(
    facts_key="sambay_lm", top_k_key="num_experts_per_tok", build=build, fresh_state=fresh_state,
    recording=NoRecording, drive_first_steps=drive_first_steps, reference_numbers=reference_numbers,
    compare=compare, check_program=kernels_through_mosaic, also_correct=scan_was_traced,
    record_window=lambda recording, before_window: None, reduce_trace=reduce_trace,
)


def run(spec) -> Dict[str, Any]:
    # no experts: ``run_parts`` reads how many are held and how many a token chooses, and none are
    config = {**spec.config, "num_experts_held": 0, "num_experts_per_tok": 0}
    return run_parts(dataclasses.replace(spec, config=config), PARTS)
