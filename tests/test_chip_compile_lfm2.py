"""LFM2-24B-A2B's cell, asked of the chip's own compiler: the gated short
convolution's two kernels at the cell's shape and the cell's whole donating
step, compiled for a described ``v5e:2x2`` (``on-chip-measurement`` guide §2,
third rehearsal).  A file of its own, so that ``--dist loadfile`` gives it a
worker of its own (``tests/test_chip_compile.py`` already holds one for ten
minutes); the fixtures that describe the topology and the helpers are that
file's.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from tests.test_chip_compile import (  # noqa: F401  (topo and one_chip are this module's fixtures too)
    _conv_through_mosaic, _flash_through_mosaic, _kernels_in, _shapes_on, _wide_results, one_chip, topo,
)

GATED = ("gated_conv_fwd", "gated_conv_bwd")


def _named(text: str, names) -> "dict[str, int]":
    return {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in names}


def test_the_gated_convolution_is_its_two_kernels_on_the_projections_own_array(one_chip):
    """``value_and_grad`` of ``gated_short_conv`` over ``[1, 8192, 6144]``
    bfloat16 (``B | C | x`` as ``in_proj`` writes them) through Mosaic: the
    program is ``gated_conv_fwd`` (the one array three times and the taps'
    array in, ``y [1, 8192, 2048]`` out) and ``gated_conv_bwd`` (the array
    five times, ``dy`` and the taps' array in; the three gradients as ONE
    ``[1, 8192, 6144]`` array and the taps' array's gradient out) and nothing
    else that walks the thirds: no slice, no copy, no concatenation, no
    float32 pass.  Four operands and seven: neither of the counts
    ``chipbench/trace_reduce.flash_kernel`` takes for a flash kernel."""
    from adapcc_tpu.ops.short_conv import gated_short_conv

    T, C = 8192, 2048

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def conv(bcx, taps):
        return jnp.sum(gated_short_conv(bcx, taps, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(conv, argnums=(0, 1))).lower(shape((1, T, 3 * C), jnp.bfloat16), shape((3, C))).compile()
    assert _kernels_in(compiled) == 2
    text = compiled.as_text()
    assert _named(text, GATED) == {"gated_conv_fwd": 1, "gated_conv_bwd": 1}
    operand = r"(/\*index=5\*/)?%[\w.\-]+"
    assert re.search(rf"%gated_conv_fwd[\w.]* = bf16\[1,8192,2048\]\S* custom-call\({operand}(, {operand}){{3}}\),", text)
    assert re.search(
        rf"%gated_conv_bwd[\w.]* = \(bf16\[1,8192,6144\]\S*, f32\[1,8,2048\]\S*\) custom-call\({operand}(, {operand}){{6}}\),", text
    )
    for elements in (T * C, T * 3 * C):
        wide = _wide_results(text, elements)
        others = {name: op for name, op in wide.items() if op != "custom-call"}
        assert set(others.values()) <= {"broadcast"}, others            # the cotangent of the test's sum


def test_the_lfm2_cells_step_fits_the_chip(topo, monkeypatch):
    """The whole donating step of ``lfm2-24b-a2b-ep4-train`` (788,052,352
    float32 parameters with AdamW's moments, one row of 8,192 tokens through
    four gated convolutions, a rotated grouped-query layer at head 64 on 8 K/V
    heads, an 11,776-wide MLP, four expert layers of 16 held of 64 under
    top-4, the tied head inside the loss; the loss and remat the configuration
    file states) compiled for the described chip: state and temporaries leave
    5% of its 16 GiB free, the five kernels are in the program under their own
    names (the device trace is read by them:
    chipbench/runners/train_lfm2_lm.kernel_of) and the five scopes in its
    operations' names."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.workloads.train_lfm2_moe import build_trainer
    from chipbench.runners.train_lfm2_lm import model_config

    _flash_through_mosaic(monkeypatch)
    _conv_through_mosaic(monkeypatch)
    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/lfm2-24b-a2b-ep4.json").read_text())
    cfg = model_config(config)
    mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
    program = config["assumed"]["program"]
    trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 788_052_352
    from adapcc_tpu.models.lfm2_moe import initial_model_state

    state = jax.eval_shape(lambda p: TrainState.create(p, tx, model_state=initial_model_state(cfg)), params)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    text = compiled.as_text()
    again = 2 if program["remat"] in ("dots", "full") else 1          # a recomputed block runs its forward kernels again
    assert _named(text, GATED + ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")) == {
        "gated_conv_fwd": 4 * again, "gated_conv_bwd": 4, "flash_fwd": again, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
    }
    for scope in ("gconv_proj", "gconv", "lfm2_attn", "moe_route", "moe_experts"):
        assert f"/{scope}/" in text, scope
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30
