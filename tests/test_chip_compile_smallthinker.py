"""SmallThinker-21BA3B-Instruct's cell, asked of the chip's own compiler: the
cell's whole donating step compiled for a described ``v5e:2x2``
(``on-chip-measurement`` guide §2, third rehearsal).  No new kernel came with
the model: what is new is the flash kernels' shape (a group of seven query
heads to a K/V head, a window of 4,096 in 8,192) and the expert layer's
numbers, and they are in this one program.  A file of its own, so that ``--dist
loadfile`` gives it a worker of its own (``tests/test_chip_compile.py`` already
holds one for ten minutes); the fixtures that describe the topology and the
helpers are that file's.  A compile that passes is not a chip run.
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
from tests.test_chip_compile import _flash_through_mosaic, _shapes_on, topo  # noqa: F401  (topo is this module's fixture too)

FLASH = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def test_the_smallthinker_cells_step_fits_the_chip(topo, monkeypatch):
    """The whole donating step of ``smallthinker-21b-a3b-ep4-train``
    (656,529,920 float32 parameters with AdamW's moments, one row of 8,192
    tokens through a global and three window-4,096 grouped-query layers at 28
    query heads on 4 K/V heads of 128, four expert layers of 16 held of 64
    under top-6 routed from the layer's input, the untied 37,984-wide head;
    the loss and remat the configuration file states) compiled for the
    described chip: state and temporaries leave 5% of its 16 GiB free, the
    three flash kernels are in the program under their own names, once a layer
    (the device trace is read by them:
    chipbench/trace_smallthinker_lm.kernel_of) at a group of seven (q and o at
    28 heads, k and v at 4: an index map, nothing repeated in HBM), and the five
    scopes in its operations' names."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.models.smallthinker import initial_model_state
    from adapcc_tpu.workloads.train_smallthinker import build_trainer
    from chipbench.runners.train_smallthinker_lm import model_config

    _flash_through_mosaic(monkeypatch)
    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/smallthinker-21b-a3b-ep4.json").read_text())
    cfg = model_config(config)
    mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
    program = config["assumed"]["program"]
    trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 656_529_920
    state = jax.eval_shape(lambda p: TrainState.create(p, tx, model_state=initial_model_state(cfg)), params)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    text = compiled.as_text()
    again = 2 if program["remat"] in ("dots", "full") else 1          # a recomputed block runs its forward kernel again
    named = {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in FLASH}
    assert named == {"flash_fwd": 4 * again, "flash_bwd_dq": 4, "flash_bwd_dkv": 4}
    for scope in ("moe_route", "attn_full", "attn_window", "moe_experts", "lm_head" if program["loss"] == "dense" else "loss"):
        assert f"/{scope}" in text, scope
    assert re.search(r"%flash_fwd[\w.]* = \(bf16\[28,8192,128\]", text) and "bf16[4,8192,128]" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30
