"""The kernels of the main path, asked of the chip's own compiler.

The TPU compiler is installed in the sandbox and compiles for a chip that is
described, not attached (``on-chip-measurement`` guide §2, third rehearsal):
every case here lowers a Pallas kernel with ``interpret=False`` at the shape
``chip_smoke.py`` runs it, compiles it for a described ``v5e:2x2``, and
asserts the Mosaic kernel is in the program.  What interpret mode cannot see
— a rank-1 value Mosaic has no layout for (the fused int8 ring aborted the
compiler process), a plan over the scoped-VMEM limit (the 32 MiB bf16 ring)
— fails here, at no chip time.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU library, so a call at import time would
give the workers of a parallel run different tests to collect.  Every case
compiles in this process (no child), and all of them live in this one file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.comm.pallas_ring import (
    plan_ring_schedule,
    ring_all_gather_shard,
    ring_allreduce_shard,
    ring_reduce_scatter_shard,
)

K, M = 1024, 1024 * 1024
WORLD = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile can be written to the persistent cache but never read
    # back without a chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the library from loading
        jax.config.update("jax_enable_compilation_cache", True)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:WORLD]), (RANKS_AXIS,))


def _kernels_in(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _compile_on_mesh(mesh, per_shard, shape, dtype):
    """Compile ``per_shard`` (a ``[1, ...]`` shard → ``[1, ...]``) under
    shard_map over the described 4-chip mesh."""
    fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=P(RANKS_AXIS), out_specs=P(RANKS_AXIS),
        check_vma=False,
    ))
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    return fn.lower(x).compile()


def _shapes_on(tree, sharding):
    """``tree``'s leaves as ``ShapeDtypeStruct``s placed by ``sharding``."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


def _flash_through_mosaic(monkeypatch):
    """Callers that leave the kernel/interpreter choice to ``kernel_mode``
    would get this process's CPU answer: steer it here, in the test."""
    import sys

    monkeypatch.setattr(
        sys.modules["adapcc_tpu.ops.flash_attention"], "resolve_interpret",
        lambda interpret, site: False,
    )


# --- flash attention at the smoke's shape: B × 1,024 × 12 heads × 64 --------


def _flash_step(shape, dtype, one_chip, block):
    from adapcc_tpu.ops import flash_attention

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block, interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()


@pytest.mark.parametrize("block", [None, 128], ids=["by-shape", "128"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_flash_fwd_bwd_compiles_at_gpt2_small_shape(one_chip, dtype, block):
    """The table's tile and the old 128: at T=1,024 every grid position has
    its own straight-line body (bf16 operands reach Mosaic transposed too)."""
    assert _kernels_in(_flash_step((4, 1024, 12, 64), dtype, one_chip, block)) == 3  # forward, dq, dk/dv


def test_flash_fwd_bwd_compiles_at_a_long_shard(one_chip):
    """T=4,096 (a ring shard): past ``_STRAIGHT_LINE_ELEMENTS`` the bodies
    loop with bounds from the traced ``program_id`` and slice K/V by it."""
    assert _kernels_in(_flash_step((1, 4096, 12, 64), jnp.bfloat16, one_chip, None)) == 3


# --- the ICI ring at world 4, both paths ------------------------------------

_RING_CASES = [
    # (id, elements per rank, payload dtype, fused wire codec, expected path)
    ("fp32-vmem", 64 * K, jnp.float32, "off", "vmem"),
    ("fp32-stream", 16 * M, jnp.float32, "off", "hbm-stream"),
    ("bf16-vmem", 64 * K, jnp.bfloat16, "off", "vmem"),
    # 32 MiB of bf16 per rank needs 17.83M of scoped VMEM against the 16M
    # default: compiles only because the plan states its own limit
    ("bf16-stream-32MiB", 16 * M, jnp.bfloat16, "off", "hbm-stream"),
    ("fused-bf16-vmem", 64 * K, jnp.float32, "bf16", "vmem"),
    ("fused-bf16-stream", 16 * M, jnp.float32, "bf16", "hbm-stream"),
    # int8: a rank-1 scale vector anywhere in the kernel aborts the compiler
    ("fused-int8-vmem", 64 * K, jnp.float32, "int8", "vmem"),
    ("fused-int8-stream", 16 * M, jnp.float32, "int8", "hbm-stream"),
]


@pytest.mark.parametrize(
    "nelems,dtype,wire,path",
    [c[1:] for c in _RING_CASES], ids=[c[0] for c in _RING_CASES],
)
def test_ring_allreduce_compiles(mesh4, nelems, dtype, wire, path):
    plan = plan_ring_schedule(nelems, dtype, WORLD, wire_dtype=wire)
    assert plan.path == path

    def per_shard(x):
        return ring_allreduce_shard(
            x[0], WORLD, RANKS_AXIS, interpret=False, wire_dtype=wire
        )[None]

    assert _kernels_in(_compile_on_mesh(mesh4, per_shard, (WORLD, nelems), dtype)) == 1


@pytest.mark.parametrize("nelems", [64 * K, 16 * M], ids=["vmem", "stream"])
def test_ring_reduce_scatter_compiles(mesh4, nelems):
    def per_shard(x):
        return ring_reduce_scatter_shard(x[0], WORLD, RANKS_AXIS, interpret=False)[None]

    assert _kernels_in(_compile_on_mesh(mesh4, per_shard, (WORLD, nelems), jnp.float32)) == 1


@pytest.mark.parametrize("nelems", [16 * K, 4 * M], ids=["vmem", "stream"])
def test_ring_all_gather_compiles(mesh4, nelems):
    def per_shard(x):
        return ring_all_gather_shard(x[0], WORLD, RANKS_AXIS, interpret=False)[None]

    assert _kernels_in(_compile_on_mesh(mesh4, per_shard, (WORLD, nelems), jnp.float32)) == 1


# --- the training loss over GPT-2 small's logits: 12 x 1,023 x 50,257 --------

_LOGITS = r"\[(?:12,1023|12276),50257\]"


def _computations(text):
    """``{name: its instruction lines}`` of every computation of a compiled module."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    return bodies


def _entry_fusions(text):
    """``(name, kind, output shapes, operand shapes, fused body)`` of every
    fusion in the compiled module's entry computation."""
    bodies = _computations(text)
    entry = bodies[re.search(r"^ENTRY (%[\w.\-]+)", text, re.M).group(1)]
    shapes = {}
    for line in entry:
        inst = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*)", line)
        if not inst:
            continue
        out, opcode, rest = inst.group(2), inst.group(3), inst.group(4)
        shapes[inst.group(1)] = out
        if opcode == "fusion":
            operands = re.findall(r"%[\w.\-]+", rest.split("), kind=")[0])
            yield (
                inst.group(1), re.search(r"kind=(\w+)", rest).group(1), out,
                [shapes.get(o, "") for o in operands],
                "\n".join(bodies[re.search(r"calls=(%[\w.\-]+)", rest).group(1)]),
            )


def test_the_loss_sweeps_the_logits_once_forward_and_once_backward(one_chip, monkeypatch):
    """``lm_loss`` as the chip's compiler sees it behind GPT-2 small's head:
    the textbook form (``log_softmax`` then ``take_along_axis``) writes the
    whole fp32 log-softmax (2.47 GB) for a gather to read 12,276 numbers of,
    and sums the gather's one-hot cotangent over the vocabulary to a
    constant: three sweeps and 5.49 GB of temporaries where this asks for
    two and under 3.3."""
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

    _flash_through_mosaic(monkeypatch)
    model = GPT2(GPT2Config(n_layer=2, attention="flash"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    tokens = jax.ShapeDtypeStruct((12, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        jax.value_and_grad(lambda p, b: lm_loss(model.apply(p, b), b))
    ).lower(_shapes_on(params, one_chip), tokens).compile()

    fusions = list(_entry_fusions(compiled.as_text()))
    assert len(fusions) > 20 and any(re.search("bf16" + _LOGITS, out) for _, _, out, _, _ in fusions)
    # nothing vocabulary-sized is written in fp32
    assert not [name for name, _, out, _, _ in fusions if re.search("f32" + _LOGITS, out)]
    # no pass builds a vocabulary-sized one-hot from an iota alone and reduces it
    assert not [
        name for name, _, _, operands, body in fusions
        if re.search(_LOGITS + r"\S* iota\(", body) and not any(re.search(_LOGITS, o) for o in operands)
    ]
    # elementwise passes over the logits: the forward's two reductions in one, the gradient
    sweeps = [name for name, kind, _, _, body in fusions if kind == "kLoop" and re.search(_LOGITS, body)]
    assert len(sweeps) <= 2, sweeps
    assert compiled.memory_analysis().temp_size_in_bytes < 3.3e9


# --- the expert layer at Trinity-Mini's share: 8,192 tokens, 16 of 128 experts --


def test_the_expert_layer_writes_short_rows_when_the_assignments_fit_them(one_chip):
    """``routed_experts`` forward and backward at cell 3's shapes (8,192
    tokens of 2,048, top-8, 16 of 128 experts of 1,024, bf16): the layer told
    how many experts there are chooses twice, and the branches it takes when
    the assignments fit 16,384 rows write nothing with the bound's 65,536
    rows: not the sorted rows, nor a grouped product's input or output, nor
    an elementwise pass over them.  What stays is the gather back to
    ``[tokens x top_k, D]``, once forward and once backward: it reads a row
    for every assignment whatever the rows are sized by (ROADMAP S9).  And
    the two paths together ask for no more temporaries than the bound's path
    alone (the branches share them)."""
    from adapcc_tpu.models.moe import assignment_bound, routed_experts, short_rows

    n, k, experts, held, d, h = 8192, 8, 128, 16, 2048, 1024
    assert (short_rows(n, k, held, experts), assignment_bound(n, k, held)) == (16384, 65536)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    stacked = {"w1": shape((held, d, h), jnp.float32), "w3": shape((held, d, h), jnp.float32),
               "w2": shape((held, h, d), jnp.float32)}

    def compiled(num_experts):
        def loss(x, weights, stacked, ids, mix):
            y, _ = routed_experts(x, ids, weights, stacked, num_experts=num_experts, act=jax.nn.silu)
            return jnp.sum(y * mix)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            shape((n, d), jnp.bfloat16), shape((n, k), jnp.float32), stacked, shape((n, k), jnp.int32),
            shape((n, d), jnp.float32),
        ).compile()

    one_path, two_paths = compiled(None), compiled(experts)
    assert " conditional(" not in one_path.as_text()
    bodies = _computations(two_paths.as_text())
    chosen = [
        re.search(r"branch_computations=\{([^}]*)\}", line).group(1).split(", ")
        for lines in bodies.values() for line in lines if " conditional(" in line
    ]
    assert len(chosen) == 2 and all(len(branches) == 2 for branches in chosen)   # forward, backward
    for bound_branch, short_branch in chosen:          # index 0: the predicate is false
        wide = lambda lines: [  # noqa: E731
            line for line in lines
            if re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \(?(?:bf16|f32)\[65536,(?:1024|2048)\]", line)
        ]
        assert len(wide(bodies[bound_branch])) >= 4
        left = wide(bodies[short_branch])
        assert len(left) <= 1 and all("/gather\"" in line and "bf16[65536,2048]" in line for line in left), left
        assert any(re.search(r"= bf16\[16384,2048\]", line) for line in bodies[short_branch])
    temporaries = lambda c: c.memory_analysis().temp_size_in_bytes  # noqa: E731
    assert temporaries(two_paths) <= temporaries(one_path)


# --- the composed programs the old on-chip smoke covered ---------------------


def test_flash_ring_attention_block_compiles(mesh4, monkeypatch):
    """Sequence-parallel ring attention with the flash block kernel: K/V
    rotate over the ring, every hop runs the Pallas kernel."""
    from adapcc_tpu.parallel.ring_attention import ring_attention_shard

    _flash_through_mosaic(monkeypatch)

    def per_shard(qkv):  # [1, 3, B, T_local, H, D]
        q, k, v = qkv[0, 0], qkv[0, 1], qkv[0, 2]
        return ring_attention_shard(
            q, k, v, axis_name=RANKS_AXIS, causal=True, scale=0.125,
            block_impl="flash", block_q=128, block_k=128,
        )[None]

    compiled = _compile_on_mesh(mesh4, per_shard, (WORLD, 3, 2, 256, 12, 64), jnp.bfloat16)
    assert _kernels_in(compiled) >= 1


def test_zero1_ring_step_compiles(mesh4):
    """The ZeRO-1 step on the Pallas ring data plane: reduce-scatter the
    gradients, shard-local adam, all-gather the params — two ring kernels."""
    import optax

    from adapcc_tpu.parallel.fsdp import Zero1Optimizer, zero1_train_step

    params = {"w1": jnp.zeros((256, 1024)), "w2": jnp.zeros((1024, 256))}

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)

    opt = Zero1Optimizer(optax.adam(1e-3), mesh4, ring=True, ring_interpret=False)
    step = zero1_train_step(loss_fn, opt, mesh4)
    replicated, sharded = NamedSharding(mesh4, P()), NamedSharding(mesh4, P(RANKS_AXIS))

    master, opt_state = jax.eval_shape(opt.init, params)
    batch = (
        jax.ShapeDtypeStruct((8 * WORLD, 256), jnp.float32, sharding=sharded),
        jax.ShapeDtypeStruct((8 * WORLD, 256), jnp.float32, sharding=sharded),
    )
    compiled = jax.jit(step).lower(
        _shapes_on(params, replicated), _shapes_on(master, sharded), _shapes_on(opt_state, sharded), batch
    ).compile()
    assert _kernels_in(compiled) >= 2
