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
    """T=4,096 (a ring shard): past ``_STRAIGHT_LINE_ELEMENTS`` one body takes
    the traced ``program_id``, slices K/V by it and walks its unmasked tiles
    (seven at most) in written-out runs of four, two and one."""
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


# --- what cells 1-3 lower to stays what it was; the hybrid cell's kernels compile --


#: sha256 of the lowered text, made from ``git archive`` of 3f0513b (the parent of PR 32) by these same functions;
#: ``flash-cell3-full`` from PR 36's own tree (on bfb9cb0), which means to alter it: past ``_STRAIGHT_LINE_ELEMENTS`` the
#: one body with traced bounds runs written-out runs of tiles where it ran one tile a turn (the same tiles in the same
#: order: tests/test_flash_attention.py holds the two equal to the bit).  **The four ``flash-*`` digests are PR 47's own
#: tree's (on fd18a10), which means to alter them**: the forward writes lse and the dq kernel reads lse and delta as rows
#: ``[B.H, 1, T]``, turned to and from the kernels' columns once a program (the values moved, none recomputed: on the chip
#: o, lse, dq, dk, dv came out the parent's bit for bit at every cell's shape, CHANGES.md PR 47); ``experts-cell3`` is 3f0513b's.
#: **``flash-cell1`` and ``flash-cell2`` are PR 48's own tree's (on b372cff), which means to alter them and no other**:
#: equal heads of 64 are read in place (the kernels' operands are the model's ``[B, T, H.D]``, two heads to a 128-lane
#: block and a program, no ``[B, T, H, D] <-> [B.H, T, D]`` transpose in the text, delta summed by a product with the
#: heads' indicator); cell 3's grouped heads keep the ``[B.H, T, D]`` entry and its two digests stand as PR 47 left them
_PARENT_LOWERED = {
    "flash-cell1": "23738d96c5f34b27c351970c109cc682b4985fd496f403414f77146eea46f816",
    "flash-cell2": "4e33b31c5e103f95c2102423d00aa4229105db84afc0b7c869c043f94584dab5",
    "flash-cell3-window": "eb295fca2c402f9bfa7e63bd2e457c89acccc094d3a2f457a16e062e7ed33565",
    "flash-cell3-full": "5a8a18e94ecd769e4102257f4f52321d1f02e3c4f5dcd32a16b38c0380c1c018",
    "experts-cell3": "c13c04dad2a0fb4e8e8d6c336ea23dec01ad1e8bd5c1c4893c82a43f75e70bf7",
}

_FLASH_CELLS = {
    "flash-cell1": ((12, 1024, 12, 64), (12, 1024, 12, 64), None),      # gpt2-small-train
    "flash-cell2": ((2, 1024, 16, 64), (2, 1024, 16, 64), None),        # gpt2-medium-ddp4, a chip's rows
    "flash-cell3-window": ((1, 8192, 32, 128), (1, 8192, 4, 128), 2048),   # trinity-mini-ep8-train, sliding layers
    "flash-cell3-full": ((1, 8192, 32, 128), (1, 8192, 4, 128), None),     # and the full layer
}


#: q, k, v and the window of a cell's attention call: cell 1, cell 3's band (the ``pl.Element`` specs), cell 5's latent
#: layer (scores over 192, values over 128)
_STATISTIC_ROWS = {
    "cell1": (*_FLASH_CELLS["flash-cell1"][:2], *_FLASH_CELLS["flash-cell1"][1:]),
    "cell3-band": (*_FLASH_CELLS["flash-cell3-window"][:2], *_FLASH_CELLS["flash-cell3-window"][1:]),
    "cell5-192-128": ((1, 8192, 32, 192), (1, 8192, 32, 192), (1, 8192, 32, 128), None),
    # a band of one key: one body at the traced position for all sixteen blocks, each writing its own lanes of the row
    "band-of-one": (*_FLASH_CELLS["flash-cell3-window"][:2], _FLASH_CELLS["flash-cell3-window"][1], 1),
}


_COMPILED_ATTENTION = {}


def _compiled_attention(cell, one_chip) -> str:
    """The text of a cell's attention call, forward and backward, compiled for
    the described chip: once a cell, for every test of this file that reads it."""
    from adapcc_tpu.ops import flash_attention

    if cell not in _COMPILED_ATTENTION:
        q, k, v, window = _STATISTIC_ROWS[cell]

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, window=window, interpret=False).astype(jnp.float32))

        shapes = [jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip) for dims in (q, k, v)]
        _COMPILED_ATTENTION[cell] = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*shapes).compile().as_text()
    return _COMPILED_ATTENTION[cell]


@pytest.mark.parametrize("cell", sorted(_STATISTIC_ROWS))
def test_the_statistics_cross_the_compiled_kernels_as_rows_a_sublane_deep(one_chip, cell):
    """PR 47: lse and delta reach the three compiled kernels as
    ``f32[B.H, 1, T]`` tiled ``(1, 128)``, so the array in HBM is its content;
    the forward's is the very array both backward kernels read, and no
    ``f32[B.H, T, 8]`` (tiled to 128 lanes: 16 times its content) is left in
    the program for XLA to slice or broadcast."""
    q = _STATISTIC_ROWS[cell][0]
    text = _compiled_attention(cell, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    heads, T = q[0] * q[2], q[1]
    row = rf"f32\[{heads},1,{T}\]\{{2,1,0:T\(1,128\)"
    forward = re.search(rf"%(\S+) = \(bf16\[\S+, {row}\S*\) custom-call\(", text)
    assert forward, "the forward kernel writes no row"
    lse = re.search(rf"%(\S+) = {row}\S* get-tuple-element\(%{re.escape(forward.group(1))}\), index=1", text).group(1)
    backward = [line for line in text.splitlines() if "custom-call(" in line and "flash_bwd" in line]
    assert len(backward) == 2 and all(f"%{lse}," in line for line in backward), "a pass of XLA's stands between the kernels"
    assert not re.search(rf"f32\[{heads},{T},8\]", text)


def test_cell_1s_compiled_kernels_read_the_models_arrays_and_no_transpose_stands_around_them(one_chip):
    """PR 48: at equal heads of 64 the three compiled kernels return
    ``bf16[B, T, H.D]`` (the model's ``[B, T, H, D]``, two heads to a lane
    block) and the program holds no array of the ``[B, H, T, D]`` or
    ``[B.H, T, D]`` shape at all, so no ``copy`` or ``transpose`` of XLA's
    makes or unmakes one around them.  What is left re-tiles this bare call's
    own 4-D arguments and results, one pass each (a model's projections write
    and read ``[B, T, H.D]`` themselves: cell 1's layer compiles to none)."""
    text = _compiled_attention("cell1", one_chip)
    kernels = [line for line in text.splitlines() if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(kernels) == 3
    for line in kernels:
        assert set(re.findall(r"bf16\[([\d,]+)\]", line.split(" custom-call(", 1)[0])) == {"12,1024,768"}, line
    assert not re.search(r"\[12,12,1024,64\]|\[144,1024,64\]", text)
    moved = [line for line in text.splitlines() if re.match(r"\s*(?:ROOT )?%\S+ = \S+ (?:copy|transpose)\(", line)]
    assert len(moved) <= 6 and all(" = bf16[12,1024,768]{" in line for line in moved), moved


def _without_source_lines(lower):
    """``lower()`` with no Python frames in the MLIR locations: a Mosaic
    kernel's serialized body then holds no file path and no line number, and
    the text says what is computed and nothing of where it was written."""
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        return lower().as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


def _lowered_text(case, one_chip) -> str:
    """Forward and backward of the flash kernels at a cell's shape, or of
    cell 3's expert layer, as lowered for the described chip."""
    import functools

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    if case in _FLASH_CELLS:
        from adapcc_tpu.ops import flash_attention

        q_dims, kv_dims, window = _FLASH_CELLS[case]

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, window=window, interpret=False).astype(jnp.float32))

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        args = (shape(q_dims, jnp.bfloat16), shape(kv_dims, jnp.bfloat16), shape(kv_dims, jnp.bfloat16))
    else:
        from adapcc_tpu.models.moe import routed_experts

        n, k, experts, held, d, h = 8192, 8, 128, 16, 2048, 1024

        def loss(x, weights, stacked, ids):
            y, sizes = routed_experts(
                x, ids, weights, stacked, num_experts=experts, act=jax.nn.silu, dtype=jnp.bfloat16
            )
            return jnp.sum(y.astype(jnp.float32)), sizes

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
        stacked = {"w1": shape((held, d, h), jnp.float32), "w3": shape((held, d, h), jnp.float32),
                   "w2": shape((held, h, d), jnp.float32)}
        args = (shape((n, d), jnp.bfloat16), shape((n, k), jnp.float32), stacked, shape((n, k), jnp.int32))
    return _without_source_lines(functools.partial(step.lower, *args))


@pytest.mark.parametrize("case", sorted(_PARENT_LOWERED))
def test_cells_1_to_3_lower_to_the_text_they_lowered_to_before_the_hybrid_model(one_chip, case):
    """PR 32 gave the flash kernels a head size of its own for v and added a
    model beside Trinity's: at the three accepted cells' shapes the kernels'
    lowered text (Mosaic bodies included) and the expert layer's are the
    parent's, character for character, so those cells compute what they
    computed.  A change that means to alter them replaces the digest and says
    so; the digests are of text without source locations, so moving code
    inside a file does not."""
    import hashlib

    assert hashlib.sha256(_lowered_text(case, one_chip).encode()).hexdigest() == _PARENT_LOWERED[case]


#: sha256 of cell 4's latent layer as lowered by PR 36's own tree (on bfb9cb0), which means to alter it: the mixer's
#: text is ca7fb08's (the parent of PR 34, before the mixer learnt the query rank and the rotation), the three flash
#: kernels' bodies run written-out runs of tiles where they ran one tile a turn; replaced again by PR 47's own tree
#: (on fd18a10) for the statistics' rows: the mixer's text around the kernels is unmoved
_PARENT_LATENT_LAYER = "17c34e1b494ee77e491736f67a5e1a12811dfaa05e01b5e77c154bb56217cc46"


def test_cell_4s_latent_layer_lowers_to_the_text_it_lowered_to_before_the_rotation(one_chip, monkeypatch):
    """PR 34 gave the latent mixer a query rank and rotated channels and moved
    it beside the models that share it: ``kimi-linear-ep32-train``'s latent
    layer (no query rank, nothing rotated: ``mla_use_nope``) at the cell's
    shape, forward and backward with its projections, norm and the three
    flash kernels at 192 / 128, lowers to the text it lowered to, character
    for character (PR 36 changed the kernels' bodies and replaced the digest)."""
    import functools
    import hashlib

    from adapcc_tpu.models.kimi_linear import MLAMixer

    _flash_through_mosaic(monkeypatch)
    _, cfg = _kimi_cell()
    mixer = MLAMixer(cfg)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    params = _shapes_on(jax.eval_shape(mixer.init, jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size))), one_chip)
    step = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(mixer.apply(p, x).astype(jnp.float32)), argnums=(0, 1)))
    text = _without_source_lines(functools.partial(step.lower, params, x))
    assert text.count("tpu_custom_call") == 3 and "q_proj" in text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_LATENT_LAYER


def _kimi_cell():
    import json
    from pathlib import Path

    from adapcc_tpu.models.kimi_linear import KimiLinearConfig

    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/kimi-linear-ep32.json").read_text())
    program = config["assumed"]["program"]
    cfg = KimiLinearConfig.from_config(
        config, experts_held=config["num_experts_held"], remat=program["remat"], dtype=jnp.dtype(program["activations"]),
    )
    return config, cfg


def _wide_results(text: str, elements: int) -> "dict[str, str]":
    """The entry computation's instructions whose result holds ``elements``
    elements, by name, with their op: each is a pass over HBM (a fusion's
    body has no pass of its own: its result in the entry counts)."""
    entry = text[text.index("ENTRY"):]
    wide = {}
    for name, out, op in re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", entry, re.M):
        sizes = [int(np.prod([int(n) for n in dims.split(",")])) for dims in re.findall(r"\w+\[([\d,]+)\]", out)]
        if elements in sizes and op not in ("parameter", "get-tuple-element", "tuple", "bitcast"):
            wide[name] = op
    return wide


def _conv_through_mosaic(monkeypatch):
    import importlib

    monkeypatch.setattr(
        importlib.import_module("adapcc_tpu.ops.short_conv"), "resolve_interpret", lambda interpret, site: False
    )


def _conv_kernels(text: str, rows: int = 8192) -> "dict[str, int]":
    """How often the short convolution's two kernels stand in a compiled
    step, and that the parent's zero-padded float32 copy of their input (``K -
    1`` rows on top of the sequence's) is gone from it."""
    assert not re.search(rf"f32\[1,{rows + 3},\d+\]", text)
    return {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in ("short_conv_fwd", "short_conv_bwd")}


def _mixers_through_mosaic(monkeypatch):
    import importlib

    _flash_through_mosaic(monkeypatch)
    _conv_through_mosaic(monkeypatch)
    monkeypatch.setattr(importlib.import_module("adapcc_tpu.ops.kda"), "resolve_interpret", lambda interpret, site: False)


def test_the_kda_scan_and_the_latent_attention_compile_at_the_hybrid_cells_shapes(one_chip):
    """``kimi-linear-ep32-train``'s two mixers' kernels, forward and backward,
    through Mosaic: the chunked scan over 32 heads of 128 at T = 8,192 (two
    kernels over the model's ``[1, 8192, 4096]`` arrays, a head a block of 128
    lanes, two heads and 256 steps a grid step; the backward one holds eight chunks' summed decays, ``beta k``, inverses,
    writes and states in scratch), and the flash kernels with scores over 192 channels and
    values over 128 (three)."""
    from adapcc_tpu.ops import flash_attention
    from adapcc_tpu.ops.kda import kda

    _, cfg = _kimi_cell()
    T, H, D = 8192, cfg.linear_attn_num_heads, cfg.linear_attn_head_dim

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scan(q, k, v, g, beta):
        return jnp.sum(kda(q, k, v, g, beta, interpret=False).astype(jnp.float32))

    wide = shape((1, T, H * D))
    compiled = jax.jit(jax.value_and_grad(scan, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, shape((1, T, H * D), jnp.float32), shape((1, T, H), jnp.float32)
    ).compile()
    assert _kernels_in(compiled) == 2
    # five operands and seven: q, k, v, the decay, beta; + do and the saved states (chipbench/trace_hybrid_lm.kernel_of)
    flat, beta, states = r"bf16\[1,8192,4096\]\S*", r"f32\[1,8192,32\]\S*", r"f32\[32,32,128,128\]\S*"
    assert re.search(rf"%kda_fwd[\w.]* = \({flat}, {states}\) custom-call\(%[\w.\-]+(, %[\w.\-]+){{4}}\),", compiled.as_text())
    assert re.search(
        rf"%kda_bwd[\w.]* = \({flat}, {flat}, {flat}, f32\[1,8192,4096\]\S*, {beta}\) custom-call\(%[\w.\-]+(, (/\*index=5\*/)?%[\w.\-]+){{6}}\),",
        compiled.as_text(),
    )

    def latent(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, interpret=False).astype(jnp.float32))

    heads, dqk = cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    compiled = jax.jit(jax.value_and_grad(latent, argnums=(0, 1, 2))).lower(
        shape((1, T, heads, dqk)), shape((1, T, heads, dqk)), shape((1, T, heads, cfg.v_head_dim))
    ).compile()
    assert _kernels_in(compiled) == 3
    assert re.search(r"%flash_bwd_dkv[\w.]* = \(bf16\[32,8192,192\]\S*, bf16\[32,8192,128\]", compiled.as_text())
    # Each kernel walks a position's unmasked tiles (15 at most) four to a turn of its one loop, the four written out
    # (two products a tile forward, three in dq, four in dk/dv), then two and one under the count's bits: no loop
    # of one tile a turn is left at this shape, and 96 of a kernel's 136 tiles are reached from the loop.
    from adapcc_tpu.utils.observability import default_registry

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    traced = jax.make_jaxpr(jax.grad(latent, argnums=(0, 1, 2)))(
        shape((1, T, heads, dqk)), shape((1, T, heads, dqk)), shape((1, T, heads, cfg.v_head_dim))
    )
    products = {}
    for call in (e for e in equations(traced.jaxpr) if e.primitive.name == "pallas_call"):
        loops = [e for e in equations(call.params["jaxpr"]) if e.primitive.name == "while"]
        products[call.params["name"]] = [
            sum(e.primitive.name == "dot_general" for e in equations(loop.params["body_jaxpr"].jaxpr)) for loop in loops
        ]
    assert products == {"flash_fwd": [4 * 2], "flash_bwd_dq": [4 * 3], "flash_bwd_dkv": [4 * 4]}
    gauges = default_registry().snapshot()["gauges"]
    assert (gauges["flash.tiles_visited"], gauges["flash.tiles_looped"]) == (3 * 136, 3 * 96)


def test_the_kda_scan_is_its_two_kernels_and_no_pass_of_xlas_around_them(one_chip):
    """``value_and_grad`` of ``kda`` at the cell's shape, ``[1, 8192,
    4096]``: the program is ``kda_fwd``, ``kda_bwd`` and nothing else that
    walks 8,192 x 4,096 elements but what stands for the test's own sum (a
    reduction to a scalar, its cotangent's broadcast).  No ``transpose``,
    ``copy``, physical ``reshape``, ``reduce-window`` or any other fusion: XLA
    has not put the heads-major wrapper, the decay's cumulative sum or ``beta
    k`` back around the kernels, and the kernels' operands and results are the
    model's arrays themselves (a parameter goes in, a result comes out)."""
    from adapcc_tpu.ops.kda import kda

    _, cfg = _kimi_cell()
    T, H, D = 8192, cfg.linear_attn_num_heads, cfg.linear_attn_head_dim

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scan(q, k, v, g, beta):
        return jnp.sum(kda(q, k, v, g, beta, interpret=False).astype(jnp.float32))

    wide = shape((1, T, H * D))
    text = jax.jit(jax.value_and_grad(scan, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, shape((1, T, H * D), jnp.float32), shape((1, T, H), jnp.float32)
    ).compile().as_text()
    wide_results = _wide_results(text, T * H * D)
    kernels = {name: op for name, op in wide_results.items() if op == "custom-call"}
    assert sorted(name.split(".")[0] for name in kernels) == ["kda_bwd", "kda_fwd"], wide_results
    others = {name: op for name, op in wide_results.items() if name not in kernels}
    assert set(others.values()) <= {"broadcast"}, others            # the cotangent of the test's sum
    assert not re.search(r" (transpose|reduce-window|cumsum)\(", text)
    assert re.search(r"%kda_fwd[\w.]* = .* custom-call\(%q[\w.]*, %k[\w.]*, %v[\w.]*, %g[\w.]*, ", text[text.index("ENTRY"):])


@pytest.fixture(scope="module")
def hybrid_step(topo):
    """The whole donating step of ``kimi-linear-ep32-train`` (602 M float32
    parameters with AdamW's moments, one row of 8,192 tokens, the loss and
    remat the configuration file states) compiled for the described chip,
    once a module: ``(compiled, its text)``."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.models.trinity import initial_model_state
    from adapcc_tpu.workloads.train_kimi_linear import build_trainer

    with pytest.MonkeyPatch.context() as patch:
        _mixers_through_mosaic(patch)
        config, cfg = _kimi_cell()
        mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
        program = config["assumed"]["program"]
        trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
        assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 602_434_432
        state = jax.eval_shape(lambda p: TrainState.create(p, tx, model_state=initial_model_state(cfg)), params)
        tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
        compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    return compiled, compiled.as_text()


def test_the_hybrid_cells_step_fits_the_chip(hybrid_step):
    """The whole donating step of ``kimi-linear-ep32-train`` compiled for the
    described chip: state and temporaries leave 5% of its 16 GiB free, and the
    five kernels are in the program under their own names (the device trace
    is read by them: chipbench/trace_hybrid_lm.py)."""
    compiled, text = hybrid_step
    names = {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in (
        "kda_fwd", "kda_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    )}
    assert names == {"kda_fwd": 4, "kda_bwd": 4, "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert _conv_kernels(text) == {"short_conv_fwd": 12, "short_conv_bwd": 12}         # q, k and v of four KDA layers
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30
    # no more than with q's and k's norm as XLA's passes (1f4764e, the parent of PR 43: 14.3053 GiB; 14.2962 with it in the kernels)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes <= 15_360_188_416


def test_the_hybrid_cells_kda_layers_hold_no_head_matrix_product_but_o_norms(hybrid_step):
    """q's and k's ``l2norm`` rides ``short_conv``'s kernels (PR 43): in the
    cell's whole step, fused bodies included, no instruction whose ``op_name``
    lies in a KDA layer's ``self_attn/`` holds a product with the heads' 0/1
    matrix (``bth,ch->btc`` spreads a head's statistic over its channels,
    ``btc,ch->bth`` sums them: ``kimi_linear._head_spread`` /
    ``_head_sums``) but ``o_norm``'s own, which stand in all four layers
    forward and backward.  The parent held 38 such instructions a step outside
    ``o_norm``, thirty of them a pass over ``[8192, 4096]``."""
    _, text = hybrid_step
    inside, outside = 0, []
    for line in text.splitlines():
        where = re.search(r'op_name="([^"]*layers_(?:0|1|2|4)/self_attn/[^"]*(?:bth,ch->btc|btc,ch->bth)[^"]*)"', line)
        if where and "/self_attn/o_norm/" in where.group(1):
            inside += 1
        elif where:
            outside.append(line.strip()[:200])
    assert inside >= 16, inside             # the names still find the products: a sum and a spread each way in four layers
    assert not outside, outside


def test_the_hybrid_cells_kda_layers_keep_one_layout(hybrid_step):
    """``KDAMixer`` works on ``[B, T, H D]`` from its projections through the
    scan to ``o_proj``.  On a TPU ``[1, 8192, 32, 128]`` is another tiling of
    the same bytes, and each crossing between the two is a pass over HBM: in
    the cell's whole step no instruction's result has that shape but the
    latent layer's own (its ``k_nope`` and ``v`` are 32 heads of 128 for the
    flash kernels), and inside the four KDA mixers (by ``op_name`` scope) no
    ``copy``, ``transpose`` or physical ``reshape``, alone or as a fusion's
    root, results in 8,192 x 4,096 elements."""
    _, text = hybrid_step
    kinds = _kimi_cell()[1].kinds
    kda_layers, latent = [i for i, kind in enumerate(kinds) if kind == "kda"], kinds.index("mla")
    assert (kda_layers, latent) == ([0, 1, 2, 4], 3)
    scope = re.compile(rf"layers_({'|'.join(map(str, kda_layers))})/self_attn/")
    roots = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\n(?:[^\n]+\n)*?\s*ROOT %[\w.\-]+ = \S+ ([\w\-]+)\(", text, re.M))
    inside, moved, by_heads = 0, {}, {}
    for line in text[text.index("ENTRY"):].splitlines():
        found = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if not found:
            continue
        name, out, op = found.groups()
        name_of = re.search(r'op_name="([^"]*)"', line)
        where = name_of.group(1) if name_of else ""
        if re.search(r"\[(1,)?8192,32,128\]", out) and op != "bitcast" and f"layers_{latent}/self_attn/" not in where:
            by_heads[name] = (op, out, where)
        if not scope.search(where):
            continue
        inside += 1
        called = re.search(r"calls=%([\w.\-]+)", line)
        kind = roots.get(called.group(1), op) if op == "fusion" and called else op
        sizes = [int(np.prod([int(n) for n in dims.split(",")])) for dims in re.findall(r"\w+\[([\d,]+)\]", out)]
        if 8192 * 4096 in sizes and kind in ("copy", "transpose", "reshape"):
            moved[name] = (kind, out)
    assert inside > 100, inside            # the scope's name still finds the mixers' instructions
    assert not by_heads, by_heads
    assert not moved, moved


# --- the latent-attention cell: six rotated latent blocks, two heads, one step ---


def _joyai_cell():
    import json
    from pathlib import Path

    from chipbench.runners.train_mla_lm import model_config

    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/joyai-flash-ep16.json").read_text())
    return config, model_config(config)


def test_the_rotated_latent_layer_compiles_at_the_latent_cells_shape(one_chip, monkeypatch):
    """``joyai-flash-ep16-train``'s mixer, forward and backward at T = 8,192:
    the query's two projections with the norm between them, the rotation of
    64 of each head's 192 score channels and of the 64 shared key channels
    (XLA's: no kernel of its own), and the three flash kernels at 192 / 128
    through Mosaic under their own names."""
    from adapcc_tpu.models.kimi_linear import MLAMixer

    _flash_through_mosaic(monkeypatch)
    _, cfg = _joyai_cell()
    assert (cfg.q_lora_rank, cfg.mla_use_nope, cfg.rope_theta) == (1536, False, 32000000)
    mixer = MLAMixer(cfg)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16, sharding=one_chip)
    params = _shapes_on(jax.eval_shape(mixer.init, jax.random.PRNGKey(0), jnp.zeros((1, 64, cfg.hidden_size))), one_chip)
    assert params["params"]["q_b_proj"]["kernel"].shape == (1536, 32 * 192)
    step = jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(mixer.apply(p, x).astype(jnp.float32)), argnums=(0, 1)))
    compiled = step.lower(params, x).compile()
    text = compiled.as_text()
    assert _kernels_in(compiled) == 3 and "mla_rope" in text
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) == 1, name
    assert re.search(r"%flash_bwd_dkv[\w.]* = \(bf16\[32,8192,192\]\S*, bf16\[32,8192,128\]", text)


def test_the_latent_cells_step_fits_the_chip(topo, monkeypatch):
    """The whole donating step of ``joyai-flash-ep16-train`` (680.44 M float32
    parameters with AdamW's moments, one row of 8,192 tokens through five
    layers and the multi-token-prediction module, two heads and two losses,
    the loss and remat the configuration file states) compiled for the
    described chip: state and temporaries leave 5% of its 16 GiB free, and
    each block's three flash kernels are in the program under their own
    names (the device trace is read by them)."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.models.joyai_flash import initial_model_state
    from adapcc_tpu.workloads.train_joyai_flash import build_trainer

    _flash_through_mosaic(monkeypatch)
    config, cfg = _joyai_cell()
    mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
    program = config["assumed"]["program"]
    trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 680_441_088
    state = jax.eval_shape(lambda p: TrainState.create(p, tx, model_state=initial_model_state(cfg)), params)
    assert state.model_state["moe_sizes"].shape == (5, 16)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    text = compiled.as_text()
    names = {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    )}
    assert names == {"flash_fwd": 6, "flash_bwd_dq": 6, "flash_bwd_dkv": 6}
    for scope in ("mla_rope", "mtp_merge", "mtp_block", "mtp_head"):
        assert f"/{scope}/" in text, scope
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30


# --- the state-space cell: nine scans, one grouped-query layer at head 64, one step ---


def _granite_cell():
    import json
    from pathlib import Path

    from chipbench.runners.train_ssm_lm import model_config

    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/granite-h-micro-vp8.json").read_text())
    return config, model_config(config)


def _scan_through_mosaic(monkeypatch):
    import importlib

    _flash_through_mosaic(monkeypatch)
    _conv_through_mosaic(monkeypatch)
    monkeypatch.setattr(importlib.import_module("adapcc_tpu.ops.ssd"), "resolve_interpret", lambda interpret, site: False)


def test_the_state_space_scan_is_its_two_kernels_and_no_pass_of_xlas_around_them(one_chip):
    """``value_and_grad`` of ``ssd`` at ``granite-h-micro-vp8-train``'s shape
    (``x [1, 8192, 4096]``: 64 heads of 64 lanes, ``dt [1, 8192, 64]``, ``B``
    and ``C`` ``[1, 8192, 128]`` shared by the heads) through Mosaic: the
    program is ``ssd_fwd``, ``ssd_bwd`` and nothing else that walks 8,192 x
    4,096 elements but what stands for the test's own sum.  No ``transpose``,
    physical ``reshape``, ``reduce-window`` or cumulative sum: the heads are
    found by the block specs, the decay is summed in VMEM, and the kernels'
    operands and results are the model's arrays themselves.  The forward
    kernel takes six arrays and gives ``y`` and the saved states; the backward
    one eight, and gives the four arrays' gradients and a partial row a grid
    step for ``A`` and ``D``."""
    from adapcc_tpu.ops.ssd import ssd

    _, cfg = _granite_cell()
    T, H, P, N = 8192, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scan(x, dt, A, B, C, D):
        return jnp.sum(ssd(x, dt, A, B, C, D, interpret=False).astype(jnp.float32))

    args = (
        shape((1, T, H * P)), shape((1, T, H), jnp.float32), shape((H,), jnp.float32), shape((1, T, N)),
        shape((1, T, N)), shape((H,), jnp.float32),
    )
    compiled = jax.jit(jax.value_and_grad(scan, argnums=tuple(range(6)))).lower(*args).compile()
    assert _kernels_in(compiled) == 2
    text = compiled.as_text()
    wide, states = r"bf16\[1,8192,4096\]\S*", r"f32\[1,16,128,4096\]\S*"
    assert re.search(rf"%ssd_fwd[\w.]* = \({wide}, {states}\) custom-call\(%[\w.\-]+(, (/\*index=5\*/)?%[\w.\-]+){{5}}\),", text)
    assert re.search(
        rf"%ssd_bwd[\w.]* = \({wide}, f32\[1,8192,64\]\S*, bf16\[1,8192,128\]\S*, bf16\[1,8192,128\]\S*, "
        rf"f32\[1,16,1,64\]\S*, (/\*index=5\*/)?f32\[1,16,1,4096\]\S*\) custom-call\(%[\w.\-]+(, (/\*index=5\*/)?%[\w.\-]+){{7}}\),",
        text,
    )
    wide_results = _wide_results(text, T * H * P)
    kernels = {name: op for name, op in wide_results.items() if op == "custom-call"}
    assert sorted(name.split(".")[0] for name in kernels) == ["ssd_bwd", "ssd_fwd"], wide_results
    others = {name: op for name, op in wide_results.items() if name not in kernels}
    assert set(others.values()) <= {"broadcast"}, others            # the cotangent of the test's sum
    assert not re.search(r" (transpose|reduce-window|cumsum)\(", text)


def test_the_grouped_query_layer_compiles_at_head_64_over_8192_steps(one_chip):
    """The cell's one attention layer in ten: 32 query heads on 8 K/V heads
    at head size 64 and T = 8,192, scores scaled by ``attention_multiplier``:
    a pair ``TILE_TABLE`` had not met (head 64 ran at T = 1,024 with equal
    heads, grouped heads at T = 8,192 with heads of 128 and 192).  The row for
    head sizes up to 128 at T = 8,192 holds it: 512-tiles, three kernels."""
    from adapcc_tpu.ops import flash_attention
    from adapcc_tpu.ops.flash_attention import default_blocks

    _, cfg = _granite_cell()
    assert default_blocks(8192, cfg.head_dim, jnp.bfloat16) == (512, 512)

    def shape(dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def grouped(q, k, v):
        o = flash_attention(q, k, v, causal=True, scale=cfg.attention_multiplier, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    kv = shape((1, 8192, cfg.num_key_value_heads, cfg.head_dim))
    compiled = jax.jit(jax.value_and_grad(grouped, argnums=(0, 1, 2))).lower(
        shape((1, 8192, cfg.num_attention_heads, cfg.head_dim)), kv, kv
    ).compile()
    assert _kernels_in(compiled) == 3
    assert re.search(r"%flash_bwd_dkv[\w.]* = \(f32\[32,8192,64\]\S*, f32\[32,8192,64\]", compiled.as_text())


def test_the_state_space_cells_step_fits_the_chip(topo, monkeypatch):
    """The whole donating step of ``granite-h-micro-vp8-train`` (772.16 M
    float32 parameters with AdamW's moments, one row of 8,192 tokens through
    nine Mamba-2 layers and one attention layer, the tied head inside the
    chunked loss, the loss and remat the configuration file states) compiled
    for the described chip: state and temporaries leave 5% of its 16 GiB
    free, and the five kernels are in the program under their own names (the
    device trace is read by them: chipbench/runners/train_ssm_lm.kernel_of).
    Under ``dots`` what is no product is run again in the backward pass: the
    scan's and the attention's forward kernels appear twice a layer."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.models.granite_hybrid import initial_model_state
    from adapcc_tpu.workloads.train_granite_hybrid import build_trainer

    _scan_through_mosaic(monkeypatch)
    config, cfg = _granite_cell()
    mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
    program = config["assumed"]["program"]
    assert (program["loss"], program["remat"]) == ("chunked", "dots")
    trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 772_160_448
    state = jax.eval_shape(lambda p: TrainState.create(p, tx, model_state=initial_model_state()), params)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    text = compiled.as_text()
    names = {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in (
        "ssd_fwd", "ssd_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    )}
    assert names == {"ssd_fwd": 18, "ssd_bwd": 9, "flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert _conv_kernels(text) == {"short_conv_fwd": 18, "short_conv_bwd": 9}          # nine mixers, the forward again under dots
    for scope in ("ssd_conv", "ssd_gate", "ssd_scan", "gqa_attn"):
        assert f"/{scope}/" in text, scope
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30


# --- the SambaY cell: two selective scans, differential attention at 64 over 128, one step ---


def _phi4_cell():
    import json
    from pathlib import Path

    from chipbench.runners.train_sambay_lm import model_config

    config = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/phi4-mini-flash-vp8.json").read_text())
    return config, model_config(config)


def _selective_scan_through_mosaic(monkeypatch):
    import importlib

    _flash_through_mosaic(monkeypatch)
    _conv_through_mosaic(monkeypatch)
    monkeypatch.setattr(
        importlib.import_module("adapcc_tpu.ops.selective_scan"), "resolve_interpret", lambda interpret, site: False
    )


def test_the_selective_scan_is_its_two_kernels_on_the_models_own_arrays(one_chip):
    """``value_and_grad`` of ``selective_scan`` at ``phi4-mini-flash-vp8-train``'s
    shape (``x`` and ``dt`` ``[1, 8192, 5120]``, ``A [5120, 16]``, ``B`` and
    ``C`` ``[1, 8192, 16]``) through Mosaic: ``sscan_fwd`` takes the six
    arrays and gives ``y`` and the state each of the 32 blocks of rows starts
    from; ``sscan_bwd`` takes eight and gives the four arrays' gradients and a
    partial a grid step for ``A`` and ``D``.  What Mosaic refuses (a slice off
    the tiling, a layout it has none for, more scoped VMEM than the kernel
    asks) fails here."""
    from adapcc_tpu.ops.selective_scan import selective_scan

    _, cfg = _phi4_cell()
    T, C, N = 8192, cfg.d_inner, cfg.mamba_d_state

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def scan(x, dt, A, B, Cm, D):
        return jnp.sum(selective_scan(x, dt, A, B, Cm, D, interpret=False).astype(jnp.float32))

    args = (
        shape((1, T, C)), shape((1, T, C), jnp.float32), shape((C, N), jnp.float32), shape((1, T, N)),
        shape((1, T, N)), shape((C,), jnp.float32),
    )
    compiled = jax.jit(jax.value_and_grad(scan, argnums=tuple(range(6)))).lower(*args).compile()
    assert _kernels_in(compiled) == 2
    text = compiled.as_text()
    wide, states = r"bf16\[1,8192,5120\]\S*", r"f32\[1,32,16,5120\]\S*"
    assert re.search(rf"%sscan_fwd[\w.]* = \({wide}, {states}\) custom-call\(%[\w.\-]+(, (/\*index=5\*/)?%[\w.\-]+){{5}}\),", text)
    assert re.search(
        rf"%sscan_bwd[\w.]* = \({wide}, f32\[1,8192,5120\]\S*, bf16\[1,8192,16\]\S*, bf16\[1,8192,16\]\S*, "
        rf"{states}, (/\*index=5\*/)?f32\[1,32,1,5120\]\S*\) custom-call\(%[\w.\-]+(, (/\*index=5\*/)?%[\w.\-]+){{7}}\),",
        text,
    )
    assert not re.search(r" (reduce-window|cumsum)\(", text)


def test_the_sambay_cells_step_fits_the_chip(topo, monkeypatch):
    """The whole donating step of ``phi4-mini-flash-vp8-train`` (697,094,272
    float32 parameters with AdamW's moments, one row of 8,192 tokens through
    two selective scans, a window, a full and a cross differential-attention
    layer at scores of 64 over values of 128 on 10 K/V heads, a gated memory
    unit, the tied head inside the loss; the loss and remat the configuration
    file states) compiled for the described chip: state and temporaries leave
    5% of its 16 GiB free, and the five kernels are in the program under their
    own names (the device trace is read by them:
    chipbench/runners/train_sambay_lm.kernel_of).  Two flash calls a layer
    forward (one softmax of a pair each), and as many ``dq`` and ``dkv``."""
    import optax

    from adapcc_tpu.ddp.trainer import TrainState
    from adapcc_tpu.workloads.train_phi4_flash import build_trainer

    _selective_scan_through_mosaic(monkeypatch)
    config, cfg = _phi4_cell()
    mesh = Mesh(np.array(topo.devices[:1]), (RANKS_AXIS,))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-6, weight_decay=0.01))
    program = config["assumed"]["program"]
    trainer, model = build_trainer(cfg, tx, mesh, loss=program["loss"], donate_state=program["donate_state"])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) == 697_094_272
    state = jax.eval_shape(lambda p: TrainState.create(p, tx), params)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=NamedSharding(mesh, P(RANKS_AXIS)))
    compiled = trainer._build().lower(_shapes_on(state, NamedSharding(mesh, P())), tokens).compile()
    text = compiled.as_text()
    names = {name: len(re.findall(rf"^\s*%{name}[\w.]* = ", text, re.M)) for name in (
        "sscan_fwd", "sscan_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
    )}
    again = 2 if program["remat"] in ("dots", "full") else 1          # a recomputed block runs its forward kernels again
    assert names == {"sscan_fwd": 2 * again, "sscan_bwd": 2, "flash_fwd": 6 * again, "flash_bwd_dq": 6, "flash_bwd_dkv": 6}
    assert _conv_kernels(text) == {"short_conv_fwd": 2 * again, "short_conv_bwd": 2}
    for scope in ("sscan_conv", "sscan_gate", "sscan_scan", "gmu", "diff_attn", "diff_mix"):
        assert f"/{scope}/" in text, scope
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 0.95 * 16 * 2**30


# --- the short convolution in front of the three scans ----------------------------


#: sha256 of ``value_and_grad`` of the short convolution as lowered at 1f4764e (the parent of PR 43, which taught the
#: kernels q's and k's norm), text without source locations, Mosaic bodies included: cell 6's and cell 7's shapes and
#: cell 4's v, none of them normed
_PARENT_CONV_LOWERED = {
    4352: "e1f76e19a4396c6cf6d5a5c0f3c4ff48ba647393ab8bfd4d3484e9c718ba1ab5",
    4096: "14681f19e4cb33287b37c04a4eb5ad42854622c4d03ce101571cd4ebe3b19a5d",
    5120: "5e3933611675e9fd40cfc9fea7c9d391d0e6062dcb1c54e7aa60b9cf08bc201d",
}


@pytest.mark.parametrize(
    "channels, biased, heads", [(4352, True, None), (4096, False, None), (5120, True, None), (4096, False, 32)],
    ids=["granite-xBC", "kimi-v", "phi4-x", "kimi-qk-normed"],
)
def test_the_short_convolution_is_its_two_kernels_at_a_published_shape(one_chip, channels, biased, heads):
    """``value_and_grad`` of ``short_conv`` over ``[1, 8192, C]`` bfloat16 at
    the three mixers' widths through Mosaic: the program is ``short_conv_fwd``
    (``x`` and the taps' array in, ``y`` out) and ``short_conv_bwd`` (``x``
    twice, ``dy`` and the taps' array in; ``dx`` and the taps' array's
    gradient out) and nothing else that walks 8,192 x C elements but what
    stands for the test's own sum: no padded float32 copy, no pass for the
    bias, the silu or their derivative, and none for the norm over 32 heads
    of 128 that Kimi-Linear's q and k ride the kernels with (no statistic
    leaves the forward kernel: the same operands and results).  Two operands
    and four: neither of the counts ``chipbench/trace_reduce.flash_kernel``
    takes for a flash kernel.  Without a norm the lowered text is the
    parent's, character for character: cells 6 and 7 run what they ran."""
    import functools
    import hashlib

    from adapcc_tpu.ops.short_conv import short_conv

    T = 8192

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def conv(x, taps, *bias):
        return jnp.sum(short_conv(x, taps, *bias, interpret=False, norm_heads=heads).astype(jnp.float32))

    args = (shape((1, T, channels), jnp.bfloat16), shape((4, channels))) + ((shape((channels,)),) if biased else ())
    step = jax.jit(jax.value_and_grad(conv, argnums=tuple(range(len(args)))))
    if heads is None:
        lowered = _without_source_lines(functools.partial(step.lower, *args))
        assert hashlib.sha256(lowered.encode()).hexdigest() == _PARENT_CONV_LOWERED[channels]
    compiled = step.lower(*args).compile()
    assert _kernels_in(compiled) == 2
    text = compiled.as_text()
    assert _conv_kernels(text) == {"short_conv_fwd": 1, "short_conv_bwd": 1}
    wide = rf"bf16\[1,8192,{channels}\]\S*"
    assert re.search(rf"%short_conv_fwd[\w.]* = {wide} custom-call\(%[\w.\-]+, %[\w.\-]+\),", text)
    assert re.search(
        rf"%short_conv_bwd[\w.]* = \({wide}, f32\[1,8,{channels}\]\S*\) custom-call\(%[\w.\-]+(, %[\w.\-]+){{3}}\),", text
    )
    wide_results = _wide_results(text, T * channels)
    kernels = {name: op for name, op in wide_results.items() if op == "custom-call"}
    assert sorted(name.split(".")[0] for name in kernels) == ["short_conv_bwd", "short_conv_fwd"], wide_results
    others = {name: op for name, op in wide_results.items() if name not in kernels}
    assert set(others.values()) <= {"broadcast"}, others            # the cotangent of the test's sum


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
