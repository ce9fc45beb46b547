"""Fused wire-codec streaming ring (docs/RING.md §5).

The bit contract under test: the fused kernels (codec inside the VMEM
staging tiles, scales on a side channel, AG forwarding bits verbatim) are
bit-identical to the unfused ``quant/ring.py`` ppermute ring wherever the
two chunk layouts coincide, bit-identical rank to rank everywhere, and
within ``ring_error_bound`` of fp32.

Coverage strategy mirrors tests/test_pallas_ring.py: the planner, support
funnel, codec helpers, pricing, sweep, tuner-grid, and engine-reroute
tests need no kernel; the kernel executions run under the Mosaic TPU
interpret mode.
The always-on section additionally validates the fused *algorithm* —
per-hop requantize, encode-once, scale forwarding — with a pure-numpy
ring simulation pinned bit-for-bit against the unfused data plane, so a
build that cannot run Pallas still regression-tests the schedule the
kernels implement.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS, build_world_mesh
from adapcc_tpu.comm.pallas_ring import (
    FUSED_WIRE_ENV,
    _decode,
    _derive_scales,
    _encode,
    _pack_scale_row,
    _scale_rows,
    _tile_elems,
    _unpack_scale_col,
    fused_ring_dispatch_reason,
    fused_wire_unsupported_reason,
    plan_ring_schedule,
    resolve_fused_wire,
)
from adapcc_tpu.quant import (
    DEFAULT_BLOCK_SIZE,
    dequantize_int8,
    get_codec,
    quantize_int8,
    ring_error_bound,
    wire_ring_allreduce_shard,
)

_TILE = _tile_elems(jnp.float32)  # 1024 elems: the fp32 (8, 128) tile

@pytest.fixture(scope="module")
def mesh4():
    return build_world_mesh(4)


def run_shard(fn, mesh, *args):
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=P(RANKS_AXIS), out_specs=P(RANKS_AXIS),
            check_vma=False,
        )
    )(*args)


# --------------------------------------------------------------------------- #
# planner: wire-aware geometry + scale-slot VMEM accounting
# --------------------------------------------------------------------------- #

def test_plan_int8_vmem_bound_grows_by_exactly_the_scale_bytes():
    """The acceptance pin: on int8 plans ``vmem_bound_bytes`` grows by
    exactly the scale side-channel bytes, on BOTH paths."""
    for nelems, chunk in ((8 * _TILE, 1 << 30), (256 * _TILE, 4096)):
        plan = plan_ring_schedule(
            nelems, jnp.float32, 4, chunk, wire_dtype="int8"
        )
        bare = dataclasses.replace(plan, scale_slot_bytes=0)
        assert plan.scale_slot_bytes > 0
        assert plan.scale_bytes > 0
        assert plan.vmem_bound_bytes == bare.vmem_bound_bytes + plan.scale_bytes
    assert plan.path == "hbm-stream"  # the loop covered both paths


def test_plan_fused_wire_geometry():
    plan = plan_ring_schedule(
        64 * _TILE * 4, jnp.float32, 4, 4096, wire_dtype="int8"
    )
    assert plan.path == "hbm-stream" and plan.wire_dtype == "int8"
    stage_elems = plan.stage_bytes // 4
    # int8 codes: 1 byte/elem on the wire tile
    assert plan.wire_stage_bytes == stage_elems
    # one fp32 scale per block, padded to a whole (8, 128) fp32 tile
    n_blocks = stage_elems // DEFAULT_BLOCK_SIZE
    assert plan.scale_slot_bytes == _scale_rows(n_blocks) * 128 * 4
    assert plan.block_size == DEFAULT_BLOCK_SIZE
    # bf16 is a pure cast: half the bytes, no scales
    bf16 = plan_ring_schedule(
        64 * _TILE * 4, jnp.float32, 4, 4096, wire_dtype="bf16"
    )
    assert bf16.wire_stage_bytes == bf16.stage_bytes // 2
    # bf16 allocates NO scale buffers (the wrappers skip the side channel
    # entirely), so zero scale accounting is exact, not an approximation
    assert bf16.scale_slot_bytes == 0 and bf16.scale_bytes == 0
    assert bf16.vmem_bound_bytes == (
        2 * bf16.stage_bytes + 3 * bf16.wire_stage_bytes
    )


def test_plan_off_unchanged_and_to_row_carries_wire():
    plan = plan_ring_schedule(64 * _TILE * 4, jnp.float32, 4, 4096)
    assert plan.wire_dtype == "off" and plan.scale_slot_bytes == 0
    assert plan.vmem_bound_bytes == 4 * plan.stage_bytes  # legacy formula
    row = plan_ring_schedule(
        64 * _TILE * 4, jnp.float32, 4, 4096, wire_dtype="int8"
    ).to_row()
    assert row["wire_dtype"] == "int8" and row["scale_slot_bytes"] > 0


def test_plan_rejects_unsupported_fused_combinations():
    with pytest.raises(ValueError, match="float32"):
        plan_ring_schedule(4096, jnp.bfloat16, 4, wire_dtype="int8")
    with pytest.raises(ValueError, match="block_size"):
        plan_ring_schedule(4096, jnp.float32, 4, wire_dtype="int8",
                           block_size=192)
    with pytest.raises(ValueError, match="no fused kernel"):
        plan_ring_schedule(4096, jnp.float32, 4, wire_dtype="fp8")


# --------------------------------------------------------------------------- #
# support funnel + env gate
# --------------------------------------------------------------------------- #

def test_fused_wire_unsupported_reason_matrix():
    assert fused_wire_unsupported_reason("float32", "int8") is None
    assert fused_wire_unsupported_reason("float32", "bf16") is None
    for block in (128, 256, 512, 1024):
        assert fused_wire_unsupported_reason("float32", "int8", block) is None
    for block in (64, 192, 2048):
        assert "block_size" in fused_wire_unsupported_reason(
            "float32", "int8", block
        )
    assert "off" in fused_wire_unsupported_reason("float32", "off")
    assert "float32" in fused_wire_unsupported_reason("bfloat16", "int8")


def test_fused_wire_env_gate(monkeypatch):
    monkeypatch.delenv(FUSED_WIRE_ENV, raising=False)
    assert resolve_fused_wire() == "auto"
    monkeypatch.setenv(FUSED_WIRE_ENV, "off")
    assert resolve_fused_wire() == "off"
    assert "pins the unfused path" in fused_ring_dispatch_reason(
        "float32", "int8"
    )
    monkeypatch.setenv(FUSED_WIRE_ENV, "o n")
    with pytest.raises(ValueError, match="ADAPCC_FUSED_WIRE"):
        resolve_fused_wire()
    # =on demands the fused kernel: any blocker becomes a loud error
    monkeypatch.setenv(FUSED_WIRE_ENV, "on")
    with pytest.raises(ValueError, match="ADAPCC_FUSED_WIRE=on"):
        fused_ring_dispatch_reason("bfloat16", "int8")


def test_dispatch_reason_matches_build_support(monkeypatch):
    monkeypatch.delenv(FUSED_WIRE_ENV, raising=False)
    assert fused_ring_dispatch_reason("float32", "int8") is None


# --------------------------------------------------------------------------- #
# in-kernel codec helpers: bitwise parity with quant/codec.py
# --------------------------------------------------------------------------- #

def _codec_roundtrip(flat: np.ndarray, wire_dtype: str, block: int = DEFAULT_BLOCK_SIZE):
    """Drive the kernels' ref-level codec helpers on one tile under the
    interpreter: ``(wire, scale tile, decoded, wire re-encoded from the
    decoded values against the same scales)``."""
    from jax.experimental import pallas as pl

    int8 = wire_dtype == "int8"
    width = block if int8 else 128
    x = jnp.asarray(flat, jnp.float32).reshape(-1, width)
    n_rows = x.shape[0]
    wire_jnp = jnp.int8 if int8 else jnp.bfloat16

    def kernel(x_ref, wire_ref, scale_ref, back_ref, again_ref):
        if int8:
            _derive_scales(x_ref, None, scale_ref, n_rows)
        _encode(x_ref, None, scale_ref, wire_ref, n_rows, int8)
        _decode(wire_ref, None, scale_ref, None, back_ref, None,
                n_rows, int8, accumulate=False)
        _encode(back_ref, None, scale_ref, again_ref, n_rows, int8)

    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, wire_jnp),
            jax.ShapeDtypeStruct((_scale_rows(n_rows), 128), jnp.float32),
            jax.ShapeDtypeStruct(x.shape, jnp.float32),
            jax.ShapeDtypeStruct(x.shape, wire_jnp),
        ),
        interpret=True,
    )(x)


def test_fused_encode_matches_quantize_int8_bitwise():
    """Tile-wise in-kernel encode == flat quantize_int8, bit for bit —
    blocks nest in tiles, so the fused wire can never drift from the
    registry codec."""
    rng = np.random.default_rng(0)
    flat = rng.normal(size=(4 * _TILE,)).astype(np.float32) * 37.0
    q_tile, scale_tile, back, _ = _codec_roundtrip(flat, "int8")
    q_ref, s_ref = quantize_int8(jnp.asarray(flat), DEFAULT_BLOCK_SIZE)
    np.testing.assert_array_equal(
        np.asarray(q_tile).reshape(-1), np.asarray(q_ref).reshape(-1)
    )
    np.testing.assert_array_equal(
        np.asarray(scale_tile).reshape(-1)[: s_ref.shape[0]], np.asarray(s_ref)
    )
    # decode parity too
    ref = dequantize_int8(q_ref, s_ref)
    np.testing.assert_array_equal(
        np.asarray(back).reshape(-1), np.asarray(ref)
    )


def test_fused_requantize_is_exact_on_decoded_values():
    """The AG forwarding claim: re-deriving codes of DECODED values against
    the original scales reproduces the codes exactly (|q| <= 127), so only
    the scales need the side channel."""
    rng = np.random.default_rng(1)
    flat = rng.normal(size=(16 * _TILE,)).astype(np.float32) * 1e3
    q, _, _, again = _codec_roundtrip(flat, "int8")
    np.testing.assert_array_equal(np.asarray(again), np.asarray(q))


@pytest.mark.parametrize("n", [13, 128])
def test_scale_tile_roundtrip(n):
    """[n, 1] scale column → lane-dense side-channel row → column: the
    sublane↔lane move Mosaic can lay out (no rank-1 value anywhere)."""
    scales = jnp.asarray(
        np.random.default_rng(2).uniform(0.1, 9, (n, 1)), jnp.float32
    )
    row = _pack_scale_row(scales)
    assert row.shape == (1, 128)
    np.testing.assert_array_equal(np.asarray(row)[0, :n], np.asarray(scales)[:, 0])
    np.testing.assert_array_equal(np.asarray(row)[0, n:], 1.0)  # all-zero-block convention
    np.testing.assert_array_equal(
        np.asarray(_unpack_scale_col(row, n)), np.asarray(scales)
    )


def test_bf16_helpers_are_the_registry_cast():
    flat = np.random.default_rng(3).normal(size=(_TILE,))
    wire, _, back, _ = _codec_roundtrip(flat, "bf16")
    assert wire.dtype == jnp.bfloat16
    ref = get_codec("bf16").apply(jnp.asarray(flat, jnp.float32).reshape(-1, 128))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(ref))


# --------------------------------------------------------------------------- #
# the fused schedule itself, simulated: bit parity with quant/ring.py
# --------------------------------------------------------------------------- #

def _requant_chunk(vals: np.ndarray, scales, block: int) -> jnp.ndarray:
    blocks = jnp.asarray(vals, jnp.float32).reshape(-1, block)
    q = jnp.clip(jnp.round(blocks / scales[:, None]), -127.0, 127.0)
    return q.astype(jnp.int8)


@jax.jit
def _decode_accumulate(cur, q, scales):
    """One jitted dequant-accumulate, the exact program shape of the ring's
    per-hop fold: XLA contracts the dequantize multiply into an FMA with
    the add, so an eager mul-then-add replay would drift an ulp from BOTH
    data planes — the simulation must round like the programs it checks."""
    return cur + dequantize_int8(q, scales, cur.shape[0])


def _simulate_fused_allreduce(xs: np.ndarray, block: int) -> np.ndarray:
    """Host replay of the fused kernels' schedule (encode per RS hop,
    encode-once + scale-forward + requantize in AG, every rank adopting
    decoded values) using the registry codec ops."""
    world, n = xs.shape
    chunk = n // world
    work = [
        np.array(x, np.float32).reshape(world, chunk).copy() for x in xs
    ]
    scale_store: list = [[None] * world for _ in range(world)]
    n_rs = world - 1
    for step in range(2 * (world - 1)):
        in_rs = step < n_rs
        ag = step - n_rs
        sends = {}
        for me in range(world):
            send_idx = (
                (me - step) % world if in_rs else (me + 1 - ag) % world
            )
            vals = work[me][send_idx]
            if in_rs or step == n_rs:
                q, s = quantize_int8(jnp.asarray(vals), block)
            else:
                s = scale_store[me][send_idx]
                q = _requant_chunk(vals, s, block)
            if not in_rs and step == n_rs:
                # owner adopts its own decoded chunk
                work[me][send_idx] = np.asarray(dequantize_int8(q, s, chunk))
            sends[me] = (q, s)
        for me in range(world):
            q, s = sends[(me - 1) % world]
            if in_rs:
                recv_idx = (me - step - 1) % world
                work[me][recv_idx] = np.asarray(
                    _decode_accumulate(jnp.asarray(work[me][recv_idx]), q, s)
                )
            else:
                recv_idx = (me - ag) % world
                work[me][recv_idx] = np.asarray(dequantize_int8(q, s, chunk))
                scale_store[me][recv_idx] = s
    return np.stack([w.reshape(-1) for w in work])


@pytest.fixture(scope="module")
def _quant_ring_oracle(mesh4):
    def run(xs):
        def per_shard(x):
            return wire_ring_allreduce_shard(
                x[0], 4, RANKS_AXIS, "int8", DEFAULT_BLOCK_SIZE
            )[None]

        return np.asarray(run_shard(per_shard, mesh4, jnp.asarray(xs)))

    return run


def _assert_ulp_close(a: np.ndarray, b: np.ndarray, ulps: int = 4) -> None:
    """Elementwise |a − b| within ``ulps`` of the values' own spacing — the
    exact headroom FP contraction can introduce, and nothing more."""
    tol = ulps * np.spacing(np.maximum(np.abs(a), np.abs(b)).astype(np.float32))
    assert (np.abs(a - b) <= tol).all(), (
        f"beyond {ulps}-ulp contraction headroom: "
        f"max diff {np.abs(a - b).max()}"
    )


def test_fused_schedule_matches_unfused_quant_ring(_quant_ring_oracle):
    """THE algorithm pin, runnable on every build: the fused schedule
    (per-hop requant, encode-once AG, forwarded scales) reproduces the
    unfused ppermute ring on coinciding chunk layouts.  Wire bits, add
    order, and rank-to-rank identity are exact; elementwise VALUES agree
    within FMA-contraction headroom (XLA contracts the dequantize multiply
    into the accumulate add differently across programs — a ≤2-ulp effect
    no cross-program comparison can pin tighter)."""
    rng = np.random.default_rng(4)
    xs = (rng.normal(size=(4, 8 * _TILE)) * 50).astype(np.float32)
    fused = _simulate_fused_allreduce(xs, DEFAULT_BLOCK_SIZE)
    unfused = _quant_ring_oracle(xs)
    _assert_ulp_close(fused, unfused)
    # rank-to-rank identity is EXACT on both planes: the AG forwards bits
    for out in (fused, unfused):
        for r in range(1, 4):
            np.testing.assert_array_equal(out[r], out[0])


def test_why_scales_are_forwarded_as_bits_not_rederived():
    """The side-channel design rationale, pinned from both sides.

    (a) Re-encoding DECODED values happens to reproduce scales bitwise —
    ``fl(fl(127·s)/127) == s`` holds for scales that are themselves
    127-quotients (empirically exhaustive; a numerical accident of c=127
    under round-to-nearest).  (b) For RAW values the same expression
    drifts an ulp ~1% of the time — the property is an accident of the
    quotient form, NOT of the expression.  The kernels therefore forward
    the scale BITS verbatim (side-channel store) so the all-gather's
    rank-to-rank bit identity rests on construction, not on (a) holding
    for every backend and every future codec constant."""
    # (a) codec-generated (quotient-form) scales: re-derivation is stable
    for seed in range(8):
        x = (np.random.default_rng(seed).normal(size=(16 * _TILE,))
             * 997.0).astype(np.float32)
        q, s = quantize_int8(jnp.asarray(x), DEFAULT_BLOCK_SIZE)
        decoded = dequantize_int8(q, s)
        q2, s2 = quantize_int8(decoded, DEFAULT_BLOCK_SIZE)
        np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    # (b) raw scales: the same round trip drifts — the accident's edge
    raw = np.random.default_rng(99).uniform(
        1e-6, 10, 200_000
    ).astype(np.float32)
    back = (np.float32(127.0) * raw).astype(np.float32) / np.float32(127.0)
    assert (back.astype(np.float32) != raw).any()


def test_error_feedback_residual_roundtrip_on_the_fused_plane():
    """The residual contract rides unchanged: with the fused collective as
    the wire (sum against zero peers == decode(encode(x))), shipped wire
    values plus the carried residual equal the true gradient mass to the
    codec invariant's own tolerance."""
    from adapcc_tpu.quant import error_feedback_step

    def fused_wire(g):
        xs = np.stack([np.asarray(g, np.float32), np.zeros_like(g)])
        return jnp.asarray(_simulate_fused_allreduce(xs, DEFAULT_BLOCK_SIZE)[0])

    rng = np.random.default_rng(12)
    residual = jnp.zeros((2 * 2 * _TILE,), jnp.float32)
    shipped = np.zeros((2 * 2 * _TILE,), np.float32)
    truth = np.zeros((2 * 2 * _TILE,), np.float32)
    for _ in range(4):
        grad = jnp.asarray(
            rng.normal(size=(2 * 2 * _TILE,)), jnp.float32
        )
        wire, residual = error_feedback_step(grad, residual, fused_wire)
        shipped += np.asarray(wire)
        truth += np.asarray(grad)
    np.testing.assert_allclose(
        shipped + np.asarray(residual), truth, rtol=1e-5, atol=1e-5
    )


def test_fused_schedule_wire_value_is_the_codec_apply():
    """The error-feedback contract: summing against zeros, the fused wire
    value of a payload is decode(encode(x)) — exactly the registry codec's
    apply, so error_feedback_step's residual invariant is unchanged on the
    fused plane."""
    rng = np.random.default_rng(6)
    x = (rng.normal(size=(2 * 2 * _TILE,)) * 11).astype(np.float32)
    xs = np.stack([x, np.zeros_like(x)])
    fused = _simulate_fused_allreduce(xs, DEFAULT_BLOCK_SIZE)
    ref = np.asarray(get_codec("int8").apply(jnp.asarray(x), DEFAULT_BLOCK_SIZE))
    np.testing.assert_array_equal(fused[0], ref)
    np.testing.assert_array_equal(fused[1], ref)


# --------------------------------------------------------------------------- #
# kernels under the interpreter (race detection on): fused vs unfused vs fp32
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("chunk_bytes", [1 << 30, 4096])  # vmem, hbm-stream
def test_kernel_fused_int8_matches_unfused(mesh4, chunk_bytes):
    """Both paths, vs the unfused ppermute ring on a coinciding chunk
    layout: values within FMA-contraction headroom (cross-program), rank
    identity exact on both planes."""
    from adapcc_tpu.comm.pallas_ring import ring_allreduce_shard

    world = 4
    n = world * 2 * _TILE  # per-rank chunks in whole tiles: layouts coincide
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(world, n)) * 13, jnp.float32)
    plan = plan_ring_schedule(
        n, jnp.float32, world, chunk_bytes, wire_dtype="int8"
    )
    assert plan.path == ("vmem" if chunk_bytes == 1 << 30 else "hbm-stream")

    def fused(x):
        return ring_allreduce_shard(
            x[0], world, interpret=True, chunk_bytes=chunk_bytes,
            wire_dtype="int8",
        )[None]

    def unfused(x):
        return wire_ring_allreduce_shard(x[0], world, RANKS_AXIS, "int8")[None]

    got = np.asarray(run_shard(fused, mesh4, xs))
    want = np.asarray(run_shard(unfused, mesh4, xs))
    _assert_ulp_close(got, want)
    for out in (got, want):
        for r in range(1, world):
            np.testing.assert_array_equal(out[r], out[0])


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_kernel_fused_within_ring_error_bound_of_fp32(mesh4, wire):
    from adapcc_tpu.comm.pallas_ring import ring_allreduce_shard

    world = 4
    n = 4 * 1000  # ragged: padded-tail chunks on the fused path
    rng = np.random.default_rng(8)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    def fused(x):
        return ring_allreduce_shard(
            x[0], world, interpret=True, chunk_bytes=4096, wire_dtype=wire,
        )[None]

    got = np.asarray(run_shard(fused, mesh4, xs))
    ref = np.asarray(xs).sum(axis=0)
    bound = (
        ring_error_bound(np.asarray(xs))
        if wire == "int8" else np.maximum(np.abs(ref), 1.0) * 0.05
    )
    assert (np.abs(got[0] - ref) <= bound).all()
    for r in range(1, world):  # forwarded bits: identical everywhere
        np.testing.assert_array_equal(got[r], got[0])


def test_kernel_fused_bit_identical_across_chunk_sizes(mesh4):
    """Padded-tail regression: a 13-tile (prime) per-rank chunk forces the
    pad/slice path for non-dividing budgets; results stay bit-identical
    across every staging size (blocks nest in tiles of every size)."""
    from adapcc_tpu.comm.pallas_ring import ring_allreduce_shard

    world = 4
    n = world * 13 * _TILE
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)
    tile_b = _TILE * 4

    def ring(chunk_bytes):
        def per_shard(x):
            return ring_allreduce_shard(
                x[0], world, interpret=True, chunk_bytes=chunk_bytes,
                wire_dtype="int8",
            )[None]

        return np.asarray(run_shard(per_shard, mesh4, xs))

    reference = ring(1 << 30)  # vmem path
    for chunk_bytes in (tile_b, 5 * tile_b, 13 * tile_b):
        np.testing.assert_array_equal(ring(chunk_bytes), reference)


def test_kernel_fused_reduce_scatter_and_all_gather(mesh4):
    from adapcc_tpu.comm.pallas_ring import (
        ring_all_gather_shard,
        ring_reduce_scatter_shard,
    )

    world = 4
    n = world * 4 * _TILE
    rng = np.random.default_rng(10)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    def rs(x):
        return ring_reduce_scatter_shard(
            x[0], world, interpret=True, chunk_bytes=4096, wire_dtype="int8",
        )[None]

    out = np.asarray(run_shard(rs, mesh4, xs))
    full = np.asarray(xs).sum(axis=0).reshape(world, 4 * _TILE)
    bound = ring_error_bound(np.asarray(xs)).reshape(world, 4 * _TILE)
    for r in range(world):
        own = (r + 1) % world
        assert (np.abs(out[r] - full[own]) <= bound[own]).all()

    # AG: encode once, forward verbatim — every rank ends with the codec
    # roundtrip of every chunk, bit-identically
    chunk = jnp.asarray(
        rng.normal(size=(world, 4 * _TILE)) * 7, jnp.float32
    )

    def ag(x):
        return ring_all_gather_shard(
            x[0], world, interpret=True, chunk_bytes=4096, wire_dtype="int8",
        )[None]

    gathered = np.asarray(run_shard(ag, mesh4, chunk))
    # the registry codec as the data plane runs it — compiled: under jit XLA
    # folds the scale's ``/ 127`` into a multiply by the reciprocal, an ulp
    # from the eager quotient on some blocks
    roundtrip = jax.jit(lambda c: get_codec("int8").apply(c, DEFAULT_BLOCK_SIZE))
    for src in range(world):
        want = np.asarray(roundtrip(chunk[src]))
        for r in range(world):
            np.testing.assert_array_equal(gathered[r, src], want)


def test_kernel_engine_fused_dispatch_and_trace(mesh4, monkeypatch):
    """Engine end to end on the fused plane: impl names the fused path,
    extras carry the executed wire dtype + shrunken wire bytes."""
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils.observability import CollectiveTrace

    monkeypatch.delenv(FUSED_WIRE_ENV, raising=False)
    strat = Strategy.ring(4)
    strat.wire_dtype = "int8"
    trace = CollectiveTrace()
    eng = CollectiveEngine(mesh4, strat, trace=trace)
    xs = jnp.asarray(
        np.random.default_rng(11).normal(size=(4, 2 * _TILE)), jnp.float32
    )
    out = np.asarray(eng.ring_allreduce(xs))
    ref = np.asarray(xs).sum(axis=0)
    assert (np.abs(out[0] - ref) <= ring_error_bound(np.asarray(xs))).all()
    ev = trace.events()[-1]
    assert ev.impl.startswith("pallas_ring[") and "+int8" in ev.impl
    assert ev.extra["wire_dtype"] == "int8"
    assert ev.extra["fused"] is True
    assert ev.extra["wire_bytes"] < ev.nbytes // 3


# --------------------------------------------------------------------------- #
# engine: reroute honesty + RS/AG loud rejects (build-independent via the
# ADAPCC_FUSED_WIRE=off pin)
# --------------------------------------------------------------------------- #

@pytest.fixture()
def mesh8():
    return build_world_mesh(8)


def test_engine_reroute_records_impl_reason_and_notes_once(
    mesh8, monkeypatch, capfd
):
    import adapcc_tpu.comm.pallas_ring as pr
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils.observability import CollectiveTrace

    monkeypatch.setenv(FUSED_WIRE_ENV, "off")  # force the reroute everywhere
    monkeypatch.setattr(pr, "_REROUTE_NOTED", set())
    strat = Strategy.ring(8)
    strat.wire_dtype = "int8"
    trace = CollectiveTrace()
    eng = CollectiveEngine(mesh8, strat, trace=trace)
    xs = jnp.ones((8, 512), jnp.float32)
    eng.ring_allreduce(xs)
    eng.ring_allreduce(xs)
    ev = trace.events()[-1]
    assert ev.impl == "quant_ring[int8]"
    assert "ADAPCC_FUSED_WIRE=off" in ev.extra["reroute_reason"]
    err = capfd.readouterr().err
    # loud, and exactly once per (codec, reason)
    assert err.count("rerouted off the staged Pallas kernel") == 1


def test_engine_rs_ag_reject_codec_loudly_instead_of_running_fp32(
    mesh8, monkeypatch
):
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.strategy.ir import Strategy

    monkeypatch.setenv(FUSED_WIRE_ENV, "off")
    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    xs = jnp.ones((8, 8 * _TILE), jnp.float32)
    with pytest.raises(ValueError, match="no unfused wire data plane"):
        eng.ring_reduce_scatter(xs, wire_dtype="int8")
    with pytest.raises(ValueError, match="no unfused wire data plane"):
        eng.ring_all_gather(
            jnp.ones((8, _TILE), jnp.float32), wire_dtype="bf16"
        )
    # strategy-synthesized codecs hit the same funnel (no silent fp32)
    strat = Strategy.ring(8)
    strat.wire_dtype = "int8"
    eng2 = CollectiveEngine(mesh8, strat)
    with pytest.raises(ValueError, match="ring_reduce_scatter"):
        eng2.ring_reduce_scatter(xs)
    # an explicit off pin restores the plain fp32 kernels' planning path
    plan = eng2._ring_plan(xs, None, rs=True, ag=False)
    assert plan.wire_dtype == "off"


def test_shard_wrappers_reject_codec_loudly():
    from adapcc_tpu.comm.pallas_ring import (
        ring_all_gather_shard,
        ring_allreduce_shard,
        ring_reduce_scatter_shard,
    )

    bad = jnp.ones((4, 256), jnp.bfloat16)
    for fn in (ring_allreduce_shard, ring_reduce_scatter_shard):
        with pytest.raises(ValueError, match="float32"):
            fn(bad[0], 4, interpret=True, wire_dtype="int8")
    with pytest.raises(ValueError, match="block_size"):
        ring_allreduce_shard(
            jnp.ones((1024,), jnp.float32), 4, interpret=True,
            wire_dtype="int8", block_size=192,
        )
    with pytest.raises(ValueError, match="float32"):
        ring_all_gather_shard(
            jnp.ones((16 * 128,), jnp.bfloat16), 4, interpret=True,
            wire_dtype="int8",
        )


# --------------------------------------------------------------------------- #
# pricing: fused vs unfused
# --------------------------------------------------------------------------- #

def test_fused_pricing_strictly_below_unfused_when_bandwidth_bound():
    from adapcc_tpu.sim.cost_model import (
        LinkCoeffs,
        fused_quantized_ring_allreduce_time,
        quantized_ring_allreduce_time,
    )

    ici = LinkCoeffs(alpha=1e-6, beta=1.0 / 45e9)
    for wire in ("bf16", "int8"):
        fused = fused_quantized_ring_allreduce_time(
            8, 128 << 20, ici, 1 << 20, wire
        )
        unfused = quantized_ring_allreduce_time(8, 128 << 20, ici, wire)
        assert fused < unfused
    # small payloads pay the exposed codec fill/drain: fused loses there —
    # which is exactly why the sweep flags the crossover per row
    assert fused_quantized_ring_allreduce_time(
        8, 64 << 10, ici, 1 << 20, "int8"
    ) > quantized_ring_allreduce_time(8, 64 << 10, ici, "int8")


def test_fused_pricing_degenerate_and_loud():
    from adapcc_tpu.sim.cost_model import (
        LinkCoeffs,
        fused_quantized_ring_allreduce_time,
    )

    ici = LinkCoeffs(alpha=1e-6, beta=1.0 / 45e9)
    assert fused_quantized_ring_allreduce_time(1, 1 << 20, ici, 1 << 20) == 0.0
    with pytest.raises(ValueError, match="off"):
        fused_quantized_ring_allreduce_time(8, 1 << 20, ici, 1 << 20, "off")
    with pytest.raises(ValueError, match="chunk_bytes"):
        fused_quantized_ring_allreduce_time(8, 1 << 20, ici, 0)


# --------------------------------------------------------------------------- #
# the --fused-sweep artifact (make fused-bench)
# --------------------------------------------------------------------------- #

def test_fused_sweep_rows_deterministic_crossover_flagged():
    from benchmarks.sim_collectives import fused_wire_sweep

    sizes = [1 << 20, 16 << 20, 128 << 20]
    chunks = [256 << 10, 1 << 20]
    rows = fused_wire_sweep(8, sizes, chunks)
    assert rows == fused_wire_sweep(8, sizes, chunks)  # byte-identical
    assert all(r["mode"] == "simulated" for r in rows)
    assert len(rows) == len(sizes) * len(chunks) * 2  # bf16 + int8
    # the acceptance pin: bandwidth-bound sizes strictly cheaper fused
    big = [r for r in rows if r["size_bytes"] == 128 << 20]
    assert big and all(r["pred_fused_us"] < r["pred_unfused_us"] for r in big)
    assert all(r["fused_faster"] for r in big)
    # crossover stamped per (wire, chunk) curve and consistent with rows
    for r in rows:
        if r["crossover_bytes"] is not None:
            assert r["fused_faster"] == (
                r["size_bytes"] >= r["crossover_bytes"]
            )
    # planner-consistent geometry on every row
    assert all(
        r["ring_path"] in ("vmem", "hbm-stream") and r["stage_bytes"] > 0
        for r in rows
    )
    assert all(
        r["scale_slot_bytes"] > 0
        for r in rows if r["wire_dtype"] == "int8"
    )


def test_fused_sweep_rejects_unfusable_codecs():
    from benchmarks.sim_collectives import fused_wire_sweep

    with pytest.raises(ValueError, match="off"):
        fused_wire_sweep(8, [1 << 20], [1 << 20], wire_dtypes=("off",))
    with pytest.raises(ValueError, match="no fused kernel"):
        fused_wire_sweep(8, [1 << 20], [1 << 20], wire_dtypes=("fp8",))


def test_fused_sweep_cli_json_and_exclusivity(capsys):
    import json

    from benchmarks.sim_collectives import main

    assert main([
        "--world", "4", "--sizes", "1M,128M", "--fused-sweep",
        "--chunks", "1M", "--json",
    ]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    rows = [json.loads(l) for l in lines]
    assert rows and all(r["impl"] == "fused_ring" for r in rows)
    assert {r["wire_dtype"] for r in rows} == {"bf16", "int8"}
    with pytest.raises(SystemExit):
        main(["--fused-sweep", "--ring-sweep"])
    with pytest.raises(SystemExit):
        main(["--fused-sweep", "--wire-dtype", "off,int8"])


# --------------------------------------------------------------------------- #
# tuner: fused cells in the grid, pin collapse, replay parsing
# --------------------------------------------------------------------------- #

def _grid_policy(**kw):
    from adapcc_tpu.tuner import TuningDatabase
    from adapcc_tpu.tuner.policy import TuningPolicy

    kw.setdefault("world", 8)
    kw.setdefault("topology", "fused-test")
    return TuningPolicy(TuningDatabase(persist=False), **kw)


def test_candidates_gain_fused_cells_crossing_chunk_and_codec():
    pol = _grid_policy(fused_paths=True)
    cells = pol.candidates("allreduce", 16 << 20)
    fused = [
        c for c in cells
        if c.wire_dtype != "off" and c.path in ("vmem", "hbm-stream")
    ]
    assert {c.wire_dtype for c in fused} == {"bf16", "int8"}
    # chunk_bytes x wire_dtype x path compete: several chunk cells per codec
    assert len({c.chunk_bytes for c in fused if c.wire_dtype == "int8"}) > 1
    # the unfused quant-ring cells stay in the grid as the A/B's other arm
    assert any(c.path == "quant-ring" for c in cells)
    # priors price fused and unfused codec cells differently
    int8_fused = next(c for c in fused if c.wire_dtype == "int8")
    quant = next(c for c in cells if c.path == "quant-ring"
                 and c.wire_dtype == "int8")
    assert pol.prior_time(int8_fused, 16 << 20) != pol.prior_time(
        quant, 16 << 20
    )


def test_candidates_fused_cells_follow_data_plane_support(monkeypatch):
    monkeypatch.delenv(FUSED_WIRE_ENV, raising=False)
    pol = _grid_policy()  # probe mode
    cells = pol.candidates("allreduce", 16 << 20)
    has_fused = any(
        c.wire_dtype != "off" and c.path in ("vmem", "hbm-stream")
        for c in cells
    )
    assert has_fused
    # ADAPCC_FUSED_WIRE=off removes them everywhere: a cell must never
    # claim a path the dispatch would not run
    monkeypatch.setenv(FUSED_WIRE_ENV, "off")
    pinned = _grid_policy().candidates("allreduce", 16 << 20)
    assert not any(
        c.wire_dtype != "off" and c.path in ("vmem", "hbm-stream")
        for c in pinned
    )


def test_fused_wire_on_prunes_the_unfused_cells(monkeypatch):
    """ADAPCC_FUSED_WIRE=on means NOTHING runs unfused — the quant-ring
    cells leave the grid (the mirror of =off pruning the fused cells), so
    tuner exploration can never hand the engine a cell it would refuse or
    silently reroute around."""
    monkeypatch.setenv(FUSED_WIRE_ENV, "on")
    cells = _grid_policy(fused_paths=True).candidates("allreduce", 16 << 20)
    assert not any(c.path == "quant-ring" for c in cells)
    assert any(c.wire_dtype == "int8" for c in cells)  # fused cells remain
    monkeypatch.delenv(FUSED_WIRE_ENV)
    both = _grid_policy(fused_paths=True).candidates("allreduce", 16 << 20)
    assert any(c.path == "quant-ring" for c in both)


def test_wire_pin_collapses_codec_axis_including_fused_cells(monkeypatch):
    from adapcc_tpu.quant import WIRE_DTYPE_ENV

    monkeypatch.setenv(WIRE_DTYPE_ENV, "int8")
    pol = _grid_policy(fused_paths=True)
    cells = pol.candidates("allreduce", 16 << 20)
    assert cells and {c.wire_dtype for c in cells} == {"int8"}
    monkeypatch.setenv(WIRE_DTYPE_ENV, "off")
    offs = _grid_policy(fused_paths=True).candidates("allreduce", 16 << 20)
    assert offs and {c.wire_dtype for c in offs} == {"off"}


def test_tune_replay_artifact_includes_fused_cells(monkeypatch):
    """The regression the satellite names: fused cells appear in the
    replay artifact on ANY build, and an ADAPCC_WIRE_DTYPE pin still
    collapses the codec axis."""
    from adapcc_tpu.quant import WIRE_DTYPE_ENV
    from benchmarks.sim_collectives import tune_replay_sweep

    monkeypatch.delenv(WIRE_DTYPE_ENV, raising=False)
    rows = tune_replay_sweep(8, [16 << 20])
    fused_rows = [
        r for r in rows
        if r["wire_dtype"] != "off" and r["path"] in ("vmem", "hbm-stream")
    ]
    assert {r["wire_dtype"] for r in fused_rows} == {"bf16", "int8"}
    assert all(r["samples"] > 0 for r in fused_rows)  # actually explored
    assert rows == tune_replay_sweep(8, [16 << 20])   # deterministic
    monkeypatch.setenv(WIRE_DTYPE_ENV, "int8")
    pinned = tune_replay_sweep(8, [16 << 20])
    assert {r["wire_dtype"] for r in pinned} == {"int8"}


def test_exec_chunk_realizes_fused_vmem_cells():
    """A fused vmem cell (keyed chunk_bytes=0) still needs a concrete
    execution budget that resolves to the vmem path."""
    pol = _grid_policy(fused_paths=True, epsilon=0.0, min_samples=1)
    nbytes = 256 << 10  # small: the planner's vmem regime for big budgets
    cells = pol.candidates("allreduce", nbytes)
    vmem_fused = next(
        c for c in cells if c.path == "vmem" and c.wire_dtype == "int8"
    )
    for _ in range(3):
        pol.db.record(vmem_fused, 1e-6)
    plan = pol.choose("allreduce", nbytes)
    assert plan.key == vmem_fused
    assert plan.chunk_bytes is not None
    realized = plan_ring_schedule(
        nbytes // 4, "float32", 8, plan.chunk_bytes, wire_dtype="int8"
    )
    assert realized.path == "vmem"


def test_replay_parses_fused_impls_into_fused_cells():
    from adapcc_tpu.tuner import TuningDatabase, replay_trace
    from adapcc_tpu.utils.observability import CollectiveTrace

    trace = CollectiveTrace()
    trace.record(
        "allreduce", "pallas_ring[hbm-stream+int8]", 8 * (4 << 20),
        chunk_bytes=1 << 20, wire_dtype="int8", duration_s=120e-6,
    )
    trace.record(
        "allreduce", "pallas_ring[vmem+bf16]", 8 * (1 << 20),
        chunk_bytes=4 << 20, wire_dtype="bf16", duration_s=80e-6,
    )
    db = TuningDatabase(persist=False)
    ingested, skipped = replay_trace(trace, db, world=8, topology="tf")
    assert (ingested, skipped) == (2, 0)
    keys = {(k.path, k.chunk_bytes, k.wire_dtype) for k in db.keys()}
    assert keys == {
        ("hbm-stream", 1 << 20, "int8"),
        ("vmem", 0, "bf16"),  # vmem: one cell regardless of budget
    }
