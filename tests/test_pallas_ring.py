"""Pallas ring collectives under the TPU interpreter on the virtual pod.

Race detection (``InterpretParams(detect_races=True)``) is enabled for every
kernel run here, so these tests double as the sanitizer pass the reference
never had (SURVEY §5.2): an unsynchronized RDMA slot reuse fails the suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from adapcc_tpu.comm.engine import CollectiveEngine
from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.comm.pallas_ring import (
    _tile_elems,
    ring_all_gather_shard,
    ring_allreduce_shard,
    ring_reduce_scatter_shard,
)
from adapcc_tpu.strategy.ir import Strategy

_TILE = _tile_elems(jnp.float32)  # fp32 tile, the payload dtype below


def run_shard(fn, mesh, *args):
    world = int(mesh.devices.size)
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=P(RANKS_AXIS), out_specs=P(RANKS_AXIS), check_vma=False
        )
    )(*args)


@pytest.mark.parametrize("n", [_TILE, 3 * _TILE, 1000])  # aligned, multi, ragged
def test_ring_allreduce_oracle(mesh4, n):
    world = 4
    xs = jnp.stack([jnp.full((n,), float(r + 1)) for r in range(world)])

    def per_shard(x):
        return ring_allreduce_shard(x[0], world, interpret=True)[None]

    out = np.asarray(run_shard(per_shard, mesh4, xs))
    np.testing.assert_allclose(out, np.full((world, n), 10.0))


def test_ring_allreduce_matches_psum_random(mesh4):
    world = 4
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(world, 2 * _TILE)), jnp.float32)

    def per_shard(x):
        return ring_allreduce_shard(x[0], world, interpret=True)[None]

    out = np.asarray(run_shard(per_shard, mesh4, xs))
    expect = np.asarray(xs).sum(axis=0)
    for r in range(world):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5, atol=1e-5)


def test_ring_allreduce_8_devices(mesh8):
    world = 8
    xs = jnp.stack([jnp.full((_TILE,), float(r + 1)) for r in range(world)])

    def per_shard(x):
        return ring_allreduce_shard(x[0], world, interpret=True)[None]

    out = np.asarray(run_shard(per_shard, mesh8, xs))
    np.testing.assert_allclose(out, np.full((world, _TILE), 36.0))


def test_ring_reduce_scatter(mesh4):
    world = 4
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(world, world * _TILE)), jnp.float32)

    def per_shard(x):
        return ring_reduce_scatter_shard(x[0], world, interpret=True)[None]

    out = np.asarray(run_shard(per_shard, mesh4, xs))  # [world, chunk]
    full = np.asarray(xs).sum(axis=0).reshape(world, _TILE)
    for r in range(world):
        own = (r + 1) % world
        np.testing.assert_allclose(out[r], full[own], rtol=1e-5, atol=1e-5)


def test_ring_all_gather(mesh4):
    world = 4
    xs = jnp.stack([jnp.full((_TILE,), float(r + 1)) for r in range(world)])

    def per_shard(x):
        return ring_all_gather_shard(x[0], world, interpret=True)[None]

    out = np.asarray(run_shard(per_shard, mesh4, xs))  # [world, world, chunk]
    for r in range(world):
        for src in range(world):
            np.testing.assert_allclose(out[r, src], np.full((_TILE,), float(src + 1)))


def test_ring_all_gather_rejects_ragged(mesh4):
    def per_shard(x):
        return ring_all_gather_shard(x[0], 4, interpret=True)[None]

    with pytest.raises(ValueError):
        run_shard(per_shard, mesh4, jnp.ones((4, 100)))


def test_ring_allreduce_bf16_tiling(mesh4):
    """bf16 payloads pad to the native (16, 128) tile and round-trip exactly
    (sums of small integers are representable in bf16)."""
    from adapcc_tpu.comm.pallas_ring import _tile_elems  # noqa

    assert _tile_elems(jnp.bfloat16) == 16 * 128
    assert _tile_elems(jnp.float32) == 8 * 128
    assert _tile_elems(jnp.int8) == 32 * 128
    world = 4
    for n in (16 * 128, 1000):  # aligned and ragged
        xs = jnp.stack(
            [jnp.full((n,), float(r + 1), jnp.bfloat16) for r in range(world)]
        )

        def per_shard(x):
            return ring_allreduce_shard(x[0], world, interpret=True)[None]

        out = np.asarray(run_shard(per_shard, mesh4, xs).astype(jnp.float32))
        np.testing.assert_allclose(out, np.full((world, n), 10.0))


def test_ring_all_gather_bf16_alignment(mesh4):
    # 8*128 elems is tile-aligned for fp32 but NOT for bf16 (needs 16*128)
    def per_shard(x):
        return ring_all_gather_shard(x[0], 4, interpret=True)[None]

    with pytest.raises(ValueError, match="2048"):
        run_shard(per_shard, mesh4, jnp.ones((4, 8 * 128), jnp.bfloat16))


def test_engine_ring_allreduce_entry(mesh8):
    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    xs = jnp.stack([jnp.full((2 * _TILE,), float(r + 1)) for r in range(8)])
    out = np.asarray(eng.ring_allreduce(xs))
    np.testing.assert_allclose(out, np.full((8, 2 * _TILE), 36.0))


def test_engine_ring_reduce_scatter_matches_xla(mesh8):
    """Engine entry point parity: the Pallas ring RS (rolled into chunk
    order) must match the XLA reduce_scatter row semantics on tile-aligned
    payloads (VERDICT r4 item 4)."""
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(8, 8 * _TILE)), jnp.float32)
    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    ring = np.asarray(eng.ring_reduce_scatter(xs))
    xla = np.asarray(eng.reduce_scatter(xs))
    assert ring.shape == xla.shape == (8, _TILE)
    np.testing.assert_allclose(ring, xla, rtol=1e-5, atol=1e-5)


def test_engine_ring_all_gather_matches_xla(mesh8):
    rng = np.random.default_rng(8)
    xs = jnp.asarray(rng.normal(size=(8, _TILE)), jnp.float32)
    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    ring = np.asarray(eng.ring_all_gather(xs))
    xla = np.asarray(eng.all_gather(xs))
    assert ring.shape == xla.shape == (8, 8, _TILE)
    np.testing.assert_allclose(ring, xla, rtol=1e-5, atol=1e-5)


# -- HBM-streaming path (payload ≫ the fixed VMEM staging budget) -------------
#
# chunk_bytes is shrunk to one fp32 tile (4 KB), so a 256 KB payload exercises
# the same payload:staging ratio (64×) as the 256 MB north-star buffer at the
# default 4 MB staging — the "256 MB virtual" regime, race-detected.


def test_stream_allreduce_parity_vs_xla(mesh4):
    """Streamed ring allreduce at payload ≫ staging must match lax.psum
    (the XLA collective) bit-for-bit shapes and numerically, under race
    detection."""
    world = 4
    n = 64 * _TILE  # 256 KB; per-rank chunk = 16 tiles of the 4 KB staging
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    def ring(x):
        return ring_allreduce_shard(
            x[0], world, interpret=True, chunk_bytes=4096
        )[None]

    def xla(x):
        return jax.lax.psum(x[0], RANKS_AXIS)[None]

    from adapcc_tpu.comm.pallas_ring import plan_ring_schedule

    assert plan_ring_schedule(n, jnp.float32, world, 4096).path == "hbm-stream"
    got = np.asarray(run_shard(ring, mesh4, xs))
    want = np.asarray(run_shard(xla, mesh4, xs))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_stream_chunk_size_bit_identical():
    """Any chunk_bytes in [1 tile, payload] gives BIT-identical results —
    including budgets that do not divide the chunk, where the kernel pads
    each chunk to whole staging tiles and slices the padding back out.
    The 13-tile (prime) per-rank chunk forces that pad/slice path for
    every non-trivial budget below."""
    import jax as _jax
    from jax.sharding import Mesh

    world = 4
    mesh = Mesh(_jax.devices()[:4], (RANKS_AXIS,))
    n = 52 * _TILE  # per-rank chunk: 13 tiles (prime)
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    def ring(chunk_bytes):
        def per_shard(x):
            return ring_allreduce_shard(
                x[0], world, interpret=True, chunk_bytes=chunk_bytes
            )[None]

        return np.asarray(run_shard(per_shard, mesh, xs))

    tile_b = _TILE * 4
    reference = ring(1 << 30)  # whole payload in one chunk → legacy vmem path
    # 2/5/7-tile budgets pad the 13-tile chunk (14/15/14 tiles staged);
    # 1/13-tile budgets divide it exactly
    for chunk_bytes in (tile_b, 2 * tile_b, 5 * tile_b, 7 * tile_b,
                        13 * tile_b, n * 4):
        got = ring(chunk_bytes)
        assert np.array_equal(got, reference), f"chunk_bytes={chunk_bytes}"


def test_stream_reduce_scatter_and_all_gather(mesh4):
    """The RS and AG halves stream too, with unchanged chunk ownership."""
    world = 4
    n = world * 16 * _TILE
    rng = np.random.default_rng(4)
    xs = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    def rs(x):
        return ring_reduce_scatter_shard(
            x[0], world, interpret=True, chunk_bytes=4096
        )[None]

    out = np.asarray(run_shard(rs, mesh4, xs))
    full = np.asarray(xs).sum(axis=0).reshape(world, 16 * _TILE)
    for r in range(world):
        np.testing.assert_allclose(
            out[r], full[(r + 1) % world], rtol=1e-5, atol=1e-5
        )

    chunk = jnp.stack(
        [jnp.full((16 * _TILE,), float(r + 1), jnp.float32) for r in range(world)]
    )

    def ag(x):
        return ring_all_gather_shard(
            x[0], world, interpret=True, chunk_bytes=4096
        )[None]

    gathered = np.asarray(run_shard(ag, mesh4, chunk))
    for r in range(world):
        for src in range(world):
            np.testing.assert_allclose(
                gathered[r, src], np.full((16 * _TILE,), float(src + 1))
            )


def test_engine_stream_allreduce_matches_psum(mesh8):
    """Engine entry point: the synthesized strategy chunk_bytes drives the
    streamed kernel, and the result matches the stacked psum oracle."""
    strategy = Strategy.ring(8)
    strategy.chunk_bytes = 4096
    eng = CollectiveEngine(mesh8, strategy)
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(8, 16 * _TILE)), jnp.float32)
    plan = eng._ring_plan(xs, None, rs=True, ag=True)
    assert plan.path == "hbm-stream"
    out = np.asarray(eng.ring_allreduce(xs))
    expect = np.asarray(xs).sum(axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_stream_allreduce_256mb_virtual_ratio(mesh4):
    """The full 4 MB-payload interpreter run at 4 KB staging (1024× ratio —
    a 4 GB payload at the default 4 MB staging): the long-pipeline soak of
    the credit protocol under race detection."""
    world = 4
    n = 1024 * _TILE
    xs = jnp.stack([jnp.full((n,), float(r + 1), jnp.float32) for r in range(world)])

    def ring(x):
        return ring_allreduce_shard(
            x[0], world, interpret=True, chunk_bytes=4096
        )[None]

    out = np.asarray(run_shard(ring, mesh4, xs))
    np.testing.assert_allclose(out, np.full((world, n), 10.0))


def test_engine_ring_rs_ag_roundtrip_is_allreduce(mesh8):
    """RS followed by AG through the engine reproduces the allreduce sum —
    the ZeRO-1 step's collective pair, stacked-view edition."""
    rng = np.random.default_rng(9)
    xs = jnp.asarray(rng.normal(size=(8, 8 * _TILE)), jnp.float32)
    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    scattered = eng.ring_reduce_scatter(xs)
    gathered = np.asarray(eng.ring_all_gather(scattered))
    expect = np.asarray(xs).sum(axis=0).reshape(8, _TILE)
    for r in range(8):
        np.testing.assert_allclose(gathered[r], expect, rtol=1e-4, atol=1e-4)
