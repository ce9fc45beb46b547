"""Ring schedule planning: path selection + chunk granularity resolution.

These are the pure-Python halves of the HBM-streaming ring (no kernel
execution), so they run on every build — including ones whose Pallas cannot
execute the kernels (where test_pallas_ring skips).  They pin the contract
the acceptance criteria name: the executed chunk size is the synthesized /
overridden ``chunk_bytes`` (observable in the plan and the dispatch trace),
and sub-chunk payloads select the legacy VMEM-resident kernel.
"""

import jax.numpy as jnp
import pytest

from adapcc_tpu.comm.pallas_ring import (
    RING_CHUNK_ENV,
    _tile_elems,
    plan_ring_schedule,
    resolve_chunk_bytes,
)
from adapcc_tpu.primitives import DEFAULT_CHUNK_BYTES
from adapcc_tpu.strategy.ir import Strategy

_TILE = _tile_elems(jnp.float32)          # 1024 elems
_TILE_BYTES = _TILE * 4                   # 4096 B


# -- resolution ---------------------------------------------------------------


def test_resolve_defaults_to_4mb():
    assert resolve_chunk_bytes() == DEFAULT_CHUNK_BYTES
    assert resolve_chunk_bytes(1 << 20) == 1 << 20


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv(RING_CHUNK_ENV, str(1 << 16))
    assert resolve_chunk_bytes() == 1 << 16
    # the sweep override beats even an explicit caller value
    assert resolve_chunk_bytes(4 << 20) == 1 << 16


@pytest.mark.parametrize("bad", ["4MB", "abc", "-1", "0"])
def test_malformed_env_fails_loudly(monkeypatch, bad):
    """A typo'd sweep override must not silently fall back to the default —
    that would invalidate the A/B (same policy as ADAPCC_MERGE_ROUNDS)."""
    monkeypatch.setenv(RING_CHUNK_ENV, bad)
    with pytest.raises(ValueError, match="ADAPCC_RING_CHUNK_BYTES"):
        resolve_chunk_bytes()


def test_negative_explicit_chunk_rejected():
    with pytest.raises(ValueError):
        resolve_chunk_bytes(0)


# -- path selection -----------------------------------------------------------


def test_subchunk_payload_selects_vmem():
    """Payloads under one chunk keep the legacy VMEM-resident kernel."""
    plan = plan_ring_schedule(4 * _TILE, jnp.float32, 4)
    assert plan.path == "vmem"
    assert plan.n_tiles == 1
    assert plan.padded_bytes <= plan.chunk_bytes


def test_oversized_payload_streams():
    n = 64 * _TILE  # 256 KB fp32, world 4
    plan = plan_ring_schedule(n, jnp.float32, 4, chunk_bytes=_TILE_BYTES)
    assert plan.path == "hbm-stream"
    assert plan.stage_bytes == _TILE_BYTES          # executed == requested
    assert plan.n_tiles == 16                       # 64 KB chunk / 4 KB tiles
    assert plan.steps == 6
    # streaming VMEM need is 4 staging tiles — independent of payload size
    assert plan.vmem_bound_bytes == 4 * _TILE_BYTES


def test_selection_boundary_is_the_chunk():
    """Exactly one chunk of payload stays VMEM-resident; one byte more (one
    tile more after padding) streams."""
    world = 4
    at = plan_ring_schedule(
        world * _TILE, jnp.float32, world, chunk_bytes=world * _TILE_BYTES
    )
    above = plan_ring_schedule(
        world * _TILE + 1, jnp.float32, world, chunk_bytes=world * _TILE_BYTES
    )
    assert at.path == "vmem"
    assert above.path == "hbm-stream"


def test_stage_minimizes_padding_under_budget():
    """A budget that does not divide the chunk executes at the smallest
    tile achieving the minimal tile count (here an exact divisor, so zero
    padding and the legacy layout)."""
    n = 48 * _TILE  # per-rank chunk: 12 tiles (world 4)
    budget = 5 * _TILE_BYTES
    plan = plan_ring_schedule(n, jnp.float32, 4, chunk_bytes=budget)
    assert plan.path == "hbm-stream"
    assert plan.stage_bytes == 4 * _TILE_BYTES      # ceil(12/ceil(12/5)) = 4
    assert plan.n_tiles == 3
    legacy = plan_ring_schedule(n, jnp.float32, 4, chunk_bytes=1 << 30)
    assert legacy.padded_bytes == plan.padded_bytes


def test_prime_tile_count_still_stages_near_budget():
    """A chunk whose tile count is prime must NOT collapse to single-tile
    staging (a latency-dominated collective): the minimal-padding rule
    stages near the budget with < one tile of zero padding per chunk."""
    # per-rank chunk: 13 tiles (prime), budget 4 tiles
    n = 4 * 13 * _TILE
    plan = plan_ring_schedule(n, jnp.float32, 4, chunk_bytes=4 * _TILE_BYTES)
    assert plan.path == "hbm-stream"
    assert plan.n_tiles == 4                        # ceil(13/4)
    assert plan.stage_bytes == 4 * _TILE_BYTES      # ceil(13/4) tiles
    # padding waste: 4 tiles * 4 - 13 = 3 tiles < one staging tile
    assert plan.padded_bytes - 4 * 13 * _TILE_BYTES == 4 * 3 * _TILE_BYTES


def test_bf16_tiles_respected():
    plan = plan_ring_schedule(
        64 * _tile_elems(jnp.bfloat16), jnp.bfloat16, 4,
        chunk_bytes=_tile_elems(jnp.bfloat16) * 2,
    )
    assert plan.path == "hbm-stream"
    # bf16 native tile is (16, 128) = 4096 B; stage stays whole tiles
    assert plan.stage_bytes % (_tile_elems(jnp.bfloat16) * 2) == 0


def test_world1_is_vmem():
    assert plan_ring_schedule(10 * _TILE, jnp.float32, 1).path == "vmem"


# -- engine plumbing (no kernel execution: planning + trace only) -------------


def test_engine_plan_defaults_to_strategy_chunk(mesh8):
    from adapcc_tpu.comm.engine import CollectiveEngine

    strategy = Strategy.ring(8)
    strategy.chunk_bytes = 2 * _TILE_BYTES
    eng = CollectiveEngine(mesh8, strategy)
    stacked = jnp.zeros((8, 64 * _TILE), jnp.float32)
    plan = eng._ring_plan(stacked, None, rs=True, ag=True)
    assert plan.chunk_bytes == 2 * _TILE_BYTES      # synthesized value flows
    assert plan.path == "hbm-stream"
    # an explicit argument overrides the strategy's synthesized granularity
    explicit = eng._ring_plan(stacked, 1 << 30, rs=True, ag=True)
    assert explicit.path == "vmem"


def test_engine_plan_env_override(mesh8, monkeypatch):
    from adapcc_tpu.comm.engine import CollectiveEngine

    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    stacked = jnp.zeros((8, 64 * _TILE), jnp.float32)
    monkeypatch.setenv(RING_CHUNK_ENV, str(_TILE_BYTES))
    plan = eng._ring_plan(stacked, None, rs=True, ag=True)
    assert plan.chunk_bytes == _TILE_BYTES
    assert plan.path == "hbm-stream"


def test_engine_trace_records_executed_chunk(mesh8):
    """The dispatch trace carries the executed path + chunk size — the
    schedule a ring collective ran at is an artifact, not a guess."""
    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.utils.observability import CollectiveTrace

    trace = CollectiveTrace()
    strategy = Strategy.ring(8)
    strategy.chunk_bytes = _TILE_BYTES
    eng = CollectiveEngine(mesh8, strategy, trace=trace)
    stacked = jnp.zeros((8, 64 * _TILE), jnp.float32)
    plan = eng._ring_plan(stacked, None, rs=True, ag=True)
    eng._record_ring("allreduce", plan, stacked, True)
    (ev,) = trace.events()
    assert ev.impl == "pallas_ring[hbm-stream]"
    assert ev.extra["chunk_bytes"] == _TILE_BYTES
    assert ev.extra["stage_bytes"] == plan.stage_bytes
    assert ev.extra["n_tiles"] == plan.n_tiles


def test_engine_ag_plan_counts_world_chunks(mesh8):
    """A pure all-gather's stacked rows are per-rank chunks: the plan prices
    world × chunk, not one chunk."""
    from adapcc_tpu.comm.engine import CollectiveEngine

    eng = CollectiveEngine(mesh8, Strategy.ring(8))
    stacked = jnp.zeros((8, _TILE), jnp.float32)
    plan = eng._ring_plan(stacked, None, rs=False, ag=True)
    assert plan.padded_bytes == 8 * _TILE_BYTES


# -- solver's per-tree chunk output (c_m) -------------------------------------


def test_per_tree_chunks_clamp_to_share():
    from adapcc_tpu.strategy.solver import per_tree_chunk_bytes

    chunks = per_tree_chunk_bytes([0.75, 0.25], 1 << 20)
    assert chunks == [786432, 262144]
    # large payloads cap at the default chunk; zero-share trees stay valid
    chunks = per_tree_chunk_bytes([1.0, 0.0], 1 << 30)
    assert chunks[0] == DEFAULT_CHUNK_BYTES
    assert chunks[1] >= 1
