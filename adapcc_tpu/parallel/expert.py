"""Expert parallelism: MoE dispatch/combine over an ``experts`` mesh axis.

The reference's ALLTOALL primitive is an unimplemented stub — its MoE
workload delegates the shuffle to fastmoe/NCCL (SURVEY §2.3,
models/moe/train_moe.py:20-41).  Here the all-to-all is native:
each rank owns ``E / world`` experts and a token shard; routing happens
locally over all experts, per-expert buffers are exchanged with
``lax.all_to_all`` over ICI, experts run on their home rank, and a second
all-to-all brings results back for the weighted combine.

The sort of assignments by expert, the grouped product and the weighted
gather back are :mod:`adapcc_tpu.models.moe`'s, the same layer a single
device runs.  What this module adds is the exchange, and with it the one
capacity left: an all-to-all moves buffers of a fixed size, so each (rank,
expert) pair gets ``moe_capacity`` rows and a rank's assignments past that
are dropped before they are sent.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from adapcc_tpu.models.moe import (
    MoEConfig,
    combine,
    dispatch,
    grouped_ffn,
    held_assignments,
    softmax_router,
)


def moe_capacity(cfg: MoEConfig, n_loc: int) -> int:
    """Static per-(rank, expert) token capacity for a local shard of
    ``n_loc`` tokens — the ONE definition of the exchange geometry, shared
    by the EP shard program and the train_moe tuner probe so the probed
    all-to-all payload can never drift from the executed one."""
    return max(
        1,
        int(-(-cfg.capacity_factor * cfg.top_k * n_loc // cfg.num_experts)),
    )


def _moe_shard(
    router_kernel: jnp.ndarray,
    router_bias: jnp.ndarray,
    w1: jnp.ndarray,
    w2: jnp.ndarray,
    x: jnp.ndarray,
    *,
    cfg: MoEConfig,
    axis_name,
    capacity: int,
    a2a=None,
):
    """Per-shard EP MoE.  ``x [n_loc, D]`` token shard; ``w1/w2`` carry this
    rank's expert slice ``[E_loc, ...]``; router params are replicated.
    ``axis_name`` may be a tuple of mesh axes (two-level worlds); ``a2a``
    overrides the token shuffle (e.g. the hierarchical DCN×ICI exchange).
    Returns ``(y [n_loc, D], aux_loss)``."""
    if a2a is None:
        a2a = partial(
            lax.all_to_all, axis_name=axis_name, split_axis=0, concat_axis=0,
            tiled=False,
        )
    world = lax.psum(1, axis_name)
    n_loc, D = x.shape
    E = cfg.num_experts
    e_loc = w1.shape[0]

    # --- local routing over all experts (fp32 softmax) --------------------
    logits = x.astype(jnp.float32) @ router_kernel + router_bias
    ids, weights, aux_loss = softmax_router(
        logits, cfg.top_k, cfg.router_z_coef, mean=partial(lax.pmean, axis_name=axis_name)
    )

    # --- my assignments, sorted by expert, into the exchange's buffers ----
    sent = held_assignments(ids, 0, E)
    rows = dispatch(x.astype(cfg.dtype), sent)               # [n_loc * k, D]
    ends = jnp.cumsum(sent.sizes)
    starts = ends - sent.sizes                               # first row of each expert
    place = jnp.arange(capacity)[None, :]
    taken = place < sent.sizes[:, None]                      # [E, capacity]
    source = jnp.minimum(starts[:, None] + place, rows.shape[0] - 1)
    picked = jnp.take(rows, source.reshape(-1), axis=0).reshape(E, capacity, D)
    expert_in = jnp.where(taken[..., None], picked, 0)
    # exchange: afterwards axis 0 indexes the *source* rank and the local
    # expert slice is mine
    recv = a2a(expert_in.reshape(world, e_loc, capacity, D))

    # --- my experts run on everyone's tokens: the one grouped product -----
    flat = recv.transpose(1, 0, 2, 3).reshape(e_loc * world * capacity, D)
    full = jnp.full((e_loc,), world * capacity, jnp.int32)
    out = grouped_ffn(flat, full, {"w1": w1, "w2": w2}, jax.nn.gelu, cfg.dtype)
    out = out.reshape(e_loc, world, capacity, D).transpose(1, 0, 2, 3)

    # --- return all-to-all + weighted combine ------------------------------
    expert_out = a2a(out).reshape(E * capacity, D)
    row = jnp.arange(rows.shape[0])
    expert_of_row = jnp.minimum(jnp.searchsorted(ends, row, side="right"), E - 1)
    place_of_row = row - starts[expert_of_row]
    kept = place_of_row < capacity                            # else dropped before the exchange
    back = jnp.take(expert_out, expert_of_row * capacity + jnp.minimum(place_of_row, capacity - 1), axis=0)
    y = combine(jnp.where(kept[:, None], back, 0), weights, sent)
    return y.astype(x.dtype), aux_loss


def expert_parallel_moe(
    params: Any,
    x: jnp.ndarray,
    cfg: MoEConfig,
    mesh: Mesh,
    axis_name: str = "experts",
    capacity: int | None = None,
    engine: Any = None,
):
    """Apply an EP-sharded MoE MLP.

    ``params``: a :class:`~adapcc_tpu.models.moe.MoEMLP` param tree (router
    Dense + stacked ``w1/w2``); experts shard over ``mesh[axis_name]``, tokens
    shard over the same axis (DP-style), router is replicated.  ``x [N, D]``
    with ``N`` divisible by the axis size.  Returns ``(y [N, D], aux_loss)``.

    On a two-level ``("dcn", "ici")`` mesh the expert/token world is the
    flattened ``dcn × ici`` grid and the dispatch/return shuffles run as the
    hierarchical two-hop exchange (`all_to_all_two_level_shard`): intra-slice
    regrouping on ICI, then strictly lane-aligned DCN traffic — instead of a
    DCN-oblivious flat collective.

    ``engine`` (a :class:`~adapcc_tpu.comm.engine.CollectiveEngine` built on
    the SAME mesh) routes the dispatch/combine all-to-alls through the
    engine's :meth:`~adapcc_tpu.comm.engine.CollectiveEngine.expert_a2a`
    instead of a raw ``lax.all_to_all`` — bit-identical exchange (pinned by
    a parity test), but the traffic is now *traced* in the engine's
    dispatch trace and *tuned* under the ``all_to_all`` primitive like
    every other collective (docs/LATENCY.md §5; the tuner database is fed
    by engine-level probe dispatches at this payload geometry, see
    workloads/train_moe.py).
    """
    from adapcc_tpu.comm.two_level import (
        all_to_all_two_level_shard,
        is_two_level,
    )

    a2a = None
    if is_two_level(mesh):
        if axis_name != "experts":
            raise ValueError(
                "on a (dcn, ici) mesh expert_parallel_moe shards experts over "
                f"the full flattened grid; a specific axis_name ({axis_name!r}) "
                "would be silently ignored — build a flat sub-mesh for "
                "single-axis EP instead"
            )
        num_slices, ici_size = (int(s) for s in mesh.devices.shape)
        axis_name = tuple(mesh.axis_names)
        world = num_slices * ici_size
        a2a = partial(
            all_to_all_two_level_shard,
            num_slices=num_slices,
            ici_size=ici_size,
        )
    else:
        world = mesh.shape[axis_name]
    if engine is not None:
        if engine.world_size != world:
            raise ValueError(
                f"engine world {engine.world_size} != expert-parallel world "
                f"{world}; build the engine on the MoE mesh"
            )
        if bool(getattr(engine, "two_level", False)) != is_two_level(mesh):
            raise ValueError(
                "engine and mesh disagree about the (dcn, ici) hierarchy; "
                "build the engine on the MoE mesh"
            )
        a2a = engine.expert_a2a(
            axis_name=None if is_two_level(mesh) else axis_name
        )
    p = params["params"]
    if cfg.num_experts % world:
        raise ValueError(f"{cfg.num_experts} experts not divisible by world {world}")
    if capacity is None:
        capacity = moe_capacity(cfg, x.shape[0] // world)

    fn = shard_map(
        partial(_moe_shard, cfg=cfg, axis_name=axis_name, capacity=capacity, a2a=a2a),
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P()),
        check_vma=False,
    )
    y, aux = fn(
        p["router"]["kernel"].astype(jnp.float32),
        p["router"]["bias"].astype(jnp.float32),
        p["w1"],
        p["w2"],
        x,
    )
    return y, aux
