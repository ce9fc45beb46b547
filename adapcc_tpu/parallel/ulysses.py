"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head exchange.

The second of the two standard long-context schemes (alongside
:mod:`adapcc_tpu.parallel.ring_attention`): instead of rotating K/V blocks
around a ring, each rank trades its sequence shard for a head shard with one
``all_to_all``, computes *full-sequence* attention on its subset of heads,
and trades back.  Two all-to-alls of activation size per layer vs the ring's
``world`` K/V hops — cheaper when heads ≥ world and the interconnect favors
few large transfers; the ring wins when per-device memory cannot hold the
full sequence for even one head.

Layout per shard (inside ``shard_map``):

    in:   [B, T/world, H, D]      sequence-sharded
    →     [B, T, H/world, D]      head-sharded (all_to_all)
    attn: full causal attention over T on H/world heads
    →     [B, T/world, H, D]      back to sequence-sharded (all_to_all)

No reference analog (SURVEY §5.7 — the reference has no sequence
parallelism); this is a new TPU-first capability.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from adapcc_tpu.parallel.ring_attention import _NEG_INF


def ulysses_attention_shard(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = True,
    scale: Optional[float] = None,
    block_impl: str = "dense",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Per-shard Ulysses attention, for use inside ``shard_map``.

    ``q/k/v``: ``[B, T_local, H, D]`` with ``H`` divisible by the axis size;
    rank r holds global positions ``[r*T_local, (r+1)*T_local)``.
    Returns ``[B, T_local, H, D]`` in ``q.dtype``.

    ``block_impl="flash"`` runs the per-head full-sequence attention on the
    Pallas flash kernel — after the all-to-all each rank holds the WHOLE
    sequence for its head group, so the single-device kernel applies
    directly (no merge statistics needed, unlike the ring).
    """
    if block_impl not in ("dense", "flash"):
        raise ValueError(f"unknown block_impl {block_impl!r} (dense|flash)")
    B, Tl, H, D = q.shape
    world = lax.psum(1, axis_name)
    if H % world != 0:
        raise ValueError(f"heads ({H}) must divide by the axis size ({world})")
    if scale is None:
        scale = 1.0 / (D**0.5)

    def seq_to_heads(x):
        # [B, Tl, H, D] → [B, world*Tl, H/world, D]: split heads into world
        # groups, exchange so each rank holds every sequence block of its
        # head group, then stitch blocks back in global sequence order
        x = x.reshape(B, Tl, world, H // world, D)  # [B,Tl,w,h,D]
        x = jnp.moveaxis(x, 2, 0)  # [w,B,Tl,h,D]
        x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
        # row j is now the j-th rank's sequence block of MY head group
        x = jnp.moveaxis(x, 1, 0)  # [B,w,Tl,h,D]
        return x.reshape(B, world * Tl, H // world, D)

    def heads_to_seq(x):
        # inverse: [B, T, H/world, D] → [B, Tl, H, D]
        x = x.swapaxes(0, 1).reshape(world, Tl, B, H // world, D)
        x = lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0)
        # row g is my sequence block of head group g
        x = jnp.moveaxis(x, 0, 2)  # [Tl,B,w,h,D] ← [w,Tl,B,h,D]
        return jnp.moveaxis(x, 0, 1).reshape(B, Tl, H, D)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)

    if block_impl == "flash":
        from adapcc_tpu.ops import flash_attention

        out = flash_attention(
            qh, kh, vh, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k,
        )
        return heads_to_seq(out).astype(q.dtype)

    s = jnp.einsum(
        "bqhd,bkhd->bhqk", qh.astype(jnp.float32) * scale, kh.astype(jnp.float32)
    )
    if causal:
        T = world * Tl
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return heads_to_seq(out).astype(q.dtype)


def ulysses_attention(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "ranks",
    causal: bool = True,
    scale: Optional[float] = None,
    block_impl: str = "dense",
) -> jnp.ndarray:
    """Global-view wrapper: ``q/k/v [B, T, H, D]`` with ``T`` and ``H``
    divisible by the mesh axis size.  ``block_impl="flash"`` runs the
    per-head attention on the Pallas flash kernel."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        partial(ulysses_attention_shard, axis_name=axis_name, causal=causal, scale=scale,
                block_impl=block_impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
