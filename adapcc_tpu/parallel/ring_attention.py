"""Ring attention: exact causal attention over a sequence-sharded axis.

Long-context support the reference does not have (SURVEY §5.7 — its nearest
analog is the chunked pipelining of collectives, allreduce.cu:536-653).  Here
the same communication family is applied to attention itself: each rank holds
a contiguous sequence shard of Q/K/V; K/V blocks rotate around the mesh axis
via ``lax.ppermute`` while a flash-style online softmax accumulates exact
attention — compute on the current block overlaps the ICI transfer of the
next, so the ring is bandwidth-, not latency-bound.

All accumulation in float32; block math in the input dtype (bfloat16 on the
MXU).  No data-dependent control flow — one ``lax.scan`` of ``world`` steps.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

_NEG_INF = -1e30  # finite "masked" score: keeps exp() well-defined


def _ring_perm(world):
    """Receive-from-right rotation: after j shifts a rank holds the K/V
    block originally owned by rank (me + j) % world.  Shared by the dense
    and flash ring paths — one definition of the rotation direction."""
    return [(i, (i - 1) % world) for i in range(world)]


def ring_attention_shard(
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
    """Per-shard ring attention, for use inside ``shard_map``.

    ``q/k/v``: ``[B, T_local, H, D]`` — this rank's contiguous sequence shard
    (rank r holds global positions ``[r*T_local, (r+1)*T_local)``).
    Returns ``[B, T_local, H, D]`` in ``q.dtype``.

    ``block_impl="flash"`` computes each ring step's block attention with
    the Pallas flash kernel (ops/flash_attention.py) instead of the dense
    ``[T_local, T_local]`` einsum: scores stream through VMEM in MXU tiles,
    so per-device memory stays O(T_local) at long context.  Partial results
    merge by the log-sum-exp combine over the kernel's ``lse`` output.
    """
    if block_impl == "flash":
        return _ring_flash_shard(q, k, v, axis_name, causal, scale, block_q, block_k)
    if block_impl != "dense":
        raise ValueError(f"unknown block_impl {block_impl!r} (dense|flash)")
    B, Tl, H, D = q.shape
    world = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32) * scale
    q_pos = me * Tl + jnp.arange(Tl)  # global query positions

    perm = _ring_perm(world)

    def step(carry, j):
        o, m, l, k_blk, v_blk = carry
        src = (me + j) % world
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32)
        )  # [B,H,Tl,Tl]
        if causal:
            k_pos = src * Tl + jnp.arange(Tl)
            mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
            s = jnp.where(mask, s, _NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # [B,H,Tl]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])  # [B,H,Tl,Tl]
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )

        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    m0 = jnp.full((B, H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    (o, _, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(world))

    # fully-masked rows (can't happen for causal self-attention, where every
    # query sees itself) would have l == 0; guard the divide anyway
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_flash_shard(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
    scale: Optional[float],
    block_q: Optional[int],
    block_k: Optional[int],
) -> jnp.ndarray:
    """Flash-ring: each ring step runs the blockwise Pallas kernel on the
    K/V block currently held, then merges via log-sum-exp using the
    kernel's ``lse`` output.  Per causal step the block is one of three
    static programs (``lax.switch`` on the rotating source rank): fully
    visible (past block), diagonal (own block, causal mask), or skipped
    (future block contributes ``lse = −inf``).

    The whole scan runs in the kernel's ``[B·H, T_local, D]`` layout —
    transposed once on entry and once on exit, never per step (the public
    wrapper's per-call layout round-trip would be inverted immediately by
    the merge)."""
    # the kernel-layout entry point, deliberately: one transpose per ring,
    # not one per step
    from adapcc_tpu.ops.flash_attention import _flash_bhtd_lse

    B, Tl, H, D = q.shape
    world = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    if scale is None:
        scale = float(1.0 / (D**0.5))
    perm = _ring_perm(world)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, Tl, D)  # noqa: E731
    qf, kf, vf = to_bhtd(q), to_bhtd(k), to_bhtd(v)

    def full_block(qf, kb, vb):
        return _flash_bhtd_lse(qf, kb, vb, scale, False, block_q, block_k, None, None)

    def diag_block(qf, kb, vb):
        return _flash_bhtd_lse(qf, kb, vb, scale, True, block_q, block_k, None, None)

    def skip_block(qf, kb, vb):
        return jnp.zeros_like(qf), jnp.full((B * H, Tl), _NEG_INF, jnp.float32)

    def step(carry, j):
        o_acc, m, l, k_blk, v_blk = carry
        src = (me + j) % world
        if causal:
            idx = jnp.where(src == me, 1, jnp.where(src < me, 0, 2))
            o_blk, lse_blk = lax.switch(
                idx, (full_block, diag_block, skip_block), qf, k_blk, v_blk
            )
        else:
            o_blk, lse_blk = full_block(qf, k_blk, v_blk)

        # log-sum-exp merge: o_blk is normalized within its block, so its
        # weight in the running estimate is exp(lse_blk − m_new)
        m_new = jnp.maximum(m, lse_blk)
        alpha = jnp.exp(m - m_new)
        w = jnp.exp(lse_blk - m_new)
        o_acc = o_acc * alpha[..., None] + o_blk.astype(jnp.float32) * w[..., None]
        l_new = l * alpha + w

        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (o_acc, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((B * H, Tl, D), jnp.float32)
    m0 = jnp.full((B * H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B * H, Tl), jnp.float32)
    (o, _, l, _, _), _ = lax.scan(step, (o0, m0, l0, kf, vf), jnp.arange(world))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(B, H, Tl, D).transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "ranks",
    causal: bool = True,
    scale: Optional[float] = None,
    block_impl: str = "dense",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Global-view convenience wrapper: ``q/k/v [B, T, H, D]`` with ``T``
    divisible by the mesh axis size; shards the sequence dim, runs the ring,
    returns the full ``[B, T, H, D]`` result.  ``block_impl="flash"`` runs
    each step's block attention on the Pallas flash kernel."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        partial(
            ring_attention_shard, axis_name=axis_name, causal=causal, scale=scale,
            block_impl=block_impl, block_q=block_q, block_k=block_k,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def reference_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True
) -> jnp.ndarray:
    """Plain full attention — the correctness oracle for the ring."""
    B, T, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (D**0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
