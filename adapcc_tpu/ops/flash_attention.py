"""Blockwise (flash) causal attention as a Pallas TPU kernel.

The reference's models materialize the full ``[T, T]`` attention matrix in
HBM (models/gpt2 via HuggingFace; our XLA path in models/gpt2.py:99-105 does
the same under fusion).  On TPU the attention matmuls belong on the MXU with
the softmax streamed through VMEM: this kernel computes attention in
``[block_q, block_k]`` tiles with the online-softmax recurrence, never
materializing ``[T, T]``, and recomputes the tiles in the backward pass from
the saved logsumexp — O(T) memory in sequence length.

Forward, per query block i (running max ``m``, normalizer ``l``):

    s_ij   = q_i k_j^T · scale                 (MXU, fp32 accumulate)
    m'     = max(m, rowmax(s_ij))
    p_ij   = exp(s_ij − m')
    l      = l·exp(m − m') + rowsum(p_ij)
    acc    = acc·exp(m − m') + p_ij v_j
    o_i    = acc / l ;  lse_i = m + log l      (saved for backward)

Backward runs two kernels (no atomics needed — each grid program owns its
output block exclusively): a dq pass gridded over query blocks and a dk/dv
pass gridded over key blocks, both rebuilding ``p_ij = exp(s_ij − lse_i)``
from the residuals with ``Δ_i = rowsum(do_i ∘ o_i)``.

Used by the GPT-2 flagship model when ``GPT2Config.attention == "flash"``;
long-context cross-chip attention composes this with the ring/Ulysses
sequence parallelism in :mod:`adapcc_tpu.parallel` (each device runs this
kernel on its local K/V shard).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret

_NEG_INF = -1e30

# Mosaic requires the last two dims of every block shape to be divisible by
# the (8, 128) tile or equal to the whole array's dims.  A naive ``[BH, T]``
# logsumexp output with block ``(1, bq)`` violates the sublane rule (the 1),
# so lse/delta cross every pallas_call boundary lane-padded to
# ``[BH, T, _LSE_LANES]`` (block ``(1, bq, 8)``: bq % 8 == 0, 8 == minor dim)
# and are sliced back to ``[BH, T]`` outside the kernels.
_LSE_LANES = 8


def _causal_mask(s, qi, kj, block_q, block_k):
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_q, block_k):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    bq, d = q.shape
    n_k = k_ref.shape[1] // block_k

    m = jnp.full((bq,), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    for j in range(n_k):
        k = k_ref[0, j * block_k : (j + 1) * block_k, :].astype(jnp.float32)
        v = v_ref[0, j * block_k : (j + 1) * block_k, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, qi, j, block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m = m_new

    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(
        (m + jnp.log(l))[:, None], (bq, _LSE_LANES)
    )


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, scale, causal, block_q, block_k,
):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse_col = lse_ref[0][:, 0:1]      # [bq, 1] from the lane-padded layout
    delta_col = delta_ref[0][:, 0:1]
    bq, d = q.shape
    n_k = k_ref.shape[1] // block_k

    dq = jnp.zeros((bq, d), jnp.float32)
    for j in range(n_k):
        k = k_ref[0, j * block_k : (j + 1) * block_k, :].astype(jnp.float32)
        v = v_ref[0, j * block_k : (j + 1) * block_k, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, qi, j, block_q, block_k)
        p = jnp.exp(s - lse_col)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_col) * scale
        dq = dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, causal, block_q, block_k,
):
    kj = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    n_q = q_ref.shape[1] // block_q

    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    for i in range(n_q):
        q = q_ref[0, i * block_q : (i + 1) * block_q, :].astype(jnp.float32)
        do = do_ref[0, i * block_q : (i + 1) * block_q, :].astype(jnp.float32)
        lse_col = lse_ref[0, i * block_q : (i + 1) * block_q, 0:1]
        delta_col = delta_ref[0, i * block_q : (i + 1) * block_q, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _causal_mask(s, i, kj, block_q, block_k)
        p = jnp.exp(s - lse_col)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_col) * scale
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _block_sizes(T: int, block_q: int, block_k: int):
    bq, bk = min(block_q, T), min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"seq len {T} must divide into blocks ({bq}, {bk})")
    # Mosaic sublane rule: the lane-padded (1, bq, _LSE_LANES) block specs
    # require 8-aligned block sizes (or the degenerate bq == T case).  An
    # unaligned block compiles past tracing and dies deep in Mosaic with a
    # cryptic tiling error on hardware — reject it here with the real reason.
    for name, b in (("block_q", bq), ("block_k", bk)):
        if b % 8 and b != T:
            raise ValueError(
                f"{name}={b} must be a multiple of 8 (Mosaic sublane "
                f"alignment) or equal to the sequence length {T}"
            )
    return bq, bk


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_bhtd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    BH, T, D = q.shape
    bq, bk = _block_sizes(T, block_q, block_k)
    grid = (BH, T // bq)
    out, lse3 = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, _LSE_LANES), jnp.float32),
        ],
        interpret=resolve_interpret(interpret, "flash_attention"),
        name="flash_fwd",
    )(q, k, v)
    lse = lse3[:, :, 0]
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    return _flash_bwd_core(scale, causal, block_q, block_k, interpret, res, do, None)


def _flash_bwd_core(scale, causal, block_q, block_k, interpret, res, do, dlse):
    """Shared backward.  An ``lse`` cotangent adds ``dS_ij += p_ij·dlse_i``,
    which folds into the existing kernels as ``delta → delta − dlse`` (the
    bracket is ``p·(dp − delta)``) — no kernel change needed."""
    q, k, v, out, lse = res
    BH, T, D = q.shape
    bq, bk = _block_sizes(T, block_q, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    interp = resolve_interpret(interpret, "flash_attention")
    # lane-pad the per-row statistics for the kernels' tiled block specs
    lse3 = jnp.broadcast_to(lse[..., None], (BH, T, _LSE_LANES))
    delta3 = jnp.broadcast_to(delta[..., None], (BH, T, _LSE_LANES))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk
        ),
        grid=(BH, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=interp,
        name="flash_bwd_dq",
    )(q, k, v, do, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk
        ),
        grid=(BH, T // bk),
        in_specs=[
            pl.BlockSpec((1, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, T, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T, _LSE_LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T, _LSE_LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        interpret=interp,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


_flash_bhtd.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd_lse(q, k, v, scale, causal, block_q, block_k, interpret):
    """Like :func:`_flash_bhtd` but also returns the per-row logsumexp —
    the merge statistic blockwise consumers (ring attention) need.  Both
    outputs are differentiable: the ``lse`` cotangent lowers to the same
    backward kernels via ``delta − dlse``."""
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out, lse


def _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k, interpret):
    out, res = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return (out, res[4]), res


def _flash_bwd_lse(scale, causal, block_q, block_k, interpret, res, cts):
    do, dlse = cts
    return _flash_bwd_core(scale, causal, block_q, block_k, interpret, res, do, dlse)


_flash_bhtd_lse.defvjp(_flash_fwd_lse, _flash_bwd_lse)


def _bthd_call(kernel_entry, q, k, v, causal, scale, block_q, block_k, interpret):
    """Shared model-layout plumbing for the public wrappers: validate,
    default the scale, run ``kernel_entry`` on ``[B·H, T, D]`` tensors, and
    return its raw outputs plus the dims needed to restore the layout."""
    B, T, H, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)  # noqa: E731
    raw = kernel_entry(
        to_bhtd(q), to_bhtd(k), to_bhtd(v),
        scale, causal, block_q, block_k, interpret,
    )
    return raw, (B, T, H, D)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Blockwise attention over ``[B, T, H, D]`` tensors (model layout).

    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret`, which
    records what it decided — the Pallas interpreter off-TPU, so the
    same call works on the virtual CPU pod.  ``scale`` defaults to
    ``1/sqrt(D)``.  ``T`` must divide by the block sizes (clamped to ``T``).
    """
    out, (B, T, H, D) = _bthd_call(
        _flash_bhtd, q, k, v, causal, scale, block_q, block_k, interpret
    )
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Blockwise attention returning ``(out [B,T,H,D], lse [B,H,T])``.

    ``lse[b,h,t] = logsumexp_j(scale·q_t·k_j)`` (with the causal mask
    applied) — the statistic a blockwise consumer needs to merge partial
    attention over K/V blocks it sees one at a time (ring attention's
    log-sum-exp combine).  Fully differentiable in both outputs.
    """
    (out, lse), (B, T, H, D) = _bthd_call(
        _flash_bhtd_lse, q, k, v, causal, scale, block_q, block_k, interpret
    )
    return (
        out.reshape(B, H, T, D).transpose(0, 2, 1, 3),
        lse.reshape(B, H, T),
    )
