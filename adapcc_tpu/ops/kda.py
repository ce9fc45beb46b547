"""Kimi Delta Attention's recurrence as chunked Pallas TPU kernels.

For one head, with keys ``k_t`` and queries ``q_t`` of ``d_k`` channels,
values ``v_t`` of ``d_v``, a per-channel log-decay ``g_t <= 0`` and a write
strength ``beta_t`` in (0, 1), the state ``S`` (``[d_k, d_v]``, zero at the
start) follows the gated delta rule

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

(docs/KIMI_LINEAR.md).  A step at a time that is ``T`` dependent rank-one
updates; here a **chunk** of ``C`` steps is taken at once.  With ``G`` the
log-decay summed from the chunk's first step (inclusive), ``kb = beta k`` and

    Phi(x, y)[r, i] = sum_c x[r, c] y[i, c] exp(G[r, c] - G[i, c])     (i <= r)

the chunk that starts from state ``S`` gives

    A  = Phi(k, kb) strictly under the diagonal        [C, C]
    U  = (I + A)^-1 (v - (k exp G) S)                  [C, d_v]  what each step writes
    o  = scale ((q exp G) S + Phi(q, kb) U)
    S' = diag(exp G_C) S + (kb exp(G_C - G))^T U       the next chunk's state

**The decay is per channel**, so ``Phi`` is not one product: ``exp(G_r) *
exp(-G_i)`` overflows where a channel forgets fast (64 steps at ``g = -20``
would be ``exp(1280)``).  A chunk is cut into sub-blocks of ``_SUB = 8`` rows.
Between sub-blocks the two operands are decayed to the row block's first row
(both exponents are then <= 0) and multiplied on the MXU; inside a sub-block
the exponent ``G_r - G_i`` is formed per pair, one column of the block at a
time on the VPU.  Nothing is clamped: a product that underflows is a term
that is zero in float32.

``(I + A)^-1`` is built from products alone: the sub-blocks on the diagonal
by ``(I - L)(I + L^2)(I + L^4)`` (``L^8 = 0``), then the blocks under them by
the same identity over the ``C / _SUB`` block rows; a grid step's chunks go
through those products together.

**What is float32.**  ``G``, every exponential, ``A``, the inverse, ``U`` and
the state stay float32 and the products that build the inverse and ``U`` run
at full precision.  The other products take their operands in the inputs'
dtype (bfloat16 in a bf16 model) and accumulate in float32, as the flash
kernels do.

**The kernels read and write the model's own arrays.**  ``q, k, g [B, T, H
d_k]``, ``v [B, T, H d_v]`` and ``beta [B, T, H]`` go into the two
``pallas_call``s as the mixer's projections wrote them, and ``o``, ``dq``,
``dk``, ``dv``, ``dg``, ``dbeta`` come out in the same shapes: XLA adds no
transpose, copy or sum around them (``tests/test_chip_compile.py`` holds
that).  **A head is a lane block**: head ``h`` is channels ``h d … (h + 1) d``
of the last axis, so a head's chunk is ``[C, d]`` contiguous rows of its
block, whole ``(16, 128)`` tiles in HBM, and no ``[B, T, H, d]`` array exists
on either side of the call (on a TPU that is another tiling of the same
bytes, and every crossing a pass over HBM).  The grid is ``(B, T / rows, H /
2)`` with blocks ``(1, rows, 2 d)`` at lane-block index ``p``: a grid step
takes two heads' rows, its two state chains written out side by side, and
every head's state waits in scratch (``[H, d_v, d_k]`` float32) for the next
block of rows.  ``beta``'s block is ``[rows, H]`` and stays in VMEM while the
grid walks the heads.  Formed in VMEM, where a chunk is first loaded: ``G``, by
six shifted adds down the chunk's rows, and ``kb = beta k`` in float32, a head's
``beta`` picked out of the ``[rows, H]`` block.  On the way back: ``dg`` by the
same adds up the rows, ``beta dkb`` folded into ``dk``, and ``dbeta = sum_c dkb
k`` written into the head's column of its block.  Through Mosaic ``d_k`` and
``d_v`` are multiples of 128 (a head is whole lane tiles); the interpreter
takes any head size.  Where ``T`` is no whole number of chunks the five inputs
are padded along ``T``, the one copy left (gauge ``kda.padded_rows``).

**Backward.**  The forward kernel keeps the state at the start of every grid
step (``[B H, T / rows, d_v, d_k]`` float32: 64 KB each).  The backward kernel
walks the grid steps from the last to the first; in each it first recomputes
the chunks' ``G``, ``kb``, ``Phi(q, kb)``, inverses, ``U`` and states forward
(into VMEM scratch, never HBM), then walks its chunks backward with the
state's cotangent carried in scratch.  The equations are in
docs/KIMI_LINEAR.md.

The chunk and the rows of a grid step follow the shape (:func:`chunk_plan`);
there is nothing to tune from outside.  The state is held transposed, ``[d_v,
d_k]``, so that the per-channel decay scales lanes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret
from adapcc_tpu.utils.observability import default_registry

_SUB = 8       # rows of a sub-block: pairs inside one are formed on the VPU
_CHUNK = 64    # rows of a chunk: eight sub-blocks, one solve
_BLOCK = 256   # rows of a grid step at most: four chunks of each of its heads behind one DMA
_GROUP = 2     # heads of a grid step: their state chains run in each other's waits
_LANES = 128   # through Mosaic a head's channels are whole lane tiles
_NEG = -1e30

_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def chunk_plan(T: int) -> Tuple[int, int, int]:
    """``(chunk, chunks per grid step, padded T)`` for a sequence of ``T``
    steps: chunks of 64 (a short sequence: what holds it, in sub-blocks), as
    many to a grid step as divide the padded length, ``_BLOCK`` rows at most."""
    chunk = min(_CHUNK, -(-T // _SUB) * _SUB)
    padded = -(-T // chunk) * chunk
    n = padded // chunk
    return chunk, max(p for p in (8, 4, 2, 1) if p == 1 or (p * chunk <= _BLOCK and n % p == 0)), padded


class _Geometry(NamedTuple):
    """Index planes of a ``[C, C]`` chunk, made once a kernel."""

    row: jnp.ndarray        # [C, C] row index
    col: jnp.ndarray        # [C, C] column index
    rel: jnp.ndarray        # [C, C] column minus the first column of the row's sub-block
    eye: jnp.ndarray        # [C, C] float32 identity
    same: jnp.ndarray       # [C, C] row and column in one sub-block
    rowmod: jnp.ndarray     # [C, 1] row index inside its sub-block
    rowid: jnp.ndarray      # [C, 1] row index


def _geometry(C: int) -> _Geometry:
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    start = (row // _SUB) * _SUB
    rowid = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    return _Geometry(
        row=row, col=col, rel=col - start, eye=(row == col).astype(jnp.float32),
        same=(col // _SUB) == (row // _SUB), rowmod=rowid % _SUB, rowid=rowid,
    )


def _hi(a, b, dims):
    """A float32 product at full precision (the inverse and what it solves);
    of ``[P, ·, ·]`` operands, one product for each ``P``."""
    batch = ((0,), (0,)) if a.ndim == 3 else ((), ())
    dims = tuple(tuple(d + a.ndim - 2 for d in side) for side in dims)
    return lax.dot_general(
        a, b, (dims, batch), precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )


def _running_sum(x, reverse: bool = False):
    """``x [C, d]`` summed along its rows from the first (from the last:
    ``reverse``) up to and with each row, in float32 on the VPU: ``log2 C``
    shifted adds, each row taking the partial sum that ends ``s`` rows off."""
    C = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < C:
        if reverse:
            x = x + jnp.where(row < C - s, pltpu.roll(x, C - s, 0), 0.0)
        else:
            x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _mx(a, b, dims, dtype):
    """An MXU product with operands in the inputs' dtype, float32 out."""
    precision = lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _block_row(x, j: int):
    """Row ``j`` of every sub-block of ``x [C, d]``, repeated over its block."""
    C, d = x.shape
    x3 = x.reshape(C // _SUB, _SUB, d)
    return jnp.broadcast_to(x3[:, j:j + 1, :], x3.shape).reshape(C, d)


def _rows(x, a: int):
    return x[a * _SUB:(a + 1) * _SUB]


def _stack_rows(parts, like):
    """Row blocks 1.. of a ``[C, ·]`` array from ``parts``; block 0 is zero."""
    return jnp.concatenate([jnp.zeros_like(parts[0])] + parts, axis=0) if parts else jnp.zeros_like(like)


def _scores(xs, y, G, geo: _Geometry, dtype):
    """``Phi(x, y)`` for each ``x`` of ``xs``: ``[C, C]``, zero above the diagonal."""
    C = G.shape[0]
    lead = jnp.exp(G - _block_row(G, 0))             # to the row's sub-block's first row: <= 1
    offs = [[] for _ in xs]
    for a in range(1, C // _SUB):
        yd = y * jnp.exp(jnp.minimum(G[a * _SUB:a * _SUB + 1] - G, 0.0))   # rows before block a: <= 1
        for parts, x in zip(offs, xs):
            parts.append(_mx(_rows(x * lead, a), yd, _NT, dtype))
    # a row block's product holds every column: keep those before the block
    outs = [jnp.where(geo.rel < 0, _stack_rows(parts, geo.eye), 0.0) for parts in offs]
    for j in range(_SUB):
        e = jnp.exp(jnp.where(geo.rowmod >= j, G - _block_row(G, j), _NEG))
        t = _block_row(y, j) * e
        outs = [
            jnp.where(geo.rel == j, jnp.sum(x * t, axis=1, keepdims=True), out)
            for x, out in zip(xs, outs)
        ]
    return outs


def _rows_back(Ds, y, G, geo: _Geometry, dtype):
    """``Psi(D, y)[r, c] = sum_{i <= r} D[r, i] y[i, c] exp(G[r, c] - G[i, c])``
    for each lower-triangular ``D`` of ``Ds``: the cotangent of ``Phi``'s row
    operand."""
    C = G.shape[0]
    lead = jnp.exp(G - _block_row(G, 0))
    offs = [[] for _ in Ds]
    for a in range(1, C // _SUB):
        yd = y * jnp.exp(jnp.minimum(G[a * _SUB:a * _SUB + 1] - G, 0.0))
        before = lax.broadcasted_iota(jnp.int32, (_SUB, C), 1) < a * _SUB
        for parts, D in zip(offs, Ds):
            parts.append(_rows(lead, a) * _mx(jnp.where(before, _rows(D, a), 0.0), yd, _NN, dtype))
    outs = [_stack_rows(parts, G) for parts in offs]
    for j in range(_SUB):
        e = jnp.exp(jnp.where(geo.rowmod >= j, G - _block_row(G, j), _NEG))
        t = _block_row(y, j) * e
        outs = [
            out + jnp.sum(jnp.where(geo.rel == j, D, 0.0), axis=1, keepdims=True) * t
            for D, out in zip(Ds, outs)
        ]
    return outs


def _cols_back(pairs, G, geo: _Geometry, dtype):
    """``sum over (Dt, x) of sum_{r >= i} Dt[i, r] x[r, c] exp(G[r, c] - G[i, c])``:
    the cotangent of ``Phi``'s column operand, each ``Dt`` the transpose of a
    lower-triangular cotangent."""
    C = G.shape[0]
    lead = jnp.exp(G - _block_row(G, 0))
    out = jnp.zeros_like(G)
    for a in range(1, C // _SUB):
        w = jnp.exp(jnp.minimum(G[a * _SUB:a * _SUB + 1] - G, 0.0))
        pick = (geo.col // _SUB == a) & (geo.row < a * _SUB)
        for Dt, x in pairs:
            out = out + w * _mx(jnp.where(pick, Dt, 0.0), x * lead, _NN, dtype)
    for j in range(_SUB):
        e = jnp.exp(jnp.where(geo.rowmod <= j, _block_row(G, j) - G, _NEG))
        for Dt, x in pairs:
            picked = jnp.sum(jnp.where(geo.rel == j, Dt, 0.0), axis=1, keepdims=True)
            out = out + picked * _block_row(x, j) * e
    return out


def inverse(A, geo: _Geometry):
    """``(I + A)^-1`` for ``A [C, C]`` strictly lower-triangular, or for each
    of ``[P, C, C]``: a grid step's chunks go through the ten dependent
    products together, so that one chunk's product fills the MXU while
    another's drains (alone, a chunk's chain waits out each product's
    latency: 60% of the forward kernel's time, PERF.md section 6, PR 32)."""
    n = A.shape[-1] // _SUB
    L = jnp.where(geo.same, A, 0.0)
    inv, power = geo.eye - L, L
    for _ in range(int(math.log2(_SUB)) - 1):        # (I - L)(I + L^2)(I + L^4): L^8 = 0
        power = _hi(power, power, _NN)
        inv = _hi(inv, geo.eye + power, _NN)
    if n == 1:
        return inv
    N = _hi(inv, A - L, _NN)                          # block-strictly-lower: N^n = 0
    out, power = geo.eye - N, N
    for _ in range(math.ceil(math.log2(n)) - 1):
        power = _hi(power, power, _NN)
        out = _hi(out, geo.eye + power, _NN)
    return _hi(out, inv, _NN)


def _decayed(q, k, kb, G):
    last = G[-1:]
    eG = jnp.exp(G)
    return eG, last, q * eG, k * eG, kb * jnp.exp(last - G)


def chunk_scores(q, k, kb, G, geo: _Geometry, dtype):
    """What of a chunk needs no state: ``(A, Phi(q, kb))``."""
    Bq, Akk = _scores((q, k), kb, G, geo, dtype)
    return jnp.where(geo.row > geo.col, Akk, 0.0), Bq


def chunk_state(q, k, kb, v, G, St, Minv, Bq, dtype, scale: float):
    """One chunk from state ``St [d_v, d_k]`` with its inverse and ``Phi(q,
    kb)`` in hand: ``(o, U, next state)``, float32 arrays in and out."""
    _, last, qd, kd, kt = _decayed(q, k, kb, G)
    U = _hi(Minv, v - _mx(kd, St, _NT, dtype), _NN)
    o = scale * (_mx(qd, St, _NT, dtype) + _mx(Bq, U, _NN, dtype))
    return o, U, St * jnp.exp(last) + _mx(U, kt, _TN, dtype)


def chunk_backward(q, k, kb, G, St, U, Minv, Bq, do, dSt, geo: _Geometry, dtype, scale: float):
    """The chunk's cotangents ``(dq, dk, dkb, dv, dG, dSt_in)`` from ``do`` and
    the cotangent ``dSt`` of the state it handed on."""
    eG, last, qd, kd, kt = _decayed(q, k, kb, G)
    elast = jnp.exp(last)
    dos = scale * do
    lower, upper = geo.row >= geo.col, geo.col >= geo.row
    dqd = _mx(dos, St, _NN, dtype)
    dBq = jnp.where(lower, _mx(dos, U, _NT, dtype), 0.0)
    dBqT = jnp.where(upper, _mx(U, dos, _NT, dtype), 0.0)
    dU = _mx(Bq, dos, _TN, dtype) + _mx(kt, dSt, _NT, dtype)
    dkt = _mx(U, dSt, _NN, dtype)
    dR = _hi(Minv, dU, _TN)
    dA = jnp.where(geo.row > geo.col, -_mx(dR, U, _NT, dtype), 0.0)
    dAT = jnp.where(geo.col > geo.row, -_mx(U, dR, _NT, dtype), 0.0)
    dkd = -_mx(dR, St, _NN, dtype)
    dSt_in = _mx(dos, qd, _TN, dtype) - _mx(dR, kd, _TN, dtype) + dSt * elast
    Pq, Pk = _rows_back((dBq, dA), kb, G, geo, dtype)
    Pt = _cols_back(((dAT, k), (dBqT, q)), G, geo, dtype)
    dlast = jnp.sum(dSt * St, axis=0, keepdims=True) * elast + jnp.sum(dkt * kt, axis=0, keepdims=True)
    dG = dqd * qd + dkd * kd - dkt * kt + q * Pq + k * Pk - kb * Pt
    dG = dG + jnp.where(geo.rowid == G.shape[0] - 1, dlast, 0.0)
    return dqd * eG + Pq, dkd * eG + Pk, dkt * jnp.exp(last - G) + Pt, dR, dG, dSt_in


def _chunks(per: int, body, carry):
    """``carry = body(c, carry)`` over a grid step's chunks, written out: the
    scheduler then fills one chunk's waits with the next one's work."""
    return body(0, carry) if per == 1 else lax.fori_loop(0, per, body, carry, unroll=True)


def _heads(group: int, body) -> None:
    """``body(j)`` for each head of a grid step's group, written out as the
    chunk loops are: two heads' state chains are independent, and the
    scheduler runs one in the other's waits (as a loop the kernel keeps the
    scan took 7% longer on the chip; PERF.md section 6, PR 33)."""
    for j in range(group):
        body(j)


def _at(c, chunk: int):
    return pl.ds(c * chunk if isinstance(c, int) else pl.multiple_of(c * chunk, chunk), chunk)


class _Heads(NamedTuple):
    """What a grid step walks: ``group`` heads, lane blocks ``first … first +
    group`` of the flat arrays, each ``per`` chunks of ``chunk`` rows."""

    H: int          # heads
    group: int      # heads a grid step walks: ``_GROUP``, or one where that does not divide the count
    per: int        # chunks of each
    chunk: int

    @classmethod
    def plan(cls, H: int, chunk: int, per: int) -> "_Heads":
        return cls(H=H, group=1 if H % _GROUP else _GROUP, per=per, chunk=chunk)

    def _block(self, ref, j: int, c):
        d = ref.shape[2] // self.group
        return (0, _at(c, self.chunk), slice(j * d, (j + 1) * d))

    def read(self, ref, j: int, c):
        """Chunk ``c`` of the block's ``j``-th head from ``ref``: ``[C, d]`` float32."""
        return ref[self._block(ref, j, c)].astype(jnp.float32)

    def write(self, ref, j: int, c, x) -> None:
        """``x [C, d]`` float32 into chunk ``c`` of the block's ``j``-th head."""
        ref[self._block(ref, j, c)] = x.astype(ref.dtype)

    def column(self, ref, h, c):
        """Head ``h``'s column of chunk ``c`` of a ``[rows, H]`` block (``beta``): ``[C, 1]``."""
        rows = ref[0, _at(c, self.chunk), :]
        head = lax.broadcasted_iota(jnp.int32, rows.shape, 1) == h
        return jnp.sum(jnp.where(head, rows, 0.0), axis=1, keepdims=True)

    def write_column(self, ref, h, c, x) -> None:
        """``x [C, 1]`` into head ``h``'s column: the block stays in VMEM while
        the grid walks its heads, and each fills its own."""
        at = (0, _at(c, self.chunk), slice(None))
        head = lax.broadcasted_iota(jnp.int32, (self.chunk, self.H), 1) == h
        ref[at] = jnp.where(head, x, ref[at])


def _solve_chunks(q_ref, k_ref, g_ref, beta_ref, first, qs, ks, Gs, kbs, invs, bqs, geo: _Geometry, lay: _Heads, dtype) -> None:
    """For every chunk of the grid step's heads (head ``first + j``'s chunk
    ``c`` is unit ``j per + c``): ``q`` and ``k`` as float32 into ``qs`` and
    ``ks`` (converted once, not once a pass), the summed decay ``G`` into
    ``Gs``, ``beta k`` into ``kbs`` (scratch
    ``[units, C, d_k]`` float32), ``Phi(q, kb)`` into ``bqs`` and ``(I + A)^-1``
    into ``invs`` (scratch ``[units, C, C]``): the scores a chunk at a time,
    the inverses together."""
    def head(j):
        h = first + j

        def scores(c, carry):
            u = j * lay.per + c
            qs[u] = q = lay.read(q_ref, j, c)
            ks[u] = k = lay.read(k_ref, j, c)
            g = lay.read(g_ref, j, c)
            Gs[u] = G = _running_sum(g)
            kbs[u] = kb = k * lay.column(beta_ref, h, c)
            invs[u], bqs[u] = chunk_scores(q, k, kb, G, geo, dtype)
            return carry

        _chunks(lay.per, scores, 0)

    _heads(lay.group, head)
    invs[...] = inverse(invs[...], geo)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref, state, qs, ks, Gs, kbs, invs, bqs, *, lay, scale):
    geo = _geometry(lay.chunk)
    dtype = q_ref.dtype
    first = pl.program_id(2) * lay.group
    fresh = pl.program_id(1) == 0
    _solve_chunks(q_ref, k_ref, g_ref, beta_ref, first, qs, ks, Gs, kbs, invs, bqs, geo, lay, dtype)

    def head(j):
        h = first + j

        @pl.when(fresh)
        def _():
            state[h] = jnp.zeros(state.shape[1:], state.dtype)

        start_ref[j, 0] = state[h]

        def one(c, St):
            u = j * lay.per + c
            o, _, St = chunk_state(
                qs[u], ks[u], kbs[u], lay.read(v_ref, j, c), Gs[u], St, invs[u], bqs[u], dtype, scale
            )
            lay.write(o_ref, j, c, o)
            return St

        state[h] = _chunks(lay.per, one, state[h])

    _heads(lay.group, head)


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, start_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
    dstate, states, us, qs, ks, Gs, kbs, invs, bqs, *, lay, scale,
):
    geo = _geometry(lay.chunk)
    dtype = q_ref.dtype
    per = lay.per
    first = pl.program_id(2) * lay.group
    fresh = pl.program_id(1) == 0
    _solve_chunks(q_ref, k_ref, g_ref, beta_ref, first, qs, ks, Gs, kbs, invs, bqs, geo, lay, dtype)

    def head(j):
        h = first + j

        @pl.when(fresh)
        def _():
            dstate[h] = jnp.zeros(dstate.shape[1:], dstate.dtype)

        def again(c, St):
            u = j * per + c
            states[u] = St
            _, us[u], St = chunk_state(
                qs[u], ks[u], kbs[u], lay.read(v_ref, j, c), Gs[u], St, invs[u], bqs[u], dtype, scale
            )
            return St

        _chunks(per, again, start_ref[j, 0])

        def back(i, dSt):
            c = per - 1 - i
            u = j * per + c
            k = ks[u]
            dq, dk, dkb, dv, dG, dSt = chunk_backward(
                qs[u], k, kbs[u], Gs[u], states[u], us[u], invs[u], bqs[u], lay.read(do_ref, j, c), dSt, geo, dtype, scale
            )
            lay.write(dq_ref, j, c, dq)
            lay.write(dk_ref, j, c, dk + lay.column(beta_ref, h, c) * dkb)
            lay.write(dv_ref, j, c, dv)
            lay.write(dg_ref, j, c, _running_sum(dG, reverse=True))     # a step's decay is in every later row's G
            lay.write_column(dbeta_ref, h, c, jnp.sum(dkb * k, axis=1, keepdims=True))
            return dSt

        dstate[h] = _chunks(per, back, dstate[h])

    _heads(lay.group, head)


def _params(interp, blocks: int):
    """A grid step's blocks are held twice (one in flight); Mosaic's default
    scoped limit is 16 MiB."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=None if interp else min(100 * 2**20, 2 * blocks + 16 * 2**20),
    )


# behind jax.jit, as the flash kernels are: a model's layers share one traced kernel
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _fwd_call(q, k, v, g, beta, scale, chunk, per, interp):
    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    lay = _Heads.plan(H, chunk, per)
    rows, units = chunk * per, lay.group * per
    steps = T // rows
    heads = lambda d: pl.BlockSpec((1, rows, lay.group * d), lambda b, i, p: (b, i, p))  # noqa: E731
    blocks = rows * (lay.group * ((2 * dk + 2 * dv) * q.dtype.itemsize + dk * 4) + H * 4)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, lay=lay, scale=scale),
        grid=(B, steps, H // lay.group),
        in_specs=[heads(dk), heads(dk), heads(dv), heads(dk), pl.BlockSpec((1, rows, H), lambda b, i, p: (b, i, 0))],
        out_specs=[
            heads(dv),
            pl.BlockSpec((lay.group, 1, dv, dk), lambda b, i, p: (b * (H // lay.group) + p, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((B * H, steps, dv, dk), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, dv, dk), jnp.float32),
            *[pltpu.VMEM((units, chunk, dk), jnp.float32)] * 4,
            *[pltpu.VMEM((units, chunk, chunk), jnp.float32)] * 2,
        ],
        compiler_params=_params(interp, blocks),
        interpret=interp,
        name="kda_fwd",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _bwd_call(q, k, v, g, beta, starts, do, scale, chunk, per, interp):
    B, T, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    lay = _Heads.plan(H, chunk, per)
    rows, units = chunk * per, lay.group * per
    steps = T // rows
    heads = lambda d: pl.BlockSpec((1, rows, lay.group * d), lambda b, i, p: (b, steps - 1 - i, p))  # noqa: E731
    every = pl.BlockSpec((1, rows, H), lambda b, i, p: (b, steps - 1 - i, 0))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    blocks = rows * (lay.group * ((4 * dk + 3 * dv) * q.dtype.itemsize + 2 * dk * 4) + 2 * H * 4)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, lay=lay, scale=scale),
        grid=(B, steps, H // lay.group),
        in_specs=[
            heads(dk), heads(dk), heads(dv), heads(dk), every, heads(dv),
            pl.BlockSpec((lay.group, 1, dv, dk), lambda b, i, p: (b * (H // lay.group) + p, steps - 1 - i, 0, 0)),
        ],
        out_specs=[heads(dk), heads(dk), heads(dv), heads(dk), every],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[
            pltpu.VMEM((H, dv, dk), jnp.float32),
            pltpu.VMEM((units, dv, dk), jnp.float32),
            pltpu.VMEM((units, chunk, dv), jnp.float32),
            *[pltpu.VMEM((units, chunk, dk), jnp.float32)] * 4,
            *[pltpu.VMEM((units, chunk, chunk), jnp.float32)] * 2,
        ],
        compiler_params=_params(interp, blocks),
        interpret=interp,
        name="kda_bwd",
    )(q, k, v, g, beta, do, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda_chunked(q, k, v, g, beta, scale, chunk, per, interp):
    return _fwd_call(q, k, v, g, beta, scale, chunk, per, interp)[0]


def _kda_fwd(q, k, v, g, beta, scale, chunk, per, interp):
    o, starts = _fwd_call(q, k, v, g, beta, scale, chunk, per, interp)
    return o, (q, k, v, g, beta, starts)


def _kda_bwd(scale, chunk, per, interp, res, do):
    q, k, v, g, beta, starts = res
    return tuple(_bwd_call(q, k, v, g, beta, starts, do, scale, chunk, per, interp))


_kda_chunked.defvjp(_kda_fwd, _kda_bwd)


def kda(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    beta: jnp.ndarray,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """The gated delta rule over ``q, k [B, T, H d_k]``, ``v [B, T, H d_v]``,
    the log-decay ``g [B, T, H d_k]`` (``<= 0``) and ``beta [B, T, H]``, from a
    zero state: ``o [B, T, H d_v]`` in ``v``'s dtype.  Head ``h`` is channels
    ``h d … (h + 1) d`` of each; the head count is ``beta``'s last axis.  ``g``
    and ``beta`` are taken in float32 whatever they come in; ``scale``
    defaults to ``1 / sqrt(d_k)``.  Differentiable in all five.
    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret` (site
    ``"kda"``)."""
    shapes = f"kda shapes: q {q.shape} k {k.shape} v {v.shape} g {g.shape} beta {beta.shape}"
    H = beta.shape[-1]
    if (
        any(x.ndim != 3 or x.shape[:2] != beta.shape[:2] for x in (q, k, v, g, beta))
        or k.shape != q.shape or g.shape != q.shape or q.shape[-1] % H or v.shape[-1] % H
    ):
        raise ValueError(shapes)
    B, T, _ = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    interp = resolve_interpret(interpret, "kda")
    if not interp and (dk % _LANES or dv % _LANES):
        raise ValueError(
            f"kda through Mosaic reads a head as a lane block of [B, T, H d]: d_k {dk} and d_v {dv} must be "
            f"multiples of {_LANES} ({shapes})"
        )
    if scale is None:
        scale = float(1.0 / math.sqrt(dk))
    chunk, per, padded = chunk_plan(T)
    metrics = default_registry()
    metrics.gauge("kda.chunk", chunk)
    metrics.gauge("kda.tiles", B * H * (padded // chunk))
    metrics.gauge("kda.padded_rows", padded - T)
    args = [q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32)]
    if padded != T:     # a padded step (g = 0, k = v = 0) forgets and writes nothing
        args = [jnp.pad(x, ((0, 0), (0, padded - T), (0, 0))) for x in args]
    return _kda_chunked(*args, scale, chunk, per, interp)[:, :T]
