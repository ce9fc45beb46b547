"""Mamba-2's state-space recurrence (SSD) as chunked Pallas TPU kernels.

For one head of ``P`` channels, with a step size ``dt_t > 0``, a decay rate
``A < 0`` and write and read directions ``B_t``, ``C_t`` of ``N`` channels
**shared by every head** (one group), the state ``H`` (``[P, N]``, zero at the
start) follows a diagonal recurrence with a scalar decay a head

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t
    y_t = H_t C_t + D x_t

(docs/GRANITE_HYBRID.md).  No delta rule: the write never erases, so a chunk
needs no solve, only products.  With ``G`` the log-decay ``dt A`` summed from
the chunk's first step (inclusive), ``xd = dt x`` and

    Lam[t, s] = exp(G_t - G_s)  for s <= t, else 0        [L, L], <= 1

the chunk of ``L`` steps that starts from state ``S`` (held ``[N, P]``) gives

    y  = ((C B^T) o Lam) xd + exp(G) o (C S) + D x
    S' = exp(G_L) S + B^T (exp(G_L - G) o xd)

``C B^T`` is one ``[L, L]`` product a chunk **for all heads**; a head brings
its own ``Lam``.  The exponent is formed per pair before the exponential, so
every one is ``<= 0`` and nothing overflows however fast a head forgets (the
sample ``ssd.decay_floor`` the model hands out says how near a chunk comes to
forgetting everything: what bounds the chunk of a kernel that splits the
exponential in two).

**The kernels read and write the model's own arrays.**  ``x [B, T, H P]``,
``dt [B, T, H]``, ``B`` and ``C`` ``[B, T, N]`` go into the two
``pallas_call``s as they are and ``y``, ``dx``, ``ddt``, ``dB``, ``dC`` come
out in the same shapes: a head is ``P`` lanes of a row, found by the block
specs, and XLA adds no transpose, reshape or cumulative sum around the kernels
(``tests/test_chip_compile.py`` holds that).  The grid is ``(B, T / rows,
H P / block)``, the head blocks innermost: ``dt``, ``B`` and ``C`` of a block
of rows stay in VMEM while the grid walks the heads, and what all heads share
is made once, at the first head block, into scratch: ``C B^T``, and ``G`` both
ways round (``[L, H]`` for columns, ``[H, L]`` for rows: two products of
``dt A`` with a triangle of ones at full precision, so no transposition and
no running sum on the VPU).  A lane tile (128 lanes through Mosaic) holds
``128 / P`` heads; each brings its ``Lam``, and its product takes the tile
with the other heads' lanes zeroed, so no head is ever sliced out of a tile.

**What is float32.**  ``dt``, ``A``, ``G``, every exponential, ``C B^T``,
``Lam`` and the state.  The products take their operands in ``x``'s dtype
(bfloat16 in a bf16 model) and accumulate in float32, as the flash and KDA
kernels do; float32 inputs keep full-precision products.

**Backward.**  The forward kernel keeps the state at the start of every grid
step (``[B, T / rows, N, H P]`` float32).  The backward kernel walks the grid
steps from the last to the first; for each lane tile it recomputes the
chunks' states forward into scratch, then walks the chunks backward with the
state's cotangent carried.  A chunk's ``y0 = y - D x`` is recomputed, and

    dxd = ((C B^T) o Lam)^T dy + exp(G_L - G) o (B dS')
    dx  = dt dxd + D dy            ddt = sum_p x dxd + A da
    dG  = sum_p (dy y0 - xd dxd) + [last row] sum (dS' o S')
    da  = dG summed from each row to the chunk's end
    dS  = exp(G_L) dS' + C^T (exp(G) o dy)

``d(C B^T) = sum over heads of (dy xd^T) o Lam`` gathers in scratch while the
grid walks the heads, and at the last head block gives ``dC`` and ``dB``
beside what the states gave them; ``dG`` and ``ddt`` gather the same way, a
column a head.  ``dA`` and ``dD`` come out as one partial row a grid step.

The chunk and the rows of a grid step follow the shape (:func:`chunk_plan`);
there is nothing to tune from outside.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret
from adapcc_tpu.utils.observability import default_registry

_SUB = 8        # a short sequence's chunk is a whole number of sublanes
_CHUNK = 128    # rows of a chunk: one MXU tile of C B^T and of Lam
_BLOCK = 512    # rows of a grid step at most: four chunks behind one DMA
_LANES = 128    # through Mosaic a lane tile; it holds 128 / P heads
# lane tiles of a grid step at most (1, 2, 4, 8: 1.81, 1.63, 1.53, 1.50 ms forward and backward at the published shape)
_TILES = 4
_NEG = -1e30

_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def chunk_plan(T: int) -> Tuple[int, int, int]:
    """``(chunk, chunks per grid step, padded T)`` for a sequence of ``T``
    steps: chunks of 128 (a short sequence: what holds it, in sublanes), as
    many to a grid step as divide the padded length, four at most."""
    chunk = min(_CHUNK, -(-T // _SUB) * _SUB)
    padded = -(-T // chunk) * chunk
    n = padded // chunk
    return chunk, max(p for p in (4, 2, 1) if n % p == 0 and p * chunk <= _BLOCK), padded


class _Plan(NamedTuple):
    """How a grid step's heads lie in its blocks."""

    chunk: int      # L
    per: int        # chunks of a grid step
    H: int          # heads
    P: int          # channels of a head
    hp: int         # heads of a lane tile
    nq: int         # lane tiles of a grid step

    @property
    def tile(self) -> int:
        return self.hp * self.P

    @property
    def width(self) -> int:
        return self.nq * self.tile

    @classmethod
    def of(cls, H: int, P: int, chunk: int, per: int, interp) -> "_Plan":
        if interp:          # any shape: pairs of heads and two tiles where the heads allow, as on the chip
            hp = 2 - H % 2
        else:
            hp = max(1, _LANES // P)
            if (hp * P) % _LANES or H % hp:
                raise ValueError(
                    f"ssd through Mosaic finds a head as {P} lanes of a {_LANES}-lane tile: the head size must "
                    f"divide {_LANES} or be a multiple of it, and the {H} heads fill whole tiles"
                )
        tiles = H // hp
        return cls(chunk=chunk, per=per, H=H, P=P, hp=hp, nq=max(n for n in range(1, _TILES + 1) if tiles % n == 0))


def _mx(a, b, dims, dtype):
    """An MXU product with operands in the inputs' dtype, float32 out."""
    precision = lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())), precision=precision,
        preferred_element_type=jnp.float32,
    )


def _hi(a, b, dims):
    """A float32 product at full precision (sums of the log-decay)."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _at(c: int, L: int):
    return pl.ds(c * L, L)


def _lower(L: int):
    row = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return row >= col


def _upper_ones(L: int):
    """``[L, L]`` float32: 1 where the row is at or before the column."""
    row = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return (row <= col).astype(jnp.float32)


def _shared(dt_ref, a_ref, b_ref, c_ref, cb, gcol, grow, plan: _Plan, dtype) -> None:
    """What every head of a grid step's rows shares, into scratch, a chunk at
    a time: ``C B^T`` ``[L, L]``, and the log-decay summed from the chunk's
    first row as columns of ``[L, H]`` and as rows of ``[H, L]``."""
    L = plan.chunk
    lower, upper = _lower(L).astype(jnp.float32), _upper_ones(L)
    for c in range(plan.per):
        a = dt_ref[0, _at(c, L), :] * a_ref[...]
        gcol[c] = _hi(lower, a, _NN)
        grow[c] = _hi(a, upper, _TN)            # [H, L]: sum_s a[s, h] [s <= t]
        cb[c] = _mx(c_ref[0, _at(c, L), :], b_ref[0, _at(c, L), :], _NT, dtype)


def _column(rows, h):
    """Head ``h``'s column of ``rows [L, H]``: ``[L, 1]``."""
    head = lax.broadcasted_iota(jnp.int32, rows.shape, 1) == h
    return jnp.sum(jnp.where(head, rows, 0.0), axis=1, keepdims=True)


def _set_column(ref, c: int, h, x) -> None:
    """``x [L, 1]`` into head ``h``'s column of ``ref[c]`` (``[L, H]``)."""
    head = lax.broadcasted_iota(jnp.int32, ref.shape[1:], 1) == h
    ref[c] = jnp.where(head, x, ref[c])


class _Tile(NamedTuple):
    """A lane tile's heads over one chunk."""

    masks: tuple        # [1, tile] each: the lanes of head j
    lams: tuple         # [L, L] float32 each: Lam of head j
    dt: jnp.ndarray     # [L, tile] float32: each head's dt over its lanes
    G: jnp.ndarray      # [L, tile] float32: each head's summed log-decay over its lanes


def _spread(masks, cols):
    """Each head's ``[L, 1]`` column over the head's lanes: ``[L, tile]``."""
    out = jnp.where(masks[0], cols[0], 0.0)
    for mask, col in zip(masks[1:], cols[1:]):
        out = jnp.where(mask, col, out)
    return out


def _tile(dt_ref, gcol, grow, c: int, first, plan: _Plan) -> _Tile:
    L = plan.chunk
    lane = lax.broadcasted_iota(jnp.int32, (1, plan.tile), 1) // plan.P
    lower = _lower(L)
    dts, Gs = dt_ref[0, _at(c, L), :], gcol[c]
    masks, lams, dcols, gcols = [], [], [], []
    for j in range(plan.hp):
        h = first + j
        g = _column(Gs, h)
        masks.append(lane == j)
        lams.append(jnp.exp(jnp.where(lower, g - grow[c, pl.ds(h, 1), :], _NEG)))
        dcols.append(_column(dts, h))
        gcols.append(g)
    return _Tile(masks=tuple(masks), lams=tuple(lams), dt=_spread(masks, dcols), G=_spread(masks, gcols))


def _by_head(t: _Tile, mats, x, dims, dtype):
    """``sum over heads of mats[j] x_j`` with ``x_j`` the tile with the other
    heads' lanes zeroed: each head's own ``[L, L]`` matrix over its lanes."""
    out = None
    for mask, m in zip(t.masks, mats):
        part = _mx(m, jnp.where(mask, x, 0.0), dims, dtype)
        out = part if out is None else out + part
    return out


def _head_sums(t: _Tile, x):
    """``x [L, tile]`` summed over each head's lanes: an ``[L, 1]`` a head."""
    return [jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True) for mask in t.masks]


def _chunk_forward(t: _Tile, cbc, x, Bc, Cc, S, dvec, dtype):
    """One chunk of a lane tile from state ``S [N, tile]``: ``(y, next state)``."""
    xd = x * t.dt
    last = t.G[-1:]
    mats = [cbc * lam for lam in t.lams]
    y = _by_head(t, mats, xd, _NN, dtype) + jnp.exp(t.G) * _mx(Cc, S, _NN, dtype) + dvec * x
    return y, jnp.exp(last) * S + _mx(Bc, jnp.exp(last - t.G) * xd, _TN, dtype)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, state, cb, gcol, grow, *, plan: _Plan):
    L, dtype = plan.chunk, x_ref.dtype
    p = pl.program_id(2)
    fresh = pl.program_id(1) == 0

    @pl.when(p == 0)
    def _():
        _shared(dt_ref, a_ref, b_ref, c_ref, cb, gcol, grow, plan, dtype)

    for q in range(plan.nq):
        lanes = slice(q * plan.tile, (q + 1) * plan.tile)
        index = p * plan.nq + q
        S = jnp.where(fresh, 0.0, state[index])
        start_ref[0, 0, :, lanes] = S
        for c in range(plan.per):
            t = _tile(dt_ref, gcol, grow, c, index * plan.hp, plan)
            y, S = _chunk_forward(
                t, cb[c], x_ref[0, _at(c, L), lanes].astype(jnp.float32), b_ref[0, _at(c, L), :],
                c_ref[0, _at(c, L), :], S, d_ref[:, lanes], dtype,
            )
            y_ref[0, _at(c, L), lanes] = y.astype(y_ref.dtype)
        state[index] = S


def _bwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, start_ref,
    dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
    dstate, states, cb, gcol, grow, dcb, dbs, dcs, dgs, ddts, *, plan: _Plan,
):
    L, per, dtype = plan.chunk, plan.per, x_ref.dtype
    p = pl.program_id(2)
    fresh = pl.program_id(1) == 0
    lower = _lower(L)
    last_row = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1

    @pl.when(p == 0)
    def _():
        _shared(dt_ref, a_ref, b_ref, c_ref, cb, gcol, grow, plan, dtype)
        for ref in (dcb, dbs, dcs, dgs, ddts):
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    for q in range(plan.nq):
        lanes = slice(q * plan.tile, (q + 1) * plan.tile)
        index = p * plan.nq + q
        first = index * plan.hp
        dvec = d_ref[:, lanes]
        tiles = [_tile(dt_ref, gcol, grow, c, first, plan) for c in range(per)]
        xs = [x_ref[0, _at(c, L), lanes].astype(jnp.float32) for c in range(per)]

        # the chunks' states again, forward: states[c] starts chunk c, states[c + 1] is what it hands on
        S = start_ref[0, 0, :, lanes]
        states[0] = S
        for c in range(per):
            t, Bc = tiles[c], b_ref[0, _at(c, L), :]
            last = t.G[-1:]
            S = jnp.exp(last) * S + _mx(Bc, jnp.exp(last - t.G) * (xs[c] * t.dt), _TN, dtype)
            states[c + 1] = S

        dS = jnp.where(fresh, 0.0, dstate[index])
        dd = jnp.zeros((1, plan.tile), jnp.float32)
        for c in reversed(range(per)):
            t, x, Bc, Cc = tiles[c], xs[c], b_ref[0, _at(c, L), :], c_ref[0, _at(c, L), :]
            dy = dy_ref[0, _at(c, L), lanes].astype(jnp.float32)
            S0 = states[c]
            last = t.G[-1:]
            xd = x * t.dt
            E, v = jnp.exp(t.G), jnp.exp(last - t.G)
            mats = [cb[c] * lam for lam in t.lams]
            y0 = _by_head(t, mats, xd, _NN, dtype) + E * _mx(Cc, S0, _NN, dtype)
            dxd = _by_head(t, mats, dy, _TN, dtype) + v * _mx(Bc, dS, _NN, dtype)
            dx_ref[0, _at(c, L), lanes] = (t.dt * dxd + dvec * dy).astype(dx_ref.dtype)
            dd = dd + jnp.sum(dy * x, axis=0, keepdims=True)
            # [1, tile]: a head's lanes sum to dG's last row
            kept = jnp.sum(dS * states[c + 1], axis=0, keepdims=True)
            ddt_cols = _head_sums(t, dxd * x)
            dg_cols = _head_sums(t, dy * y0 - xd * dxd)
            acc = dcb[c]
            for j in range(plan.hp):
                h = first + j
                at_end = jnp.sum(jnp.where(t.masks[j], kept, 0.0), axis=1, keepdims=True)
                _set_column(dgs, c, h, dg_cols[j] + jnp.where(last_row, at_end, 0.0))
                _set_column(ddts, c, h, ddt_cols[j])
                scores = _mx(jnp.where(t.masks[j], dy, 0.0), xd, _NT, dtype)     # dy_j xd_j^T
                acc = acc + jnp.where(lower, scores * t.lams[j], 0.0)
            dcb[c] = acc
            Edy = E * dy
            dcs[c] += _mx(Edy, S0, _NT, dtype)
            dbs[c] += _mx(v * xd, dS, _NT, dtype)
            dS = jnp.exp(last) * dS + _mx(Cc, Edy, _TN, dtype)
        dstate[index] = dS
        dd_ref[0, 0, :, lanes] = dd

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        upper = _upper_ones(L)
        dA = jnp.zeros(a_ref.shape, jnp.float32)
        for c in range(per):
            Bc, Cc = b_ref[0, _at(c, L), :], c_ref[0, _at(c, L), :]
            dc_ref[0, _at(c, L), :] = (dcs[c] + _mx(dcb[c], Bc, _NN, dtype)).astype(dc_ref.dtype)
            db_ref[0, _at(c, L), :] = (dbs[c] + _mx(dcb[c], Cc, _TN, dtype)).astype(db_ref.dtype)
            da = _hi(upper, dgs[c], _NN)                     # a row's decay is in every later row's G
            ddt_ref[0, _at(c, L), :] = ddts[c] + da * a_ref[...]
            dA = dA + jnp.sum(da * dt_ref[0, _at(c, L), :], axis=0, keepdims=True)
        da_ref[0, 0] = dA


def _params(interp):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=None if interp else 64 * 2**20,
    )


# behind jax.jit, as the flash and KDA kernels are: a model's layers share one traced kernel
@functools.partial(jax.jit, static_argnums=(6, 7))
def _fwd_call(x, dt, A, B, C, D, plan: _Plan, interp):
    Bt, T, HP = x.shape
    N, H, L, per, W = B.shape[-1], plan.H, plan.chunk, plan.per, plan.width
    rows = L * per
    steps = T // rows
    wide = pl.BlockSpec((1, rows, W), lambda b, i, p: (b, i, p))
    every = lambda n: pl.BlockSpec((1, rows, n), lambda b, i, p: (b, i, 0))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(Bt, steps, HP // W),
        in_specs=[
            wide, every(H), pl.BlockSpec((1, H), lambda b, i, p: (0, 0)), every(N), every(N),
            pl.BlockSpec((1, W), lambda b, i, p: (0, p)),
        ],
        out_specs=[wide, pl.BlockSpec((1, 1, N, W), lambda b, i, p: (b, i, 0, p))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bt, steps, N, HP), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H // plan.hp, N, plan.tile), jnp.float32),
            pltpu.VMEM((per, L, L), jnp.float32),
            pltpu.VMEM((per, L, H), jnp.float32),
            pltpu.VMEM((per, H, L), jnp.float32),
        ],
        compiler_params=_params(interp),
        interpret=interp,
        name="ssd_fwd",
    )(x, dt, A, B, C, D)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _bwd_call(x, dt, A, B, C, D, starts, dy, plan: _Plan, interp):
    Bt, T, HP = x.shape
    N, H, L, per, W = B.shape[-1], plan.H, plan.chunk, plan.per, plan.width
    rows = L * per
    steps = T // rows
    wide = pl.BlockSpec((1, rows, W), lambda b, i, p: (b, steps - 1 - i, p))
    every = lambda n: pl.BlockSpec((1, rows, n), lambda b, i, p: (b, steps - 1 - i, 0))  # noqa: E731
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(Bt, steps, HP // W),
        in_specs=[
            wide, every(H), pl.BlockSpec((1, H), lambda b, i, p: (0, 0)), every(N), every(N),
            pl.BlockSpec((1, W), lambda b, i, p: (0, p)), wide,
            pl.BlockSpec((1, 1, N, W), lambda b, i, p: (b, steps - 1 - i, 0, p)),
        ],
        out_specs=[
            wide, every(H), every(N), every(N),
            pl.BlockSpec((1, 1, 1, H), lambda b, i, p: (b, steps - 1 - i, 0, 0)),
            pl.BlockSpec((1, 1, 1, W), lambda b, i, p: (b, steps - 1 - i, 0, p)),
        ],
        out_shape=[
            like(x), like(dt), like(B), like(C),
            jax.ShapeDtypeStruct((Bt, steps, 1, H), jnp.float32),
            jax.ShapeDtypeStruct((Bt, steps, 1, HP), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H // plan.hp, N, plan.tile), jnp.float32),
            pltpu.VMEM((per + 1, N, plan.tile), jnp.float32),
            pltpu.VMEM((per, L, L), jnp.float32),
            pltpu.VMEM((per, L, H), jnp.float32),
            pltpu.VMEM((per, H, L), jnp.float32),
            pltpu.VMEM((per, L, L), jnp.float32),
            *[pltpu.VMEM((per, L, N), jnp.float32)] * 2,
            *[pltpu.VMEM((per, L, H), jnp.float32)] * 2,
        ],
        compiler_params=_params(interp),
        interpret=interp,
        name="ssd_bwd",
    )(x, dt, A, B, C, D, dy, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_chunked(x, dt, A, B, C, D, plan, interp):
    return _fwd_call(x, dt, A, B, C, D, plan, interp)[0]


def _ssd_fwd(x, dt, A, B, C, D, plan, interp):
    y, starts = _fwd_call(x, dt, A, B, C, D, plan, interp)
    return y, (x, dt, A, B, C, D, starts)


def _ssd_bwd(plan, interp, res, dy):
    x, dt, A, B, C, D, starts = res
    dx, ddt, dB, dC, dA, dD = _bwd_call(x, dt, A, B, C, D, starts, dy, plan, interp)
    return dx, ddt, dA.sum(axis=(0, 1)), dB, dC, dD.sum(axis=(0, 1))


_ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """The state-space recurrence over ``x [B, T, H P]`` (head ``h`` is
    channels ``h P .. (h + 1) P``), the step sizes ``dt [B, T, H]`` (``> 0``:
    after the softplus), the decay rates ``A [H]`` (``< 0``), ``B`` and ``C``
    ``[B, T, N]`` shared by the heads and the skip ``D [H]``, from a zero
    state: ``y [B, T, H P]`` in ``x``'s dtype.  ``dt``, ``A`` and ``D`` are
    taken in float32 whatever they come in.  Differentiable in all six.
    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret` (site
    ``"ssd"``)."""
    Bt, T, HP = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    if (
        HP % H or dt.shape != (Bt, T, H) or B.shape != (Bt, T, N) or C.shape != B.shape
        or A.shape != (H,) or D.shape != (H,) or B.dtype != x.dtype or C.dtype != x.dtype
    ):
        raise ValueError(f"ssd shapes: x {x.shape} dt {dt.shape} A {A.shape} B {B.shape} C {C.shape} D {D.shape}")
    interp = resolve_interpret(interpret, "ssd")
    chunk, per, padded = chunk_plan(T)
    plan = _Plan.of(H, HP // H, chunk, per, interp)
    if not interp and N % _LANES:
        raise ValueError(
            f"ssd through Mosaic takes whole lane tiles: the state's {N} channels must be a multiple of {_LANES}"
        )
    metrics = default_registry()
    metrics.gauge("ssd.chunk", chunk)
    metrics.gauge("ssd.tiles", Bt * H * (padded // chunk))
    metrics.gauge("ssd.padded_rows", padded - T)
    args = [x, dt.astype(jnp.float32), B, C]
    if padded != T:     # a padded step (dt = 0) forgets nothing and writes nothing
        args = [jnp.pad(a, ((0, 0), (0, padded - T), (0, 0))) for a in args]
    x, dt, B, C = args
    A = A.astype(jnp.float32).reshape(1, H)
    D = jnp.repeat(D.astype(jnp.float32), HP // H).reshape(1, HP)
    return _ssd_chunked(x, dt, A, B, C, D, plan, interp)[:, :T]


def chunk_decay_floor(dt: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    """The smallest decay any chunk of any head lays on the state it was
    handed, ``min exp(sum over the chunk of dt A)``, for the ``dt [B, T, H]``
    and ``A [H]`` of one :func:`ssd` call at the chunk it takes: a float32
    scalar, no gradient.  One small reduction over ``dt``."""
    Bt, T, H = dt.shape
    chunk, _, padded = chunk_plan(T)
    a = lax.stop_gradient(dt.astype(jnp.float32) * A.astype(jnp.float32))
    a = jnp.pad(a, ((0, 0), (0, padded - T), (0, 0))).reshape(Bt, padded // chunk, chunk, H)
    return jnp.exp(jnp.min(jnp.sum(a, axis=2)))
