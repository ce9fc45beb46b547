"""Mamba-1's selective scan as Pallas TPU kernels: a diagonal recurrence with a
decay of its own for every channel and every state.

For ``C`` channels and ``N`` states, with a step size ``dt_t[c] > 0`` a
channel, decay rates ``A[c, n] < 0`` and write and read directions ``B_t``,
``C_t`` of ``N`` numbers **shared by every channel**, the state ``H`` (``[C,
N]``, zero at the start) follows

    H_t[c, n] = exp(dt_t[c] A[c, n]) H_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n C_t[n] H_t[c, n] + D[c] x_t[c]

(docs/PHI4_FLASH.md).  The decay differs in both axes of the state at every
step, so a chunk of it is no matrix product (``ops/ssd.py``'s decay is a
scalar a head, ``ops/kda.py``'s rank-one in the state's other axis): there is
no head and nothing for the MXU in the recurrence.  It is walked **a step at
a time** on the VPU, every exponent ``dt A <= 0``: nothing overflows however
fast a channel forgets, and there is no chunk-parallel form whose split
exponential would bound the chunk.

**Layout.**  Channels on lanes, states on sublanes: the state of a block of
``W`` channels is ``[N, W]`` float32 (``N = 16``, ``W = 512``: eight
registers, four independent chains a step).  ``x`` and ``dt`` of a step are a
row ``[1, W]`` spread over the sublanes.  ``B_t`` and ``C_t`` come with ``N``
on lanes (``[T, N]``) and are needed with ``N`` on sublanes, the same in every
lane: once a grid step's rows, at its first channel block, they are spread
into scratch ``[rows, N, 128]`` by a product with a constant one-hot matrix
(``B[t0 : t0 + g]^T E``: the MXU's only work here, exact in bfloat16, shared
by all channel blocks).  ``y_t`` is a sum over sublanes.

**Grid** ``(B, T / rows, C / W)``, the channel blocks innermost: ``B`` and
``C`` of a block of rows stay in VMEM while the grid walks the channels, and
each channel block's state waits in scratch for the next block of rows.  The
forward kernel writes the state every grid step starts from (``[B, T / rows,
N, C]`` float32).

**Backward.**  The grid steps from the last to the first.  A grid step walks
its rows forward again from the saved state, keeping every ``H_t`` in VMEM
(``[rows + 1, N, W]`` float32), then backward with ``G = a_{t+1} dH_{t+1}``
carried (``a_t = exp(dt_t A)``, ``g = dy``):

    dH_t = G + g_t C_t                      dC_t[n] = sum_c g_t[c] H_t[c, n]
    dB_t[n] = sum_c dH_t[c, n] dt_t[c] x_t[c]
    s_t[c]  = sum_n dH_t[c, n] B_t[n]       W_t = dH_t a_t H_{t-1}
    dx_t  = dt_t s_t + D g_t                ddt_t = x_t s_t + sum_n A W_t
    dA   += dt_t W_t                        dD   += g_t x_t

``dB`` and ``dC`` are sums over all channels: each step adds its lanes'
products into scratch ``[rows, N, 128]`` (lane tile on lane tile, on the VPU)
while the grid walks the channel blocks, and at the last block the 128 lanes
are summed and turned back to ``[rows, N]`` by the transposed one-hot product.
``dA`` and ``dD`` come out as one partial a grid step, summed by XLA.

**What is float32.**  ``dt``, ``A``, ``D``, the state, every exponential and
every sum over time; ``x``, ``B``, ``C`` and ``dy`` are read in their own dtype
(bfloat16 in a bf16 model) and ``y``, ``dx``, ``dB``, ``dC`` written in it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret
from adapcc_tpu.utils.observability import default_registry

_LANES = 128    # a lane tile
_ROWS = 256     # rows of a grid step at most: the backward kernel keeps a state a row in VMEM
_TILES = 4      # lane tiles of a grid step at most: W = 512 channels, an [N, W] state in eight registers

_NT = ((1,), (1,))
_TN = ((0,), (0,))


class _Plan(NamedTuple):
    """The blocks of one call."""

    rows: int       # steps of a grid step
    group: int      # steps walked between two loads of x and dt and two stores of y: a sublane tile of the dtype
    width: int      # W: channels of a grid step, whole lane tiles
    N: int

    @property
    def tiles(self) -> int:
        return self.width // _LANES


def plan_for(T: int, C: int, N: int, dtype) -> "tuple[_Plan, int, int]":
    """``(plan, padded T, padded C)``: rows in whole sublane tiles of
    ``dtype`` (8 float32, 16 bfloat16), :data:`_ROWS` at most; channels in
    whole lane tiles, as many to a grid step as divide them, :data:`_TILES` at
    most."""
    group = 8 * 4 // jnp.dtype(dtype).itemsize
    rows = min(_ROWS, -(-T // group) * group)
    tiles = -(-C // _LANES)
    per = max(n for n in range(1, _TILES + 1) if tiles % n == 0)
    return _Plan(rows=rows, group=group, width=per * _LANES, N=N), -(-T // rows) * rows, tiles * _LANES


def _hot(group: int):
    """``E [group, group * 128]``: 1 where the column's lane tile is the row."""
    row = lax.broadcasted_iota(jnp.int32, (group, group * _LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (group, group * _LANES), 1)
    return (col // _LANES == row).astype(jnp.float32)


def _hi(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


def _spread(ref, out, plan: _Plan) -> None:
    """``ref[0] [rows, N]`` into ``out [rows, N, 128]``: row ``t`` with ``N``
    on sublanes, the same in every lane."""
    hot = _hot(plan.group)

    def some(g, _):
        r = pl.multiple_of(g * plan.group, plan.group)
        wide = _hi(ref[0, pl.ds(r, plan.group), :].astype(jnp.float32), hot, _TN)     # [N, group * 128]
        for i in range(plan.group):
            out[r + i] = wide[:, i * _LANES:(i + 1) * _LANES]
        return 0

    lax.fori_loop(0, plan.rows // plan.group, some, 0)


def _gather(acc, ref, plan: _Plan) -> None:
    """``acc [rows, N, 128]`` summed over its lanes into ``ref[0] [rows, N]``:
    :func:`_spread` the other way round."""
    hot = _hot(plan.group)

    def some(g, _):
        r = pl.multiple_of(g * plan.group, plan.group)
        wide = jnp.concatenate([acc[r + i] for i in range(plan.group)], axis=1)         # [N, group * 128]
        ref[0, pl.ds(r, plan.group), :] = _hi(hot, wide, _NT).astype(ref.dtype)
        return 0

    lax.fori_loop(0, plan.rows // plan.group, some, 0)


def _lanes(tile, plan: _Plan):
    """``[N, 128]`` over the ``W`` lanes of a grid step."""
    return tile if plan.tiles == 1 else jnp.concatenate([tile] * plan.tiles, axis=1)


def _fold(wide, plan: _Plan):
    """``[N, W]`` summed lane tile on lane tile: ``[N, 128]``."""
    out = wide[:, :_LANES]
    for j in range(1, plan.tiles):
        out = out + wide[:, j * _LANES:(j + 1) * _LANES]
    return out


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, state, bb, cb, rowbuf, *, plan: _Plan):
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _spread(b_ref, bb, plan)
        _spread(c_ref, cb, plan)

    A, skip = a_ref[...], d_ref[...]
    H = jnp.where(pl.program_id(1) == 0, 0.0, state[p])
    start_ref[0, 0] = H

    def some(g, H):
        r = pl.multiple_of(g * plan.group, plan.group)
        xs = x_ref[0, pl.ds(r, plan.group), :].astype(jnp.float32)
        dts = dt_ref[0, pl.ds(r, plan.group), :]
        for i in range(plan.group):
            x, dt = xs[i:i + 1], dts[i:i + 1]
            H = jnp.exp(dt * A) * H + (dt * x) * _lanes(bb[r + i], plan)
            rowbuf[i:i + 1, :] = jnp.sum(H * _lanes(cb[r + i], plan), axis=0, keepdims=True) + skip * x
        y_ref[0, pl.ds(r, plan.group), :] = rowbuf[...].astype(y_ref.dtype)
        return H

    state[p] = lax.fori_loop(0, plan.rows // plan.group, some, H)


def _bwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, start_ref,
    dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
    dstate, hs, bb, cb, dbb, dcb, dxbuf, ddtbuf, *, plan: _Plan,
):
    p = pl.program_id(2)
    groups = plan.rows // plan.group

    @pl.when(p == 0)
    def _():
        _spread(b_ref, bb, plan)
        _spread(c_ref, cb, plan)
        dbb[...] = jnp.zeros(dbb.shape, dbb.dtype)
        dcb[...] = jnp.zeros(dcb.shape, dcb.dtype)

    A, skip = a_ref[...], d_ref[...]

    # every state of the grid step's rows again, forward: hs[t] is the state before row t, hs[t + 1] after it
    hs[0] = start_ref[0, 0]

    def again(g, H):
        r = pl.multiple_of(g * plan.group, plan.group)
        xs = x_ref[0, pl.ds(r, plan.group), :].astype(jnp.float32)
        dts = dt_ref[0, pl.ds(r, plan.group), :]
        for i in range(plan.group):
            x, dt = xs[i:i + 1], dts[i:i + 1]
            H = jnp.exp(dt * A) * H + (dt * x) * _lanes(bb[r + i], plan)
            hs[r + i + 1] = H
        return H

    lax.fori_loop(0, groups, again, hs[0])

    def back(k, carry):
        G, H, dA, dD = carry                  # H: the state after the row walked next
        r = pl.multiple_of((groups - 1 - k) * plan.group, plan.group)
        xs = x_ref[0, pl.ds(r, plan.group), :].astype(jnp.float32)
        dts = dt_ref[0, pl.ds(r, plan.group), :]
        dys = dy_ref[0, pl.ds(r, plan.group), :].astype(jnp.float32)
        for i in reversed(range(plan.group)):
            x, dt, g = xs[i:i + 1], dts[i:i + 1], dys[i:i + 1]
            before = hs[r + i]
            dH = G + g * _lanes(cb[r + i], plan)
            dcb[r + i] += _fold(g * H, plan)
            dbb[r + i] += _fold(dH * (dt * x), plan)
            s = jnp.sum(dH * _lanes(bb[r + i], plan), axis=0, keepdims=True)
            a = jnp.exp(dt * A)
            G = a * dH
            kept = G * before                 # dH a H_{t-1}
            dxbuf[i:i + 1, :] = dt * s + skip * g
            ddtbuf[i:i + 1, :] = x * s + jnp.sum(kept * A, axis=0, keepdims=True)
            dA, dD, H = dA + dt * kept, dD + g * x, before
        dx_ref[0, pl.ds(r, plan.group), :] = dxbuf[...].astype(dx_ref.dtype)
        ddt_ref[0, pl.ds(r, plan.group), :] = ddtbuf[...]
        return G, H, dA, dD

    G = jnp.where(pl.program_id(1) == 0, 0.0, dstate[p])      # the grid's first step is the sequence's last rows
    zero = jnp.zeros_like(A)
    G, _, dA, dD = lax.fori_loop(0, groups, back, (G, hs[plan.rows], zero, zero[:1]))
    dstate[p] = G
    da_ref[0, 0] = dA
    dd_ref[0, 0] = dD

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        _gather(dbb, db_ref, plan)
        _gather(dcb, dc_ref, plan)


def _params(interp):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=None if interp else 96 * 2**20,
    )


# behind jax.jit, as the other kernels are: a model's layers share one traced kernel
@functools.partial(jax.jit, static_argnums=(6, 7))
def _fwd_call(x, dt, A, B, C, D, plan: _Plan, interp):
    Bt, T, Cp = x.shape
    N, rows, W = plan.N, plan.rows, plan.width
    steps = T // rows
    wide = pl.BlockSpec((1, rows, W), lambda b, i, p: (b, i, p))
    every = pl.BlockSpec((1, rows, N), lambda b, i, p: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(Bt, steps, Cp // W),
        in_specs=[
            wide, wide, pl.BlockSpec((N, W), lambda b, i, p: (0, p)), every, every,
            pl.BlockSpec((1, W), lambda b, i, p: (0, p)),
        ],
        out_specs=[wide, pl.BlockSpec((1, 1, N, W), lambda b, i, p: (b, i, 0, p))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bt, steps, N, Cp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Cp // W, N, W), jnp.float32),
            *[pltpu.VMEM((rows, N, _LANES), jnp.float32)] * 2,
            pltpu.VMEM((plan.group, W), jnp.float32),
        ],
        compiler_params=_params(interp),
        interpret=interp,
        name="sscan_fwd",
    )(x, dt, A, B, C, D)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _bwd_call(x, dt, A, B, C, D, starts, dy, plan: _Plan, interp):
    Bt, T, Cp = x.shape
    N, rows, W = plan.N, plan.rows, plan.width
    steps = T // rows
    wide = pl.BlockSpec((1, rows, W), lambda b, i, p: (b, steps - 1 - i, p))
    every = pl.BlockSpec((1, rows, N), lambda b, i, p: (b, steps - 1 - i, 0))
    state = pl.BlockSpec((1, 1, N, W), lambda b, i, p: (b, steps - 1 - i, 0, p))
    row = pl.BlockSpec((1, 1, 1, W), lambda b, i, p: (b, steps - 1 - i, 0, p))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(Bt, steps, Cp // W),
        in_specs=[
            wide, wide, pl.BlockSpec((N, W), lambda b, i, p: (0, p)), every, every,
            pl.BlockSpec((1, W), lambda b, i, p: (0, p)), wide, state,
        ],
        out_specs=[wide, wide, every, every, state, row],
        out_shape=[
            like(x), like(dt), like(B), like(C),
            jax.ShapeDtypeStruct((Bt, steps, N, Cp), jnp.float32),
            jax.ShapeDtypeStruct((Bt, steps, 1, Cp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Cp // W, N, W), jnp.float32),
            pltpu.VMEM((rows + 1, N, W), jnp.float32),
            *[pltpu.VMEM((rows, N, _LANES), jnp.float32)] * 4,
            *[pltpu.VMEM((plan.group, W), jnp.float32)] * 2,
        ],
        compiler_params=_params(interp),
        interpret=interp,
        name="sscan_bwd",
    )(x, dt, A, B, C, D, dy, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, A, B, C, D, plan, interp):
    return _fwd_call(x, dt, A, B, C, D, plan, interp)[0]


def _scan_fwd(x, dt, A, B, C, D, plan, interp):
    y, starts = _fwd_call(x, dt, A, B, C, D, plan, interp)
    return y, (x, dt, A, B, C, D, starts)


def _scan_bwd(plan, interp, res, dy):
    x, dt, A, B, C, D, starts = res
    dx, ddt, dB, dC, dA, dD = _bwd_call(x, dt, A, B, C, D, starts, dy, plan, interp)
    return dx, ddt, dA.sum(axis=(0, 1)), dB, dC, dD.sum(axis=(0, 1))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """The selective recurrence over ``x [B, T, C]``, the step sizes ``dt [B,
    T, C]`` (``> 0``: after the softplus), the decay rates ``A [C, N]`` (``<
    0``), ``B`` and ``C`` ``[B, T, N]`` shared by the channels and the skip ``D
    [C]``, each row of the batch from a zero state: ``y [B, T, C]`` in ``x``'s
    dtype.  ``dt``, ``A`` and ``D`` are taken in float32 whatever they come in
    (and ``dt``'s gradient is float32).  Differentiable in all six.  Any ``T``
    and ``C``: rows are padded to the block (a padded step has ``dt = 0``: it
    forgets nothing and writes nothing) and channels to whole lane tiles.
    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret` (site
    ``"selective_scan"``)."""
    Bt, T, Ch = x.shape
    N = B.shape[-1]
    if (
        dt.shape != x.shape or B.shape != (Bt, T, N) or C.shape != B.shape or A.shape != (Ch, N)
        or D.shape != (Ch,) or B.dtype != x.dtype or C.dtype != x.dtype
    ):
        raise ValueError(
            f"selective_scan shapes: x {x.shape} dt {dt.shape} A {A.shape} B {B.shape} C {C.shape} D {D.shape}"
        )
    interp = resolve_interpret(interpret, "selective_scan")
    plan, Tp, Cp = plan_for(T, Ch, N, x.dtype)
    metrics = default_registry()
    metrics.gauge("sscan.chunk", plan.rows)
    metrics.gauge("sscan.tiles", Bt * (Tp // plan.rows) * (Cp // plan.width))
    metrics.gauge("sscan.padded_rows", Tp - T)
    metrics.gauge("sscan.lane_block", plan.width)
    rows, lanes = (0, Tp - T), (0, Cp - Ch)
    pad = lambda a, *widths: jnp.pad(a, widths) if any(w != (0, 0) for w in widths) else a  # noqa: E731
    y = _scan(
        pad(x, (0, 0), rows, lanes), pad(dt.astype(jnp.float32), (0, 0), rows, lanes),
        pad(A.astype(jnp.float32).T, (0, 0), lanes), pad(B, (0, 0), rows, (0, 0)), pad(C, (0, 0), rows, (0, 0)),
        pad(D.astype(jnp.float32).reshape(1, Ch), (0, 0), lanes), plan, interp,
    )
    return y[:, :T, :Ch]
