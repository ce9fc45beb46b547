"""The short causal convolution in front of a scan, with its bias and its
silu, as one Pallas TPU kernel each way.

For ``x [B, T, C]``, taps ``[K, C]`` (``K`` small: 4 in every published
configuration here) and an optional bias ``[C]``,

    pre_t[c] = sum_j taps[j, c] x_{t - (K - 1) + j}[c]  (+ bias[c])
    y_t[c]   = silu(pre_t[c]) = pre_t[c] sigmoid(pre_t[c])

depthwise (a channel sees only itself), causal, zeros before the sequence, no
reset anywhere inside a row of the batch (a packed row's joins are crossed,
as the references cross them).  Kimi-Linear's KDA mixer runs it over q, k and
v (no bias; q and k with the norm below), Granite's Mamba-2 mixer over ``x``,
``B`` and ``C`` together and Phi-4-flash's Mamba-1 mixer over ``x`` (both
biased).  A fourth user, LFM2's ``conv`` layers, has no scan behind the
convolution and no silu: there it is the mixer itself, gated before and after
(:func:`gated_short_conv`, at the end of this file, over the same walk).
Written in
``jax.numpy`` it is a zero-padded float32 copy of ``x``, ``K`` shifted slices,
a sum and three roundings, none of which XLA can fuse across the scan's
``pallas_call``, and four more float32 passes for its derivative; here each
direction reads its arrays once and writes its result once.

**Layout.**  Channels on lanes, time on sublanes.  A grid step owns ``rows``
steps of ``width`` channels (:func:`plan_for`: about a million elements) and
walks them :data:`_CHUNK` lanes at a time, down the rows in turns of
:data:`_TURN` groups of sixteen (a bfloat16 tile, two float32 ones), all in
registers: ``x_{t - s}`` of an ``[8, lanes]`` slab is the slab and the one
before it, selected by row and rotated by ``s`` sublanes (the XLU); nothing is
padded and no float32 array leaves VMEM.  The VPU binds both kernels, not the
HBM: a turn is written out because the next one starts only when it ends, and
a block is long because the walk down a chunk of lanes starts and ends once a
block.  The sigmoid is ``(1 + tanh(pre / 2)) / 2``: one transcendental, no
division.  The taps and the bias reach the kernels as one float32 array ``[8,
C]`` (the taps in rows ``0 .. K - 1``, the bias in row ``K``): one sublane
tile, and two operands where there would be three.

**Forward** (``short_conv_fwd``; grid ``(B, C / width, T / rows)``, rows
innermost).  The last eight rows of a grid step's ``x`` wait in scratch for
the next block of rows (zeros before the first): the halo.  The sum, the bias
and the silu are float32; ``y`` is rounded once, to ``x``'s dtype.

**Backward** (``short_conv_bwd``; the same grid, the blocks of rows from the
last to the first).  The residuals are ``x`` and the taps' array; ``pre`` is
formed again in registers.  With ``dpre = dy silu'(pre)``,

    dx_t        = sum_j taps[j] dpre_{t + (K - 1) - j}
    dtaps[j, c] = sum_t dpre_t[c] x_{t - (K - 1) + j}[c]      dbias[c] = sum_t dpre_t[c]

``dx`` wants ``dpre`` of up to ``K - 1`` later rows: walking backward, the
first eight rows of ``dpre`` of the block after wait in scratch.  ``pre``
wants ``x`` of up to ``K - 1`` earlier rows, which that walk has not met:
they come as a second, sixteen-row block of the same array (``x``'s rows just
before the grid step's; the one extra read, 16 / rows of ``x``).  The taps'
and the bias's gradients are summed in float32, a sublane tile a tap in
registers along a grid step, then into one output block ``[8, width]`` a row
of the batch that the grid revisits along the rows (hence rows innermost and
``arbitrary``).

**The norm** (``norm_heads = H``: Kimi-Linear's q and k, whose published
mixer is ``l2norm(silu(conv(x W)))``).  With a head the ``D = C / H`` channels
``h D … (h + 1) D`` and ``s_t[h]`` the sum of ``y_t[c]^2`` over them,

    r_t[h] = rsqrt(s_t[h] + eps)        n_t[c] = y_t[c] r_t[h]

and the kernel's result is ``n``, from the float32 ``y`` a slab holds and
rounded once: a square, one lane reduction a head (a head of several lane
tiles adds its tiles first), a root on an ``[8, 1]`` column and a product,
where XLA ran five passes over ``[T, C]`` and three products with the heads'
0/1 matrix around the ``pallas_call`` it could not fuse into.  The norm is
local to a row and a head, so the halos are what they were.  Backward, the
kernel forms ``y``, ``r`` and ``n`` again beside ``pre`` and turns the
incoming cotangent ``dn`` into

    dy_t[c] = r_t[h] (dn_t[c] - n_t[c] sum over the head of dn_t n_t)

in the same slab, before ``dpre = dy silu'(pre)``; neither ``y`` nor a
statistic is kept for it.  A chunk of lanes and a grid step hold whole heads
(:func:`plan_for`); through Mosaic a head is whole lane tiles.  A head's
reduction, root and spread are one dependent chain through the XLU and the
EUP, and a turn of the inner loop is all the scheduler sees at once: under a
norm a turn writes out :data:`_NORM_TURN` groups, so that other slabs' work
fills the chain's waits.  Without a norm both kernels trace to what they
traced to before they knew one.

Operands / results: forward 2 -> 1, backward 4 -> 2 (``x``, its halo, ``dy``,
the taps' array -> ``dx`` and the taps' array's gradient): none of the
signatures ``chipbench/trace_reduce.flash_kernel`` knows a flash kernel by.

**The gated form** (``gated_conv_fwd`` / ``gated_conv_bwd``;
:func:`gated_short_conv`): ``y = C * conv(B * x)`` over a projection's output
``[B | C | x]`` as it stands, no bias, no activation.  The thirds come as
three ``BlockSpec``s on the one array at lane-block offsets 0, ``C / width``
and ``2 C / width``, so nothing is sliced or copied; ``z = B * x`` is formed on
the slab and stands where ``x`` stood in the walk above (the forward's halo
scratch keeps ``z``'s last eight rows; the backward's second, sixteen-row
block comes twice, for ``B`` and for ``x``); ``C`` multiplies the float32 sum
before the one rounding.  Backward, with ``dc = dy * C`` in ``dpre``'s place
(there is no ``silu'``): ``c`` is formed again, ``dC = dy * c``, ``dz`` is the
walk's ``dx``, ``dB = dz * x``, ``dx = dz * B``, the taps' gradient ``sum_t
dc_t z_{t - (K - 1) + j}``.  The three gradients are written as the thirds of
ONE array, which the projection's two gradient products read in place: the
backward kernel's grid step holds every channel and as many fewer rows
(:func:`_whole_width`), its one output block the rows of all ``3 C``.
Operands / results: forward 4 -> 1, backward 7 -> 2: again none of the flash
kernels' signatures.  Without gates the two kernels above trace to what they
traced to before this form came.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret
from adapcc_tpu.utils.observability import default_registry

_LANES = 128    # a lane tile
_SLAB = 8       # a float32 sublane tile: what a shift is formed on
_GROUP = 16     # rows loaded and stored at once: a bfloat16 sublane tile, two slabs
_CHUNK = 256    # lanes walked at once: every carried array two registers a slab
_TURN = 2       # groups written out in one turn of the inner loop (Mosaic unrolls no loop in part)
# and under a norm: a head's lane reduction, root and spread are one long chain of the XLU's and the EUP's, and only
# what stands written out in one turn can fill its waits (two groups: 0.63 ms a call forward at 4,096 channels; eight: 0.30)
_NORM_TURN = 8

# a grid step at most: rows (long: the walk down a chunk of lanes starts and ends once a block; 0.37 -> 0.30 ms a
# call forward from 512 rows to 4,096 at 4,352 channels), lane tiles, and elements of the two together (two
# megabytes of bfloat16 a block; the backward kernel holds three, twice)
_ROWS = 4096
_TILES = 8
_BLOCK = 1 << 20


def _unit(head: Optional[int]) -> int:
    """The fewest lanes that are whole lane tiles and, under a norm over
    heads of ``head`` channels, whole heads (128 for a head of 128 or of 16,
    256 for one of 256, 384 for one of 48)."""
    return _LANES if head is None else math.lcm(head, _LANES)


class _Plan(NamedTuple):
    """The blocks of one call."""

    rows: int       # steps of a grid step, whole groups
    width: int      # channels of a grid step, whole lane tiles
    K: int
    biased: bool
    head: Optional[int] = None      # channels of a head whose L2 norm the kernels apply to ``y``; None: no norm
    eps: float = 0.0                # under that norm's root

    @property
    def tiles(self) -> int:
        return self.width // _LANES

    @property
    def per(self) -> int:
        """Groups written out in one turn of the inner loop."""
        return _TURN if self.head is None else _NORM_TURN

    @property
    def chunk(self) -> int:
        """Lanes walked at once: :data:`_CHUNK` where it divides the grid
        step's (and is whole heads under a norm), else a lane tile, or the
        lane tiles that hold whole heads."""
        unit = _unit(self.head)
        return _CHUNK if self.width % _CHUNK == 0 and _CHUNK % unit == 0 else unit


def plan_for(
    T: int, C: int, K: int = 4, biased: bool = False, head: Optional[int] = None, eps: float = 0.0
) -> "tuple[_Plan, int, int]":
    """``(plan, padded T, padded C)``: channels in whole lane tiles, as many
    to a grid step as divide them, :data:`_TILES` at most (4,352 channels are
    34 lane tiles: 2 a grid step; 4,096 and 5,120 take 8); ``T`` in as few
    equal blocks of rows as :data:`_ROWS` and :data:`_BLOCK` allow, each
    whole turns of the inner loop (T = 8,192: two blocks of 4,096 rows at
    256 channels, eight of 1,024 at 1,024).  Under a norm over heads of
    ``head`` channels :func:`_unit` stands in a lane tile's place, so no head
    lies across two grid steps or two chunks of lanes."""
    unit = _unit(head)
    units = -(-C // unit)
    width = unit * max(n for n in range(1, max(_TILES * _LANES // unit, 1) + 1) if units % n == 0)
    steps = -(-T // min(_ROWS, _BLOCK // width))
    plan = _Plan(rows=0, width=width, K=K, biased=biased, head=head, eps=eps)
    turn = _GROUP * plan.per
    rows = -(-T // (steps * turn)) * turn
    return plan._replace(rows=rows), -(-T // rows) * rows, units * unit


def _sigmoid(a):
    """Through ``tanh``: one transcendental and no division (0.40 ms a call forward for 0.46 at 4,352 channels)."""
    return 0.5 * jnp.tanh(0.5 * a) + 0.5


def _down(before, cur, s: int, row):
    """``cur`` shifted ``s`` rows later: row ``t`` holds ``cur[t - s]``, and
    ``before[8 + t - s]`` where that is above the slab."""
    return cur if s == 0 else pltpu.roll(jnp.where(row >= _SLAB - s, before, cur), s, 0)


def _up(cur, after, s: int, row):
    """``cur`` shifted ``s`` rows earlier: row ``t`` holds ``cur[t + s]``, and
    ``after[t + s - 8]`` where that is under the slab."""
    return cur if s == 0 else pltpu.roll(jnp.where(row < s, after, cur), _SLAB - s, 0)


def _weights(w_ref, lanes, plan: _Plan):
    """The taps and the bias of a chunk of lanes, each spread over a slab."""
    w = w_ref[:, lanes]
    spread = lambda j: jnp.broadcast_to(w[j:j + 1], (_SLAB, w.shape[1]))  # noqa: E731
    return [spread(j) for j in range(plan.K)], spread(plan.K) if plan.biased else None


def _pre(taps, bias, shifted):
    """``sum_s taps[K - 1 - s] x_{t - s} + bias`` from the shifted slabs."""
    K = len(taps)
    acc = taps[K - 1] * shifted[0]
    for s in range(1, K):
        acc = acc + taps[K - 1 - s] * shifted[s]
    return acc if bias is None else acc + bias


def _head_sum(a):
    """``a [8, a head's lanes]`` summed over the lanes: ``[8, 1]``, one lane
    reduction (a head of several lane tiles adds its tiles first)."""
    if a.shape[1] % _LANES == 0:
        a = functools.reduce(operator.add, [a[:, i:i + _LANES] for i in range(0, a.shape[1], _LANES)])
    return jnp.sum(a, axis=1, keepdims=True)


def _by_head(fn, plan: _Plan, *slabs):
    """``fn`` on each head's lanes of the ``[8, lanes]`` slabs, the results side by side again."""
    lanes = slabs[0].shape[1]
    out = [fn(*(a[:, h:h + plan.head] for a in slabs)) for h in range(0, lanes, plan.head)]
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _normed(y, plan: _Plan):
    """``n = y r``, ``r = rsqrt(sum over the head of y^2 + eps)``."""
    return _by_head(lambda y: y * lax.rsqrt(_head_sum(y * y) + plan.eps), plan, y)


def _norm_pulled(y, dn, plan: _Plan):
    """``dy = r (dn - n sum over the head of (dn n))``: ``n``'s cotangent back
    through the norm, ``r`` and ``n`` formed again from ``y``."""

    def pull(y, dn):
        r = lax.rsqrt(_head_sum(y * y) + plan.eps)
        n = y * r
        return r * (dn - n * _head_sum(dn * n))

    return _by_head(pull, plan, y, dn)


def _fwd_kernel(x_ref, w_ref, y_ref, tail, *, plan: _Plan):
    K, chunk, per = plan.K, plan.chunk, plan.per
    first = pl.program_id(2) == 0
    row = lax.broadcasted_iota(jnp.int32, (_SLAB, chunk), 0)

    def some_lanes(c, _):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        taps, bias = _weights(w_ref, lanes, plan)

        def some_rows(k, before):
            for u in range(per):
                rows = pl.ds(pl.multiple_of((k * per + u) * _GROUP, _GROUP), _GROUP)
                xs = x_ref[0, rows, lanes].astype(jnp.float32)
                out = []
                for cur in (xs[:_SLAB], xs[_SLAB:]):
                    pre = _pre(taps, bias, [_down(before, cur, s, row) for s in range(K)])
                    y = pre * _sigmoid(pre)
                    out.append(y if plan.head is None else _normed(y, plan))
                    before = cur
                y_ref[0, rows, lanes] = jnp.concatenate(out, axis=0).astype(y_ref.dtype)
            return before

        before = jnp.where(first, 0.0, tail[:, lanes])
        tail[:, lanes] = lax.fori_loop(0, plan.rows // (_GROUP * per), some_rows, before)
        return 0

    lax.fori_loop(0, plan.width // chunk, some_lanes, 0)


def _bwd_kernel(x_ref, halo_ref, dy_ref, w_ref, dx_ref, dw_ref, head, *, plan: _Plan):
    K, chunk, per = plan.K, plan.chunk, plan.per
    groups = plan.rows // _GROUP
    i = pl.program_id(2)                        # the grid's first step is the sequence's last rows
    start = i == pl.num_programs(2) - 1         # this grid step holds the sequence's first rows
    row = lax.broadcasted_iota(jnp.int32, (_SLAB, chunk), 0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    def some_lanes(c, _):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        taps, bias = _weights(w_ref, lanes, plan)

        def slab(before, cur, dy, after, sums):
            """``dpre`` of the slab ``cur`` (``before``: the slab of ``x``
            above it; ``dy`` the cotangent of the kernel's result, ``n``'s
            under a norm), the sums with it added, and the slab's ``dx`` from
            its ``dpre`` and the slab's under it (``after``)."""
            shifted = [_down(before, cur, s, row) for s in range(K)]
            pre = _pre(taps, bias, shifted)
            sig = _sigmoid(pre)
            if plan.head is not None:
                dy = _norm_pulled(pre * sig, dy, plan)
            dpre = dy * (sig * (1.0 + pre * (1.0 - sig)))
            sums = [sums[j] + dpre * shifted[K - 1 - j] for j in range(K)] + [sums[K] + dpre]
            dx = taps[K - 1] * dpre
            for s in range(1, K):
                dx = dx + taps[K - 1 - s] * _up(dpre, after, s, row)
            return dpre, dx, sums

        def turn(first_group, above, after, sums):
            """``per`` groups of sixteen rows from ``first_group`` on, the last
            row first; ``above`` is the slab of ``x`` over them."""
            group = lambda g: pl.ds(pl.multiple_of((first_group + g) * _GROUP, _GROUP), _GROUP)  # noqa: E731
            xs = [x_ref[0, group(g), lanes].astype(jnp.float32) for g in range(per)]
            for g in reversed(range(per)):
                dys = dy_ref[0, group(g), lanes].astype(jnp.float32)
                lo, hi = xs[g][:_SLAB], xs[g][_SLAB:]
                after, dx_hi, sums = slab(lo, hi, dys[_SLAB:], after, sums)
                after, dx_lo, sums = slab(xs[g - 1][_SLAB:] if g else above, lo, dys[:_SLAB], after, sums)
                dx_ref[0, group(g), lanes] = jnp.concatenate([dx_lo, dx_hi], axis=0).astype(dx_ref.dtype)
            return after, sums

        def some_rows(k, carry):
            first_group = groups - (k + 1) * per
            above = x_ref[0, pl.ds(pl.multiple_of((first_group - 1) * _GROUP, _GROUP), _GROUP), lanes]
            return turn(first_group, above[_SLAB:].astype(jnp.float32), *carry)

        zero = jnp.zeros((_SLAB, chunk), jnp.float32)
        after = jnp.where(i == 0, 0.0, head[:, lanes])
        carry = lax.fori_loop(0, groups // per - 1, some_rows, (after, [zero] * (K + 1)))
        halo = jnp.where(start, 0.0, halo_ref[0, _SLAB:, lanes].astype(jnp.float32))
        after, sums = turn(0, halo, *carry)
        head[:, lanes] = after
        for j in range(K + 1 if plan.biased else K):
            dw_ref[0, j:j + 1, lanes] += jnp.sum(sums[j], axis=0, keepdims=True)
        return 0

    lax.fori_loop(0, plan.width // chunk, some_lanes, 0)


def _params(interp):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=None if interp else 64 * 2**20,
    )


# behind jax.jit, as the other kernels are: a model's layers share one traced kernel
@functools.partial(jax.jit, static_argnums=(2, 3))
def _fwd_call(x, w, plan: _Plan, interp):
    Bt, T, Cp = x.shape
    rows, W = plan.rows, plan.width
    block = pl.BlockSpec((1, rows, W), lambda b, p, i: (b, i, p))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(Bt, Cp // W, T // rows),
        in_specs=[block, pl.BlockSpec((w.shape[0], W), lambda b, p, i: (0, p))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_SLAB, W), jnp.float32)],
        compiler_params=_params(interp),
        interpret=interp,
        name="short_conv_fwd",
    )(x, w)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _bwd_call(x, w, dy, plan: _Plan, interp):
    Bt, T, Cp = x.shape
    rows, W = plan.rows, plan.width
    steps, per = T // rows, rows // _GROUP
    block = pl.BlockSpec((1, rows, W), lambda b, p, i: (b, steps - 1 - i, p))
    # the sixteen rows before the grid step's (its own first sixteen where it has none before: not read then)
    halo = pl.BlockSpec((1, _GROUP, W), lambda b, p, i: (b, jnp.maximum((steps - 1 - i) * per - 1, 0), p))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=(Bt, Cp // W, steps),
        in_specs=[block, halo, block, pl.BlockSpec((w.shape[0], W), lambda b, p, i: (0, p))],
        out_specs=[block, pl.BlockSpec((1, w.shape[0], W), lambda b, p, i: (b, 0, p))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bt, *w.shape), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((_SLAB, W), jnp.float32)],
        compiler_params=_params(interp),
        interpret=interp,
        name="short_conv_bwd",
    )(x, x, dy, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, w, plan, interp):
    return _fwd_call(x, w, plan, interp)


def _conv_fwd(x, w, plan, interp):
    return _fwd_call(x, w, plan, interp), (x, w)


def _conv_bwd(plan, interp, res, dy):
    x, w = res
    dx, dw = _bwd_call(x, w, dy, plan, interp)
    return dx, dw.sum(axis=0)


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv(
    x: jnp.ndarray,
    taps: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
    *,
    norm_heads: Optional[int] = None,
    norm_eps: float = 1e-6,
) -> jnp.ndarray:
    """``silu(sum_j taps[j] x_{t - (K - 1) + j} + bias)`` over ``x [B, T,
    C]`` with ``taps [K, C]`` and ``bias [C]`` or none, each row of the batch
    from zeros before its first step: ``[B, T, C]`` in ``x``'s dtype, summed
    in float32 and rounded once.  With ``norm_heads = H`` the result is that
    ``y`` times ``rsqrt(sum over a head of y^2 + norm_eps)``, head ``h`` the
    channels ``h C / H … (h + 1) C / H``, still float32 until the one
    rounding (what the mixer's mathematics says of this call: q and k are
    normed, v is not).  Differentiable in ``x``, ``taps`` and ``bias`` (the
    parameters' gradients summed in float32).  Any ``T`` and ``C``: rows are
    padded with zeros to whole blocks and channels to whole lane tiles (the
    published shapes are both already: no copy there).  Through Mosaic a
    normed head is whole lane tiles (``ValueError`` otherwise); the
    interpreter takes any head size.  ``interpret=None`` asks
    :func:`ops.kernel_mode.resolve_interpret` (site ``"short_conv"``)."""
    Bt, T, C = x.shape
    K = taps.shape[0]
    if taps.shape != (K, C) or not 1 <= K < _SLAB or (bias is not None and bias.shape != (C,)):
        raise ValueError(f"short_conv shapes: x {x.shape} taps {taps.shape} bias {None if bias is None else bias.shape}")
    if norm_heads is not None and (norm_heads < 1 or C % norm_heads):
        raise ValueError(f"short_conv shapes: {C} channels are no {norm_heads} heads")
    interp = resolve_interpret(interpret, "short_conv")
    head = None if norm_heads is None else C // norm_heads
    if head is not None and not interp and head % _LANES:
        raise ValueError(
            f"short_conv through Mosaic norms heads of whole lane tiles ({_LANES} channels): "
            f"x {x.shape} in {norm_heads} heads of {head}"
        )
    plan, Tp, Cp = plan_for(T, C, K, bias is not None, head, float(norm_eps) if head else 0.0)
    metrics = default_registry()
    metrics.incr("conv.calls")
    if head is not None:
        metrics.incr("conv.norm_calls")
    metrics.gauge("conv.block_rows", plan.rows)
    metrics.gauge("conv.lane_tiles", plan.tiles)
    w = taps.astype(jnp.float32)
    if bias is not None:
        w = jnp.concatenate([w, bias.astype(jnp.float32)[None]])
    w = jnp.pad(w, ((0, _SLAB - w.shape[0]), (0, Cp - C)))
    if (Tp, Cp) != (T, C):
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, Cp - C)))
    return _conv(x, w, plan, interp)[:, :T, :C]


# --------------------------------------------------------------------------- #
# the double-gated form: the convolution as the mixer itself
# --------------------------------------------------------------------------- #


def _gated_fwd_kernel(b_ref, c_ref, x_ref, w_ref, y_ref, tail, *, plan: _Plan):
    """``y = C * conv(B * x)``: :func:`_fwd_kernel`'s walk with ``z = B * x``
    formed on the slab in ``x``'s place (``tail`` keeps ``z``'s last eight
    rows), no bias and no silu, and ``C`` on the float32 sum."""
    K, chunk, per = plan.K, plan.chunk, plan.per
    first = pl.program_id(2) == 0
    row = lax.broadcasted_iota(jnp.int32, (_SLAB, chunk), 0)

    def some_lanes(c, _):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        taps, _ = _weights(w_ref, lanes, plan)

        def some_rows(k, before):
            for u in range(per):
                rows = pl.ds(pl.multiple_of((k * per + u) * _GROUP, _GROUP), _GROUP)
                zs = b_ref[0, rows, lanes].astype(jnp.float32) * x_ref[0, rows, lanes].astype(jnp.float32)
                gate = c_ref[0, rows, lanes].astype(jnp.float32)
                out = []
                for cur, g in ((zs[:_SLAB], gate[:_SLAB]), (zs[_SLAB:], gate[_SLAB:])):
                    out.append(g * _pre(taps, None, [_down(before, cur, s, row) for s in range(K)]))
                    before = cur
                y_ref[0, rows, lanes] = jnp.concatenate(out, axis=0).astype(y_ref.dtype)
            return before

        before = jnp.where(first, 0.0, tail[:, lanes])
        tail[:, lanes] = lax.fori_loop(0, plan.rows // (_GROUP * per), some_rows, before)
        return 0

    lax.fori_loop(0, plan.width // chunk, some_lanes, 0)


def _gated_bwd_kernel(b_ref, c_ref, x_ref, bh_ref, xh_ref, dy_ref, w_ref, d_ref, dw_ref, head, *, plan: _Plan):
    """:func:`_bwd_kernel`'s walk with ``dc = dy * C`` in ``dpre``'s place
    (there is no ``silu'``): ``c`` is formed again from ``z = B * x``, ``dC =
    dy * c``, ``dz`` is ``dx``'s sum, ``dB = dz * x`` and ``dx = dz * B``.
    The three gradients go to the thirds of ``d_ref``, whose block is the
    grid step's rows of all ``3 C`` channels (the grid step holds every
    channel: ``plan.width`` is ``C``)."""
    K, chunk, per = plan.K, plan.chunk, plan.per
    groups = plan.rows // _GROUP
    i = pl.program_id(2)                        # the grid's first step is the sequence's last rows
    start = i == pl.num_programs(2) - 1         # this grid step holds the sequence's first rows
    row = lax.broadcasted_iota(jnp.int32, (_SLAB, chunk), 0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    def some_lanes(c, _):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        taps, _ = _weights(w_ref, lanes, plan)

        def slab(before, z, b, x, gate, dy, after, sums):
            """The slab's three gradients and its ``dc`` (``before``: the slab
            of ``z`` above it; ``after``: ``dc`` of the slab under it), the
            taps' sums with it added."""
            shifted = [_down(before, z, s, row) for s in range(K)]
            dgate = dy * _pre(taps, None, shifted)
            dc = dy * gate
            sums = [sums[j] + dc * shifted[K - 1 - j] for j in range(K)]
            dz = taps[K - 1] * dc
            for s in range(1, K):
                dz = dz + taps[K - 1 - s] * _up(dc, after, s, row)
            return dc, (dz * x, dgate, dz * b), sums

        def turn(first_group, above, after, sums):
            """``per`` groups of sixteen rows from ``first_group`` on, the last
            row first; ``above`` is the slab of ``z`` over them."""
            group = lambda g: pl.ds(pl.multiple_of((first_group + g) * _GROUP, _GROUP), _GROUP)  # noqa: E731
            load = lambda ref, g: ref[0, group(g), lanes].astype(jnp.float32)  # noqa: E731
            zs = [load(b_ref, g) * load(x_ref, g) for g in range(per)]
            for g in reversed(range(per)):
                b, x, gate, dy = (load(ref, g) for ref in (b_ref, x_ref, c_ref, dy_ref))
                lo, hi = slice(0, _SLAB), slice(_SLAB, _GROUP)
                after, d_hi, sums = slab(zs[g][lo], zs[g][hi], b[hi], x[hi], gate[hi], dy[hi], after, sums)
                over = zs[g - 1][hi] if g else above
                after, d_lo, sums = slab(over, zs[g][lo], b[lo], x[lo], gate[lo], dy[lo], after, sums)
                for third, (a_lo, a_hi) in enumerate(zip(d_lo, d_hi)):       # dB, dC, dx
                    there = pl.ds(pl.multiple_of(third * plan.width + c * chunk, _LANES), chunk)
                    d_ref[0, group(g), there] = jnp.concatenate([a_lo, a_hi], axis=0).astype(d_ref.dtype)
            return after, sums

        def some_rows(k, carry):
            first_group = groups - (k + 1) * per
            over = pl.ds(pl.multiple_of((first_group - 1) * _GROUP, _GROUP), _GROUP)
            above = b_ref[0, over, lanes][_SLAB:].astype(jnp.float32) * x_ref[0, over, lanes][_SLAB:].astype(jnp.float32)
            return turn(first_group, above, *carry)

        zero = jnp.zeros((_SLAB, chunk), jnp.float32)
        after = jnp.where(i == 0, 0.0, head[:, lanes])
        carry = lax.fori_loop(0, groups // per - 1, some_rows, (after, [zero] * K))
        halo = bh_ref[0, _SLAB:, lanes].astype(jnp.float32) * xh_ref[0, _SLAB:, lanes].astype(jnp.float32)
        after, sums = turn(0, jnp.where(start, 0.0, halo), *carry)
        head[:, lanes] = after
        for j in range(K):
            dw_ref[0, j:j + 1, lanes] += jnp.sum(sums[j], axis=0, keepdims=True)
        return 0

    lax.fori_loop(0, plan.width // chunk, some_lanes, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gated_fwd_call(bcx, w, plan: _Plan, interp):
    Bt, T, _ = bcx.shape
    rows, W = plan.rows, plan.width
    Cp = w.shape[1]
    third = lambda a: pl.BlockSpec((1, rows, W), lambda b, p, i: (b, i, a * (Cp // W) + p))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, plan=plan),
        grid=(Bt, Cp // W, T // rows),
        in_specs=[third(0), third(1), third(2), pl.BlockSpec((w.shape[0], W), lambda b, p, i: (0, p))],
        out_specs=third(0),
        out_shape=jax.ShapeDtypeStruct((Bt, T, Cp), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((_SLAB, W), jnp.float32)],
        compiler_params=_params(interp),
        interpret=interp,
        name="gated_conv_fwd",
    )(bcx, bcx, bcx, w)


def _whole_width(plan: _Plan, Cp: int) -> _Plan:
    """``plan`` with every channel in a grid step and as many fewer rows, a
    whole part of ``plan``'s: the backward kernel's blocks, which let it
    write the three gradients as the thirds of one array."""
    turns = plan.rows // (_GROUP * plan.per)
    need = -(-plan.rows * Cp // max(_BLOCK, plan.rows * plan.width))
    parts = next(n for n in range(need, turns + 1) if turns % n == 0)
    return plan._replace(rows=plan.rows // parts, width=Cp)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gated_bwd_call(bcx, w, dy, plan: _Plan, interp):
    """``(d[B | C | x], dw)``: the three gradients as the thirds of one
    array, written where they belong, on :func:`_whole_width`'s blocks."""
    Bt, T, _ = bcx.shape
    Cp = w.shape[1]
    plan = _whole_width(plan, Cp)
    rows, per = plan.rows, plan.rows // _GROUP
    steps = T // rows
    third = lambda a, wide=1: pl.BlockSpec((1, rows, wide * Cp), lambda b, p, i: (b, steps - 1 - i, a))  # noqa: E731
    # the sixteen rows before the grid step's (its own first sixteen where it has none before: not read then)
    halo = lambda a: pl.BlockSpec((1, _GROUP, Cp), lambda b, p, i: (b, jnp.maximum((steps - 1 - i) * per - 1, 0), a))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_gated_bwd_kernel, plan=plan),
        grid=(Bt, 1, steps),
        in_specs=[
            third(0), third(1), third(2), halo(0), halo(2), third(0),
            pl.BlockSpec((w.shape[0], Cp), lambda b, p, i: (0, 0)),
        ],
        out_specs=[third(0, 3), pl.BlockSpec((1, w.shape[0], Cp), lambda b, p, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype), jax.ShapeDtypeStruct((Bt, *w.shape), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_SLAB, Cp), jnp.float32)],
        compiler_params=_params(interp),
        interpret=interp,
        name="gated_conv_bwd",
    )(bcx, bcx, bcx, bcx, bcx, dy, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated(bcx, w, plan, interp):
    return _gated_fwd_call(bcx, w, plan, interp)


def _gated_fwd(bcx, w, plan, interp):
    return _gated_fwd_call(bcx, w, plan, interp), (bcx, w)


def _gated_bwd(plan, interp, res, dy):
    bcx, w = res
    dbcx, dw = _gated_bwd_call(bcx, w, dy, plan, interp)
    return dbcx, dw.sum(axis=0)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_short_conv(bcx: jnp.ndarray, taps: jnp.ndarray, interpret: Optional[bool] = None) -> jnp.ndarray:
    """``C * conv(B * x)`` over ``bcx [Bt, T, 3 C]``, a projection's output
    as it stands (``B | C | x`` by thirds), with ``taps [K, C]``: ``y_t = C_t
    sum_j taps[j] (B x)_{t - (K - 1) + j}``, depthwise and causal, each row of
    the batch from zeros, no bias and no activation: ``[Bt, T, C]`` in
    ``bcx``'s dtype, the product ``B x``, the sum and ``C``'s product in
    float32, rounded once.  Differentiable in ``bcx`` and ``taps`` (the taps'
    gradient summed in float32); the gradient of ``bcx`` is one array whose
    thirds the backward kernel writes where they belong.  Any ``T`` and ``C``:
    rows are padded with zeros to whole blocks and each third to whole lane
    tiles (2,048 channels a third are sixteen: no copy there).
    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret` (site
    ``"short_conv"``)."""
    Bt, T, C3 = bcx.shape
    K, C = taps.shape[0], C3 // 3
    if C3 % 3 or taps.shape != (K, C) or not 1 <= K < _SLAB:
        raise ValueError(f"gated_short_conv shapes: bcx {bcx.shape} (three thirds) taps {taps.shape}")
    interp = resolve_interpret(interpret, "short_conv")
    plan, Tp, Cp = plan_for(T, C, K)
    metrics = default_registry()
    metrics.incr("conv.gated_calls")
    metrics.gauge("gconv.block_rows", plan.rows)
    metrics.gauge("gconv.lane_tiles", plan.tiles)
    w = jnp.pad(taps.astype(jnp.float32), ((0, _SLAB - K), (0, Cp - C)))
    if (Tp, Cp) != (T, C):
        bcx = jnp.concatenate(
            [jnp.pad(a, ((0, 0), (0, Tp - T), (0, Cp - C))) for a in jnp.split(bcx, 3, axis=-1)], axis=-1
        )
    return _gated(bcx, w, plan, interp)[:, :T, :C]
