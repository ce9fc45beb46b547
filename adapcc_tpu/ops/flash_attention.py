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
    acc    = acc·exp(m − m') + p_ij v_j        (p_ij in v's dtype)
    o_i    = acc / l ;  lse_i = m + log l      (saved for backward)

Backward runs two kernels (no atomics needed — each grid program owns its
output block exclusively): a dq pass gridded over query blocks and a dk/dv
pass gridded over key blocks, both rebuilding ``p_ij = exp(s_ij − lse_i)``
from the residuals with ``Δ_i = rowsum(do_i ∘ o_i)``.  The dk/dv pass works
on the transposed tile ``s_ji = k_j q_i^T``, so that ``dv += p^T do`` and
``dk += dS^T q`` are plain products and lse, Δ are rows.

**What crosses the boundary.**  lse and Δ leave and enter all three kernels
as lane-dense rows ``f32[B·H, 1, T]``, whole (a band's dk/dv kernel takes the
band's lanes): the forward turns its ``[bq, 1]`` column ``m + log l`` into a
``[1, bq]`` row once a query block, after the walk, and writes its own lanes;
the dq kernel turns its lanes of both rows into the columns it subtracts once
a program, before the walk; the dk/dv kernel reads rows as they are.  The
turn moves values and computes none, so the results are to the bit those of
any other layout, and nothing outside the kernels pads, broadcasts or slices
a statistic: the forward's array is the one the backward kernels read.

**Where the operands live.**  The model keeps q, k, v and wants o as
``[B, T, H, D]``, which is ``[B, T, H·D]`` for free.  Where the heads are
equal (``H_q == H_kv``), ``D == D_v``, a 128-lane block is whole heads
(``pair = max(1, 128 // D)``, ``pair·D`` a multiple of 128) and ``H`` divides
by ``pair``, the kernels read q, k, v, do and write o, dq, dk, dv in that
array: the grid's first axis runs over ``B · H/pair`` lane blocks, a block
``(1, rows, pair·D)`` at ``(b // (H/pair), ·, b % (H/pair))``, and no pass of
XLA's stands between a projection and a kernel; the residuals are the model's
arrays.  :func:`_bthd_call` decides, by the shapes and nothing else; one
kernel body serves both ways of addressing.  At ``D = 64`` a program holds
two heads side by side in its blocks' lanes.  *Masked operands, no lane
slice*: once a program, outside the walk, the resident operand (q and do; k
and v in the dk/dv kernel) is formed once for each head with the other head's
lanes zero (cleared on the 32-bit words, :func:`_heads_of`), and every
product of a head contracts over all 128 lanes against the other operand as
loaded, the other head's lanes adding exact zeros to an fp32 sum.  The
accumulating products come out 128 lanes wide with the head's own 64 valid;
each head keeps its accumulator and the block is put together once, after the
walk, at the store (the forward divides once, the normalizers spread over
their heads' lanes).  A 64-deep contraction and a 64-wide result already take
a whole pass of the 128 x 128 MXU, so the passes are the ``[B·H, T, D]``
entry's, and the programs are half as many.  The backward kernels take both
heads tile by tile (a K/V or q/do tile loaded once for both); the forward
walks one head's tiles, then the other's.  Measured on a v5e at cell 1's
shape against the ``[B·H, T, D]`` entry (PERF.md §6, PR 48): forward +4.9% a
call, dq -3.0%, dk/dv -2.2%, the three together -0.7%; with lane slices
instead of masks +9.7%, +3.0%, +0.8%.  lse and Δ stay ``f32[B·H, 1, T]``, a block
``(pair, 1, T)`` a program; Δ is summed over each head's lanes of ``do ∘ o``
as a product with the heads' 0/1 indicator (:func:`_head_sums`).  Everything
else keeps the ``[B·H, T, D]`` entry and the transposes around it, unchanged:
grouped K/V heads (cells 3, 6, 7, 8; an index map alone would do, not
measured), a head size of its own for v (cells 4, 5, 7), an odd number of
64-wide heads, and the ring shard, which calls ``_flash_bhtd_lse`` on arrays
it has already laid out (``parallel/ring_attention.py``).

**Which tiles.**  With ``causal=True`` a kernel visits only the tiles the
mask leaves: query block ``qi`` takes key blocks ``0 … ((qi+1)·bq − 1) // bk``,
key block ``kj`` takes query blocks from ``(kj·bk) // bq`` up, and the mask is
applied only on the tiles the diagonal crosses (:func:`visited_tiles` counts
them: 36 of 64 at T=1,024 with 128-tiles).  Where the code stays small the
bounds are static: the kernel body is written out once for each grid position
along the block axis (:func:`_per_program`), because a loop of one tile a
turn, bounded by the traced ``program_id``, cost 3.4 times as much for each
tile on a v5e.  Long sequences (past ``_STRAIGHT_LINE_ELEMENTS``) take one
body with traced bounds, and it too runs written-out tiles: the tiles every
position has (the diagonal's) at a traced start, and the unmasked ones, whose
count ``n`` differs with the position, as runs by what the code can see of
``n`` (:func:`_tiles`): ``G`` tiles written out, their blocks ``base + r``
with ``r`` a Python integer, for each of ``n // G`` turns of a loop, then
``G/2`` where ``n`` has that bit, and so on down to one; ``G`` the largest
whose code fits ``_TRACED_BASE_ELEMENTS`` (4 at T=8,192 with 512-tiles: 8
tiles of code; :func:`looped_tiles` counts what is reached from the loop).
The tiles come in the order the loop of one tile a turn took them, which is
``G = 1`` of the same code, so the results are that loop's to the bit.
``causal=False`` (ring attention's off-diagonal shards) visits every tile
from one body.

**A window.**  ``window=W`` (with ``causal=True``) lets query ``t`` see key
``s`` only where ``0 <= t - s < W``: a band under the diagonal.  A query
block then has a *first* key block as well as a last, and the mask is applied
on both edges of the band (:func:`_key_span`, :func:`_query_span`).  The
band's shape repeats from block to block, so with square tiles the positions
whose bounds differ only by their own index share ONE written-out body
(bounds static relative to the traced position): at T=8,192, W=2,048 and
512-tiles that is four leading bodies plus one, 15 tiles of code, where one
body for each of the 16 positions would be 70.

**Grouped KV heads.**  ``k`` and ``v`` may carry fewer heads than ``q``
(``H_q = G * H_kv``): query head ``h`` reads KV head ``h // G`` through the
K/V block index, nothing is repeated in HBM, and the dk/dv kernel writes one
fp32 partial for each query head that the wrapper sums over the group.  Run
on a chip (PERF.md §4): equal heads at head size 64 and T=1,024, read in place,
two heads a program (cells 1-2, PR 48); eight query heads to a K/V head at
head size 128 and T=8,192, windowed and full (cell 3); equal heads with scores over 192 and values over 128 at
T=8,192 (cells 4-5); four query heads to a K/V head at head size 64 and
T=8,192 with a given ``scale`` (cell 6, PR 39).

**A head size of its own for v.**  ``v`` may be ``[B, T, H_kv, D_v]`` with
``D_v != D`` (latent attention: scores over 192 channels, values over 128):
the two score products run at ``D``, the three value products at ``D_v``,
``o`` and ``dv`` come out ``D_v`` wide.  With ``D_v == D`` the kernels lower
to what they lowered to before (tests/test_chip_compile.py holds the text).

**Which dtype.**  The seven products take their operands in the inputs' own
dtype and accumulate in fp32: bf16 q/k/v/do go to the MXU as bf16, and ``p``
and ``dS`` are cast to that dtype for their four products (what the XLA
attention path does with its softmax); fp32 inputs keep fp32 products.  The
softmax arithmetic, ``m``, ``l``, ``lse``, ``Δ`` and the accumulators are fp32
either way.

**Which tile.**  ``block_q`` / ``block_k`` default to the tile
:data:`TILE_TABLE` holds for ``(T, D, dtype)``, measured on the chip.

**The signature that must hold.**  The benchmark's trace reader
(``chipbench/trace_reduce.flash_kernel``) tells the kernels apart by their
operands and results: three Mosaic custom calls an attention; the forward
takes q, k, v and returns two arrays; both backward kernels take q, k, v, do,
lse, delta, dq returning one array and dk/dv a tuple of two.  So no
scalar-prefetch operand, no fused dq+dkv kernel, no split forward (VMEM scratch
is not an operand); the ``name=`` of each ``pallas_call`` stays
(tests/test_flash_attention.py guards both).  A window and grouped heads
change neither: the window is a static bound, the group an index map.

Used by the GPT-2 flagship model when ``GPT2Config.attention == "flash"``;
long-context cross-chip attention composes this with the ring/Ulysses
sequence parallelism in :mod:`adapcc_tpu.parallel` (each device runs this
kernel on its local K/V shard).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.ops.kernel_mode import resolve_interpret
from adapcc_tpu.utils.observability import default_registry

_NEG_INF = -1e30

# Mosaic requires the last two dims of every block shape to be divisible by
# the (8, 128) tile or equal to the whole array's dims.  The per-row statistics
# (the forward's lse out, lse and delta into both backward kernels) cross every
# pallas_call boundary as rows, ``f32[BH, 1, T]`` with T along the lanes, in
# blocks ``(1, 1, T)``: both minor dims are the array's own, so the rule holds
# at every block size, and HBM tiles such an array a sublane deep, so the array
# is its content.  (With T on the sublanes the rule wants a lane axis beside
# it, which HBM tiles out to 128 lanes: sixteen times the content at eight
# lanes, made, broadcast and sliced by XLA around every call; PERF.md §6, PR
# 47.)  A program takes its own ``bq`` lanes of the row; the kernels gridded
# over query blocks hold the statistics as ``[bq, 1]`` columns and turn them
# once a program, outside the walk (:func:`_as_row`, :func:`_as_column`: a
# relayout, every bit kept).
_LANES = 128


def _as_row(column):
    """A ``[n, 1]`` column of per-row statistics as the ``[1, n]`` row that
    crosses the kernel's boundary: the same values, moved (the column spread
    over a tile's lanes, the slab transposed on the XLU, its first row kept;
    a ``reshape`` lowers too and cost ``flash_fwd`` 0.55 us a program on a
    v5e, PERF.md §6, PR 47)."""
    return jnp.transpose(jnp.broadcast_to(column, (column.shape[0], _LANES)))[:1]


def _as_column(row):
    """A ``[1, n]`` row as the ``[n, 1]`` column a query block's tiles
    subtract: the same values, moved."""
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, row.shape[1])))[:, :1]


def _causal_mask(s, qi, kj, block_q, block_k, q_axis=0):
    """Mask tile ``(qi, kj)`` of the scores; queries run along ``q_axis``."""
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = kj * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(k_pos <= q_pos, s, _NEG_INF)


def _band_mask(s, offset, window: int, q_axis=0):
    """Mask a tile whose first query lies ``offset`` positions after its
    first key: key ``s`` is seen by query ``t`` where ``0 <= t - s < window``
    (both edges of the band at once)."""
    ahead = (
        offset
        + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        - lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    )
    return jnp.where((ahead >= 0) & (ahead < window), s, _NEG_INF)


def _mask(s, base, q_rel, k_rel, block_q, block_k, window, q_axis=0):
    """Mask the tile of query block ``base + q_rel`` and key block
    ``base + k_rel`` (``base`` is 0, or the traced position of a body that
    several positions share: then the tiles are square and it cancels)."""
    if window is None:
        return _causal_mask(s, _at(base, q_rel), _at(base, k_rel), block_q, block_k, q_axis)
    return _band_mask(s, q_rel * block_q - k_rel * block_k, window, q_axis)


def _at(base, rel):
    """Block ``base + rel``; ``base`` is the integer 0 except in a body that
    several positions share."""
    return rel if isinstance(base, int) and base == 0 else base + rel


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _key_blocks(qi: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """Key blocks a causal query block ``qi`` needs: ``[0, full)`` lie wholly
    at or under the diagonal (last column <= first row), ``[full, end)`` are
    crossed by it."""
    return (qi * block_q + 1) // block_k, ((qi + 1) * block_q - 1) // block_k + 1


def _query_blocks(kj: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """Query blocks a causal key block ``kj`` is seen by: ``[start, full)``
    are crossed by the diagonal, ``[full, n_q)`` lie wholly under it (first
    row >= last column)."""
    return (kj * block_k) // block_q, ((kj + 1) * block_k + block_q - 2) // block_q


def _key_span(qi, block_q: int, block_k: int, window: Optional[int]):
    """``(lo, a, b, end)`` for causal query block ``qi`` (an integer or
    traced): key blocks ``[lo, a)`` and ``[b, end)`` need the mask (the
    window's far edge; the diagonal, and both where the window is narrower
    than a tile), ``[a, b)`` lie wholly inside the band."""
    full, end = _key_blocks(qi, block_q, block_k)
    if window is None:
        return 0, 0, full, end
    first_row = qi * block_q
    lo = _most(first_row - window + 1, 0) // block_k
    a = _most(first_row + block_q - window + block_k - 1, 0) // block_k
    return lo, a, _most(full, a), end


def _query_span(kj, block_q: int, block_k: int, window: Optional[int], n_q: int):
    """``(start, a, b, end)`` for causal key block ``kj``: query blocks
    ``[start, a)`` and ``[b, end)`` need the mask, ``[a, b)`` see the whole
    key block."""
    start, full = _query_blocks(kj, block_q, block_k)
    if window is None:
        return start, full, n_q, n_q
    first_col = kj * block_k
    end = _least((first_col + block_k + window - 2) // block_q + 1, n_q)
    a = _least(full, end)
    return start, a, _least(_most((first_col + window) // block_q, a), end), end


def visited_tiles(
    T: int, block_q: int, block_k: int, causal: bool, window: Optional[int] = None
) -> int:
    """``[block_q, block_k]`` tiles of one ``[T, T]`` score plane that each of
    the three kernels visits: every tile without a mask, and under the causal
    mask (and the window, where one is given) exactly those with an unmasked
    element."""
    n_q, n_k = T // block_q, T // block_k
    if not causal:
        return n_q * n_k
    spans = (_key_span(qi, block_q, block_k, window) for qi in range(n_q))
    return sum(end - lo for lo, _, _, end in spans)


def _query_side(T: int, block_q: int, block_k: int, causal: bool, window):
    """``(n, span)`` of the kernels gridded over query blocks (forward, dq):
    how many there are and the key blocks each takes (:func:`_key_span`)."""
    n_k = T // block_k
    return T // block_q, lambda qi: _key_span(qi, block_q, block_k, window) if causal else (0, 0, n_k, n_k)


def _key_side(T: int, block_q: int, block_k: int, causal: bool, window):
    """``(n, span)`` of the kernel gridded over key blocks (dk/dv): how many
    there are and the query blocks each is seen by (:func:`_query_span`)."""
    n_q = T // block_q
    return T // block_k, lambda kj: _query_span(kj, block_q, block_k, window, n_q) if causal else (0, 0, n_q, n_q)


#: Score-plane elements (tiles x block_q x block_k) a causal kernel writes out
#: as straight-line code with static bounds at most.  Under it every grid
#: position gets its own copy of the body; over it (long sequences: the code
#: grows with T squared and falls out of instruction memory) the one body takes
#: the traced ``program_id`` and walks its tiles in runs at a traced base.
#: Measured on a v5e (PERF.md §6, PR 25): static wins at T=1,024 and T=2,048
#: (0.8 and 2.6 M elements with 512-tiles), the one body at T=4,096 (9.4 M).
_STRAIGHT_LINE_ELEMENTS = 1 << 22

#: Score-plane elements that one body with traced bounds writes out at most:
#: its runs of tiles at a traced base and the tiles every position has.
#: Measured on a v5e (PERF.md §6, PR 36) at T=8,192 with 512-tiles, the three
#: kernels of one latent layer (32 heads, 192 / 128): 8 tiles of code (runs of
#: 4 in a loop of up to three turns, then 2 and 1, beside the diagonal's tile)
#: 24.73 ms; 16 tiles (a run of 8, then 4, 2, 1) 25.03 and twice the compile;
#: 4 tiles 24.77; one tile a turn 25.65; the parent's loop 26.67.
_TRACED_BASE_ELEMENTS = 1 << 21


def _bodies(n: int, span, share: bool):
    """The written-out bodies of ``n`` grid positions: ``(first, last,
    bounds)`` runs, ``bounds`` those of ``first``.  A run longer than one
    holds consecutive positions whose bounds differ only by the position's
    own index (the interior of a window's band, square tiles): they share one
    body.  Without ``share`` every position is its own run."""
    runs = []
    for i in range(n):
        bounds = span(i)
        rel = tuple(x - i for x in bounds)
        if share and runs and runs[-1][3] == rel:
            runs[-1][1] = i
        else:
            runs.append([i, i, bounds, rel])
    return [(first, last, bounds) for first, last, bounds, _ in runs]


def _top(n: int) -> int:
    """The largest power of two that is at most ``n`` (at least 1)."""
    return 1 << (n.bit_length() - 1)


def _runs(n: int, span, block_q: int, block_k: int, pair: int = 1):
    """How ``n`` positions walk their spans from ONE body whose bounds are
    traced: ``(most, run)`` for each of a span's three parts (masked,
    unmasked, masked), ``most`` the tiles any position has there.  ``run`` 0:
    every position has that many, written out at the part's traced start.
    Else the count differs and is split by what the code can see of it:
    ``run`` tiles written out, in a loop, while that many are left, then a
    half of that where the count's bit says so, and so on down to one.  The
    runs are the largest power of two (at most the largest in ``most``) whose
    code, ``2·run − 1`` tiles for each such part beside the written-out ones,
    stays within ``_TRACED_BASE_ELEMENTS``, and 1 where nothing fits: one tile
    a turn.  A written-out tile is the code of ``pair`` heads' (a program of
    the in-place entry holds as many), so it counts that many times."""
    spans = [span(i) for i in range(n)]
    counts = [[s[i + 1] - s[i] for s in spans] for i in range(3)]
    room = _TRACED_BASE_ELEMENTS // (block_q * block_k * pair)

    def parts(run):
        return tuple((max(c), 0 if min(c) == max(c) else min(run, _top(max(c)))) for c in counts)

    def code(run):
        return sum(2 * run - 1 if run else most for most, run in parts(run))

    run = 1
    while 2 * run <= max(map(max, counts)) and code(2 * run) <= room:
        run *= 2
    return parts(run)


def _written_out(n: int, span, block_q: int, block_k: int, causal: bool, window, pair: int = 1):
    """The written-out bodies (:func:`_bodies`) of a kernel over ``n`` grid
    positions along the block axis, or None where their code (each tile that
    of ``pair`` heads) would pass ``_STRAIGHT_LINE_ELEMENTS`` (one body then
    walks by :func:`_runs`)."""
    if not causal or n == 1:
        return [(0, 0, span(0))]
    bodies = _bodies(n, span, share=window is not None and block_q == block_k)
    written = sum(bounds[3] - bounds[0] for _, _, bounds in bodies)
    return bodies if written * block_q * block_k * pair <= _STRAIGHT_LINE_ELEMENTS else None


def looped_tiles(
    T: int, block_q: int, block_k: int, causal: bool, window: Optional[int] = None, key_side: bool = False,
    pair: int = 1,
) -> int:
    """Of :func:`visited_tiles`, those a kernel gridded over query blocks (or
    over key blocks: ``key_side``) reaches from inside a loop with a traced
    trip count; the others are straight-line code, under a condition or not.
    ``pair``: the heads a program holds (:func:`_operands`)."""
    n, span = (_key_side if key_side else _query_side)(T, block_q, block_k, causal, window)
    if _written_out(n, span, block_q, block_k, causal, window, pair) is not None:
        return 0
    parts = _runs(n, span, block_q, block_k, pair)
    return sum(
        (s[i + 1] - s[i]) // run * run for s in map(span, range(n)) for i, (_, run) in enumerate(parts) if run
    )


def _per_program(body, n: int, span, block_q: int, block_k: int, causal: bool, window, pair: int) -> None:
    """``body(rel, bounds, base, walk)`` for the grid position ``base + rel``
    along the block axis (of ``n``): ``_at(base, rel)`` is the program's own
    block in every body (a query block finds its lanes of a statistic's row by
    it).  ``bounds = span(position)`` counted from ``base``, ``walk`` the
    :func:`_band` that visits them.  Under the causal
    mask the positions differ in their bounds: each gets its own copy of the
    body with the position a Python integer (``base`` 0), where that keeps the
    code small enough (every bound and slice is then static and Mosaic
    schedules a block's tiles as one basic block); the positions inside a
    window's band share one copy, ``base`` the traced position and the bounds
    static from it (a window of one key on square tiles is all band: one copy
    for every position).  Else ``rel`` is the traced ``pl.program_id``, the
    bounds are traced and ``walk`` splits each of them into written-out runs
    (:func:`_runs`).  Without the mask one body serves them all: every
    position has its bounds, counted from 0, and ``rel`` is the traced
    position."""
    bodies = _written_out(n, span, block_q, block_k, causal, window, pair)
    if n == 1:
        return body(0, bodies[0][2], 0, _band)
    position = pl.program_id(1)
    if not causal:
        return body(position, bodies[0][2], 0, _band)
    if bodies is None:
        return body(position, span(position), 0, functools.partial(_band, parts=_runs(n, span, block_q, block_k, pair)))
    for first, last, bounds in bodies:
        if first == last:
            pl.when(position == first)(functools.partial(body, first, bounds, 0, _band))
        else:
            shared = functools.partial(body, 0, tuple(x - first for x in bounds), position, _band)
            pl.when((position >= first) & (position <= last))(shared)


def _tiles(lo, hi, tile, carry, most=0, run=0):
    """``carry = tile(j, carry)`` for ``j`` in ``[lo, hi)``, ascending.  Both
    bounds Python integers: written out.  One traced (:func:`_runs` gives
    ``most`` and ``run``): ``most`` tiles written out from the traced ``lo``
    where every position has as many (``run`` 0); else ``run`` tiles written
    out for each turn of a loop while that many are left, then the rest by
    the bits of the count, each bit's tiles written out under its own
    condition.  (The loop stays where it turns once at most: under a
    condition the first run would see the accumulators' zeros as constants,
    Mosaic then folds the first sum away, and ``dq`` rounds otherwise than
    from the loop: an ulp of bfloat16 in 1e-5 of its elements on a v5e,
    PERF.md §6, PR 36.)"""
    if isinstance(lo, int) and isinstance(hi, int):
        for j in range(lo, hi):
            carry = tile(j, carry)
        return carry

    def written(base, count):
        def tiles(carry):
            for r in range(count):
                carry = tile(base + r, carry)
            return carry
        return tiles

    if not run:
        return written(lo, most)(carry)
    count = hi - lo
    carry = lax.fori_loop(0, count // run, lambda turn, carry: written(lo + turn * run, run)(carry), carry)
    at = lo + count // run * run
    while run > 1:
        run //= 2
        carry = lax.cond((count & run) != 0, written(at, run), lambda carry: carry, carry)
        at = at + (count & run)
    return carry


def _band(bounds, tile, carry, parts=((0, 0),) * 3):
    """The three parts of a span: masked, unmasked, masked."""
    lo, a, b, end = bounds
    carry = _tiles(lo, a, functools.partial(tile, masked=True), carry, *parts[0])
    carry = _tiles(a, b, functools.partial(tile, masked=False), carry, *parts[1])
    return _tiles(b, end, functools.partial(tile, masked=True), carry, *parts[2])


def _dot(a, b, contract):
    """One MXU product in the operands' own dtype, accumulated in fp32."""
    return lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _block(i, block: int):
    """The slice of block ``i`` (a Python integer or traced) along T."""
    return pl.ds(i * block if isinstance(i, int) else pl.multiple_of(i * block, block), block)


def _rows(ref, i, block: int):
    """Block ``i`` of ``block`` rows of a ``[1, T, D]`` ref."""
    return ref[0, _block(i, block), :]


def _held(base, r, first, band):
    """Where block ``base + r`` lies in a ref that holds the whole sequence
    (``band`` None) or only ``band`` blocks from block ``first`` on, counted
    from ``base`` like ``r``."""
    return _at(base, r) if band is None else r - first


def _heads_of(x, pair: int):
    """A ``[rows, pair·d]`` block that holds ``pair`` heads side by side in
    its lanes, once for each head with the other heads' lanes zero: a product
    that contracts over all the lanes is then that head's alone (exact zeros
    added to an fp32 sum), and no lane is sliced or shifted.  One head: the
    block itself.  The lanes are cleared on the block's 32-bit words (two
    rows of bfloat16 a word, the same lane): a select on bfloat16 itself is
    unpacked to float32 and packed again on a v5e, which cost every kernel
    2-3% of a call at cell 1's shape (PERF.md §6, PR 48)."""
    if pair == 1:
        return [x]
    whole_words = (x.shape[0] * x.dtype.itemsize) % 4 == 0
    words = pltpu.bitcast(x, jnp.uint32) if whole_words else x
    head = lax.broadcasted_iota(jnp.int32, words.shape, 1) // (words.shape[1] // pair)
    kept = [jnp.where(head == h, words, jnp.zeros_like(words)) for h in range(pair)]
    return [pltpu.bitcast(k, x.dtype) for k in kept] if whole_words else kept


def _side_by_side(parts, lanes=None):
    """One ``[rows, pair·d]`` block from a result for each head, each as wide
    as the block and valid on its own head's lanes (a product against all the
    lanes of the other operand leaves the other heads' columns beside it); or
    from a ``[rows, 1]`` column for each head, spread over the head's lanes
    of ``lanes``."""
    block = parts[-1]
    if len(parts) == 1:
        return block
    lanes = lanes or block.shape[1]
    head = lax.broadcasted_iota(jnp.int32, (block.shape[0], lanes), 1) // (lanes // len(parts))
    for h in reversed(range(len(parts) - 1)):
        block = jnp.where(head == h, parts[h], block)
    return block


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_q, block_k, window, seq, band, pair):
    q = q_ref[0]
    bq = q.shape[0]
    q_heads = _heads_of(q, pair)

    def query_block(rel, bounds, base, walk):
        # a head's whole walk after the other's (the backward kernels take the heads tile by tile): measured, the
        # forward is 1-2% of a call faster so at cell 1's shape, within 5% of the [B·H, T, D] entry's (PERF.md §6, PR 48)
        def tiles_of(q):
            def tile(r, carry, masked):
                m, l, acc = carry
                at = _held(base, r, bounds[0], band)
                k, v = _rows(k_ref, at, block_k), _rows(v_ref, at, block_k)
                s = _dot(q, k, (1, 1)) * scale
                if masked:
                    s = _mask(s, base, rel, r, block_q, block_k, window)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc = acc * alpha + _dot(p.astype(v.dtype), v, (1, 0))
                return m_new, l, acc

            return tile

        def start():
            return (
                jnp.full((bq, 1), _NEG_INF, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32),
                jnp.zeros((bq, v_ref.shape[-1]), jnp.float32),
            )

        done = [walk(bounds, tiles_of(q), start()) for q in q_heads]
        # one division a program: the heads' sums and normalizers side by side first
        acc = _side_by_side([acc for _, _, acc in done])
        o_ref[0] = (acc / _side_by_side([l for _, l, _ in done], acc.shape[1])).astype(o_ref.dtype)
        for h, (m, l, _) in enumerate(done):
            lse_ref[h, :, _block(_at(base, rel), block_q)] = _as_row(m + jnp.log(l))

    _per_program(query_block, *_query_side(seq, block_q, block_k, causal, window), block_q, block_k, causal, window, pair)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, scale, causal, block_q, block_k, window, seq, band, pair,
):
    q = q_ref[0]
    do = do_ref[0]
    heads = list(zip(_heads_of(q, pair), _heads_of(do, pair)))

    def query_block(rel, bounds, base, walk):
        own = _block(_at(base, rel), block_q)
        columns = [(_as_column(lse_ref[h, :, own]), _as_column(delta_ref[h, :, own])) for h in range(pair)]

        def tile(r, carry, masked):
            at = _held(base, r, bounds[0], band)
            k, v = _rows(k_ref, at, block_k), _rows(v_ref, at, block_k)

            def head(q, do, lse_col, delta_col, dq):
                s = _dot(q, k, (1, 1)) * scale
                if masked:
                    s = _mask(s, base, rel, r, block_q, block_k, window)
                p = jnp.exp(s - lse_col)
                ds = p * (_dot(do, v, (1, 1)) - delta_col) * scale
                return dq + _dot(ds.astype(k.dtype), k, (1, 0))

            return tuple(head(*operands, *stats, dq) for operands, stats, dq in zip(heads, columns, carry))

        done = walk(bounds, tile, tuple(jnp.zeros(q.shape, jnp.float32) for _ in heads))
        dq_ref[0] = _side_by_side(done).astype(dq_ref.dtype)

    _per_program(query_block, *_query_side(seq, block_q, block_k, causal, window), block_q, block_k, causal, window, pair)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, causal, block_q, block_k, window, seq, band, pair,
):
    k = k_ref[0]
    v = v_ref[0]
    heads = list(zip(_heads_of(k, pair), _heads_of(v, pair)))
    n_q = seq // block_q

    def key_block(rel, bounds, base, walk):
        # a band of queries starts at this key block, or as late as fits
        first = None if band is None else 0 if not isinstance(base, int) else _least(rel, n_q - band)

        def tile(r, carry, masked):
            # the tile transposed, [bk, bq]: all four products then contract
            # in the MXU's own orientations (no transposed left operand), and
            # lse and delta are rows
            i = _held(base, r, first, band)
            q, do = _rows(q_ref, i, block_q), _rows(do_ref, i, block_q)

            def head(h, k, v, dk, dv):
                lse_row = lse_ref[h, :, _block(i, block_q)]
                delta_row = delta_ref[h, :, _block(i, block_q)]
                s = _dot(k, q, (1, 1)) * scale
                if masked:
                    s = _mask(s, base, r, rel, block_q, block_k, window, q_axis=1)
                p = jnp.exp(s - lse_row)
                dv = dv + _dot(p.astype(do.dtype), do, (1, 0))
                ds = p * (_dot(v, do, (1, 1)) - delta_row) * scale
                dk = dk + _dot(ds.astype(q.dtype), q, (1, 0))
                return dk, dv

            return tuple(head(h, *operands, *sums) for h, (operands, sums) in enumerate(zip(heads, carry)))

        zeros = jnp.zeros(k.shape, jnp.float32)
        sums = (zeros, zeros if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32))
        done = walk(bounds, tile, (sums,) * pair)
        dk_ref[0] = _side_by_side([dk for dk, _ in done]).astype(dk_ref.dtype)
        dv_ref[0] = _side_by_side([dv for _, dv in done]).astype(dv_ref.dtype)

    _per_program(key_block, *_key_side(seq, block_q, block_k, causal, window), block_q, block_k, causal, window, pair)


#: The tile by shape, measured on a TPU v5e (PERF.md §6, PR 25): rows of
#: ``(longest T, widest head, operand bytes) -> (block_q, block_k)``, the first
#: row that holds the shape wins, the last holds every shape.  Read here and
#: nowhere else: a config's ``flash_block = None`` (``GPT2Config``,
#: ``TrinityConfig``) and a bare kernel call both resolve through
#: :func:`default_blocks`.
TILE_TABLE = (
    ((4096, 64, 2), (512, 512)),   # bf16, measured at T=1,024 (B.H = 144 and 32) and T=4,096
    ((1024, 64, 4), (512, 512)),   # fp32, measured at T=1,024
    # bf16, head size 128 at T=8,192, window 2,048 and none (PERF.md §6, PR 26); head size 64 on 8 K/V heads there too (PR 39)
    ((8192, 128, 2), (512, 512)),
    ((8192, 192, 2), (512, 512)),  # bf16, q/k of 192 over v of 128 at T=8,192 (PERF.md §6, PR 32)
    ((float("inf"),) * 3, (128, 128)),   # not measured: the tile every shape ran before
)


def resolve_block(seq: int, want: int) -> int:
    """Largest 8-aligned tile <= ``want`` that divides ``seq``; falls back
    to the full sequence when no aligned divisor exists."""
    b = min(max(8, want - want % 8), seq)
    while b >= 8 and seq % b:
        b -= 8
    return b if b >= 8 and seq % b == 0 else seq


def default_blocks(T: int, D: int, dtype) -> Tuple[int, int]:
    """``(block_q, block_k)`` for a ``[T, D]`` attention over ``dtype``
    operands: the table's tile for the shape, cut to a divisor of ``T``."""
    shape = (T, D, jnp.dtype(dtype).itemsize)
    want = next(
        tile for limit, tile in TILE_TABLE if all(x <= m for x, m in zip(shape, limit))
    )
    return resolve_block(T, want[0]), resolve_block(T, want[1])


def _block_sizes(T: int, D: int, dtype, block_q: Optional[int], block_k: Optional[int]):
    """The tile the kernels run: the caller's, else the table's."""
    by_shape = default_blocks(T, D, dtype)
    bq = by_shape[0] if block_q is None else min(block_q, T)
    bk = by_shape[1] if block_k is None else min(block_k, T)
    if T % bq or T % bk:
        raise ValueError(f"seq len {T} must divide into blocks ({bq}, {bk})")
    # Mosaic sublane rule: the (1, bq, D) blocks of q, o, do, dq and the
    # (1, bk, D) ones of k, v, dk, dv require 8-aligned block sizes (or the
    # degenerate bq == T case).  An unaligned block compiles past tracing and
    # dies deep in Mosaic with a cryptic tiling error on hardware — reject it
    # here with the real reason.
    for name, b in (("block_q", bq), ("block_k", bk)):
        if b % 8 and b != T:
            raise ValueError(
                f"{name}={b} must be a multiple of 8 (Mosaic sublane "
                f"alignment) or equal to the sequence length {T}"
            )
    return bq, bk


def _record_tiles(T: int, bq: int, bk: int, causal: bool, kernels: int, window, where: "_Operands") -> None:
    """Trace-time gauges (once per compile, nothing per step): the tiles of
    one ``[T, T]`` score plane the attention call's kernels visit and would
    visit without skipping, summed over ``kernels`` of them (one: the forward;
    three: with dq and dk/dv), of the visited those reached from a loop
    (:func:`looped_tiles`), the tile, and whether the kernels read the
    model's own arrays (``flash.in_place`` 1) or ``[B·H, T, D]`` copies."""
    metrics = default_registry()
    metrics.gauge("flash.tiles_visited", kernels * visited_tiles(T, bq, bk, causal, window))
    metrics.gauge("flash.tiles_total", kernels * (T // bq) * (T // bk))
    by_query, by_key = (looped_tiles(T, bq, bk, causal, window, key_side=side, pair=where.pair) for side in (False, True))
    metrics.gauge("flash.tiles_looped", by_query if kernels == 1 else 2 * by_query + by_key)
    metrics.gauge("flash.block_q", bq)
    metrics.gauge("flash.block_k", bk)
    metrics.gauge("flash.in_place", int(where.in_place))


#: Bytes of whole-sequence operands (K and V, or q and do, double-buffered)
#: past which a kernel asks Mosaic for more than its default 16 MiB of scoped
#: VMEM; under it no compiler parameter is passed at all (GPT-2's shapes).
_VMEM_ASK_OVER = 6 << 20
_VMEM_LIMIT = 96 << 20


def _compiler_params(T: int, D: int, Dv: int, dtype) -> dict:
    """``D``, ``Dv``: the lanes of a program's block (a pair's width)."""
    resident = 2 * T * (D + Dv) * jnp.dtype(dtype).itemsize
    if resident <= _VMEM_ASK_OVER:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)}


def _band_blocks(T: int, bq: int, bk: int, window) -> Optional[int]:
    """Blocks of K and V a query block's band spans (and of q and do a key
    block's), where only those need be resident: a window, square tiles (the
    band then starts a fixed number of blocks before the diagonal) and a band
    shorter than the sequence.  None: the whole sequence is resident."""
    if window is None or bq != bk:
        return None
    blocks = (window - 1 + bk - 1) // bk + 1
    return blocks if blocks < T // bk else None


class _Operands(NamedTuple):
    """Where grid row ``b`` of a kernel finds its heads.  On the
    ``[B·H, T, D]`` entry a row is a head: ``pair`` 1, ``q_at(b) = (b, 0)``,
    ``kv_at(b) = (b // groups, 0)``.  In place a row is ``pair`` heads that
    share a lane block of the model's ``[B, T, H·D]``: ``(batch row, lane
    block) = (b // (H/pair), b % (H/pair))`` for q, o, do, dq and k, v, dk, dv
    alike.  Either way the rows' heads are rows ``pair·b …`` of the
    statistics' ``[B·H, 1, T]``."""

    in_place: bool
    rows: int       # grid positions along axis 0
    pair: int       # heads a program holds, side by side in its blocks' lanes
    q_at: Callable
    kv_at: Callable


def _lane_pair(D: int) -> int:
    """Heads of size ``D`` that fill a 128-lane block (one where a head is
    that wide or wider)."""
    return max(1, _LANES // D)


def _operands(q_shape, k_shape) -> _Operands:
    """By the arrays the entry was handed: the model's ``[B, T, H, D]``
    (:func:`_bthd_call` hands them over only where the rule of "Where the
    operands live" holds) or ``[B·H, T, D]``."""
    if len(q_shape) == 4:
        B, _, H, D = q_shape
        pair = _lane_pair(D)
        at = lambda b: (b // (H // pair), b % (H // pair))  # noqa: E731
        return _Operands(True, B * H // pair, pair, at, at)
    kv = _kv_index(q_shape[0] // k_shape[0])
    return _Operands(False, q_shape[0], 1, lambda b: (b, 0), lambda b: (kv(b), 0))


def _lanes_shape(shape):
    """``[B, T, H, D] -> [B, T, H·D]``; a ``[B·H, T, D]`` shape as it is."""
    return shape if len(shape) == 3 else (*shape[:2], shape[2] * shape[3])


def _lanes(x):
    """The model's ``[B, T, H, D]`` as the ``[B, T, H·D]`` the kernels
    address (the same bytes); a ``[B·H, T, D]`` array as it is."""
    return x if x.ndim == 3 else x.reshape(_lanes_shape(x.shape))


def _head_sums(x, heads: int):
    """``f32[B, T, H·D] -> f32[B, H, T]``: each head's ``D`` lanes summed, as a
    product with the heads' 0/1 indicator.  XLA's own ``sum`` over the
    ``[B, T, H, D]`` view wants ``T`` on the lanes first and re-lays the whole
    array for it (a copy of ``f32[12, 1024, 768]`` a layer in cell 1's
    compiled step); the product's emitter reads the array as it lies, takes
    the elementwise pass that made it as its own input, and writes the
    statistics' layout.  At ``HIGHEST`` the float32 operand goes through whole
    (three bfloat16 pieces against an exact indicator) and the sum is
    accumulated in float32."""
    width = x.shape[-1]
    indicator = jnp.arange(width)[:, None] // (width // heads) == jnp.arange(heads)[None, :]
    return jnp.einsum(
        "btc,ch->bht", x, indicator.astype(x.dtype),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


def _own(block: int, width: int, at):
    """The spec of grid position ``(b, i)``'s own ``block`` rows of an
    operand, ``width`` lanes at ``at(b)``."""
    def index(b, i):
        row, lane = at(b)
        return row, i, lane

    return pl.BlockSpec((1, block, width), index)


def _resident(T: int, width: int, band: Optional[int], block: int, at, first):
    """The spec of an operand's ``width`` lanes at ``at(b)`` held whole, or
    (``band`` blocks) from block ``first(i)`` of grid position ``i`` on:
    element-indexed, the offsets multiples of the block so that Mosaic can
    prove them aligned."""
    def whole(b, i):
        row, lane = at(b)
        return row, 0, lane

    def banded(b, i):
        row, lane = at(b)
        return row, first(i) * block, lane * width

    if band is None:
        return pl.BlockSpec((1, T, width), whole)
    return pl.BlockSpec((pl.Element(1), pl.Element(band * block), pl.Element(width)), banded)


def _stat_row(T: int, pair: int = 1):
    """The spec of a ``[B·H, 1, T]`` statistic: the rows of grid row ``b``'s
    ``pair`` heads, whole.  As the forward's result it is one block that
    every program along grid axis 1 revisits, each storing its own ``bq``
    lanes; it is written back when ``b`` moves on.  That holds while axis 1 runs in order on one core
    (Mosaic's default, ``arbitrary``): marked ``parallel`` for a chip with
    two cores, each core would write back a whole row over the other's
    lanes, so that axis takes no such mark while the row is the block."""
    return pl.BlockSpec((pair, 1, T), lambda b, i: (b, 0, 0))


def _kv_index(groups: int):
    """Query head ``b`` of ``[B·H_q]`` reads KV head ``b // groups`` of
    ``[B·H_kv]`` (``H_q = groups · H_kv``, heads minor)."""
    return (lambda b: b) if groups == 1 else (lambda b: b // groups)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash_bhtd(q, k, v, scale, causal, block_q, block_k, interpret, window):
    """``q, k, v`` as ``[B·H, T, D]``, or all three as the model's
    ``[B, T, H, D]`` where :func:`_bthd_call` found that the kernels can read
    them in place; ``o`` comes back in the layout ``q`` came in."""
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window):
    T, D = q.shape[1], q.shape[-1]
    bq, bk = _block_sizes(T, D, q.dtype, block_q, block_k)
    _record_tiles(T, bq, bk, causal, 1, window, _operands(q.shape, k.shape))
    interp = resolve_interpret(interpret, "flash_attention")
    out, lse = _fwd_call(q, k, v, scale, causal, bq, bk, interp, window)
    return out, (q, k, v, out, lse)


# The pallas_calls sit behind jax.jit so that a model's layers, which call them
# with one shape and one set of parameters, share one traced and lowered kernel:
# JAX traces a pallas_call's kernel anew at every call site, and a 24-layer
# step pays 72 of them, twice (PERF.md §6, PR 25: the step's trace).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd_call(q, k, v, scale, causal, bq, bk, interp, window):
    T, D, Dv = q.shape[1], q.shape[-1], v.shape[-1]
    where = _operands(q.shape, k.shape)
    pair, heads = where.pair, where.rows * where.pair
    band = _band_blocks(T, bq, bk, window)
    ahead = 0 if band is None else band - 1     # the band starts this many blocks before the diagonal
    held = lambda d: _resident(T, pair * d, band, bk, where.kv_at, lambda i: jnp.maximum(i - ahead, 0))  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk, window=window,
            seq=T, band=band, pair=pair,
        ),
        grid=(where.rows, T // bq),
        in_specs=[_own(bq, pair * D, where.q_at), held(D), held(Dv)],
        out_specs=[
            _own(bq, pair * Dv, where.q_at),
            _stat_row(T, pair),   # revisited along axis 1, which therefore stays "arbitrary" (see _stat_row)
        ],
        out_shape=[
            jax.ShapeDtypeStruct(_lanes_shape((*q.shape[:-1], Dv)), q.dtype),
            jax.ShapeDtypeStruct((heads, 1, T), jnp.float32),
        ],
        interpret=interp,
        name="flash_fwd",
        **_compiler_params(T if band is None else band * bk, pair * D, pair * Dv, k.dtype),
    )(_lanes(q), _lanes(k), _lanes(v))
    return out.reshape(*q.shape[:-1], Dv), lse.reshape(heads, T)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, do):
    return _flash_bwd_core(scale, causal, block_q, block_k, interpret, window, res, do, None)


def _flash_bwd_core(scale, causal, block_q, block_k, interpret, window, res, do, dlse):
    """Shared backward.  An ``lse`` cotangent adds ``dS_ij += p_ij·dlse_i``,
    which folds into the existing kernels as ``delta → delta − dlse`` (the
    bracket is ``p·(dp − delta)``) — no kernel change needed."""
    q, k = res[0], res[1]
    T, D = q.shape[1], q.shape[-1]
    bq, bk = _block_sizes(T, D, q.dtype, block_q, block_k)
    # a backward pass closes an attention call: its forward kernel and these two
    _record_tiles(T, bq, bk, causal, 3, window, _operands(q.shape, k.shape))
    interp = resolve_interpret(interpret, "flash_attention")
    return _bwd_call(res, do, dlse, scale, causal, bq, bk, interp, window)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _bwd_call(res, do, dlse, scale, causal, bq, bk, interp, window):
    q, k, v, out, lse = res
    T, D, Dv = q.shape[1], q.shape[-1], v.shape[-1]
    where = _operands(q.shape, k.shape)
    pair, heads = where.pair, where.rows * where.pair
    groups = 1 if where.in_place else heads // k.shape[0]
    if where.in_place:
        delta = _head_sums(_lanes(do).astype(jnp.float32) * _lanes(out).astype(jnp.float32), q.shape[2]).reshape(heads, T)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    stats = lse[:, None, :], delta[:, None, :]
    operands = tuple(map(_lanes, (q, k, v, do)))

    band = _band_blocks(T, bq, bk, window)
    rows = T if band is None else band * bk
    ahead = 0 if band is None else band - 1
    held = lambda d: _resident(T, pair * d, band, bk, where.kv_at, lambda i: jnp.maximum(i - ahead, 0))  # noqa: E731
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk, window=window,
            seq=T, band=band, pair=pair,
        ),
        grid=(where.rows, T // bq),
        in_specs=[
            _own(bq, pair * D, where.q_at),
            held(D),
            held(Dv),
            _own(bq, pair * Dv, where.q_at),
            _stat_row(T, pair),
            _stat_row(T, pair),
        ],
        out_specs=_own(bq, pair * D, where.q_at),
        out_shape=jax.ShapeDtypeStruct(operands[0].shape, q.dtype),
        interpret=interp,
        name="flash_bwd_dq",
        **_compiler_params(rows, pair * D, pair * Dv, k.dtype),
    )(*operands, *stats)

    # grouped heads: each query head writes its own fp32 share of dk and dv
    # (a grid program owns its output block), summed over the group below
    part = jnp.float32 if groups > 1 else None
    last = 0 if band is None else T // bq - band    # the last block a band of queries can start at
    seen = lambda d: _resident(T, pair * d, band, bq, where.q_at, lambda j: jnp.minimum(j, last))  # noqa: E731
    if band is None:
        stat = _stat_row(T, pair)
    else:
        stat = pl.BlockSpec(     # element-indexed: grid row b's heads start at row pair·b
            (pl.Element(pair), pl.Element(1), pl.Element(rows)),
            lambda b, j: (b if pair == 1 else b * pair, 0, jnp.minimum(j, last) * bq),
        )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk, window=window,
            seq=T, band=band, pair=pair,
        ),
        grid=(where.rows, T // bk),
        in_specs=[
            seen(D),
            _own(bk, pair * D, where.kv_at),
            _own(bk, pair * Dv, where.kv_at),
            seen(Dv),
            stat,
            stat,
        ],
        out_specs=[
            _own(bk, pair * D, where.q_at),
            _own(bk, pair * Dv, where.q_at),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(operands[0].shape, part or k.dtype),
            jax.ShapeDtypeStruct(operands[3].shape, part or v.dtype),
        ],
        interpret=interp,
        name="flash_bwd_dkv",
        **_compiler_params(rows, pair * D, pair * Dv, q.dtype),
    )(*operands, *stats)
    if groups > 1:
        dk = dk.reshape(heads // groups, groups, T, D).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(heads // groups, groups, T, Dv).sum(axis=1).astype(v.dtype)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash_bhtd.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhtd_lse(q, k, v, scale, causal, block_q, block_k, interpret, window):
    """Like :func:`_flash_bhtd` but also returns the per-row logsumexp —
    the merge statistic blockwise consumers (ring attention) need.  Both
    outputs are differentiable: the ``lse`` cotangent lowers to the same
    backward kernels via ``delta − dlse``."""
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window)
    return out, lse


def _flash_fwd_lse(q, k, v, scale, causal, block_q, block_k, interpret, window):
    out, res = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window)
    return (out, res[4]), res


def _flash_bwd_lse(scale, causal, block_q, block_k, interpret, window, res, cts):
    do, dlse = cts
    return _flash_bwd_core(scale, causal, block_q, block_k, interpret, window, res, do, dlse)


_flash_bhtd_lse.defvjp(_flash_fwd_lse, _flash_bwd_lse)


def _bthd_call(kernel_entry, q, k, v, causal, scale, block_q, block_k, interpret, window=None):
    """Shared model-layout plumbing for the public wrappers: validate,
    default the scale, and run ``kernel_entry`` on the arrays as they are
    where the kernels can read them in place (the rule of "Where the operands
    live": decided here, by the shapes, and nowhere else), else on
    ``[B·H, T, D]`` copies; returns its raw outputs, ``o`` as ``[B, T, H,
    D_v]`` either way."""
    B, T, H, D = q.shape
    Hkv = k.shape[2] if k.ndim == 4 else 0
    if k.shape[:-1] != v.shape[:-1] or k.shape != (B, T, Hkv, D) or Hkv == 0 or H % Hkv:
        raise ValueError(
            f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape} (k and v may "
            "only carry fewer heads than q, a whole number of query heads to each; "
            "v alone may have a head size of its own)"
        )
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal=True and at least one position")
        window = None if window >= T else int(window)   # a band over the whole triangle
    if scale is None:
        scale = float(1.0 / np.sqrt(D))
    pair = _lane_pair(D)
    if q.shape == k.shape == v.shape and (pair * D) % _LANES == 0 and H % pair == 0:
        return kernel_entry(q, k, v, scale, causal, block_q, block_k, interpret, window)
    to_bhtd = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, T, x.shape[-1])  # noqa: E731
    from_bhtd = lambda o: o.reshape(B, H, T, v.shape[-1]).transpose(0, 2, 1, 3)  # noqa: E731
    raw = kernel_entry(
        to_bhtd(q), to_bhtd(k), to_bhtd(v),
        scale, causal, block_q, block_k, interpret, window,
    )
    return (from_bhtd(raw[0]), raw[1]) if isinstance(raw, tuple) else from_bhtd(raw)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Blockwise attention over ``[B, T, H, D]`` tensors (model layout).

    ``interpret=None`` asks :func:`ops.kernel_mode.resolve_interpret`, which
    records what it decided — the Pallas interpreter off-TPU, so the
    same call works on the virtual CPU pod.  ``scale`` defaults to
    ``1/sqrt(D)``.  ``block_q`` / ``block_k`` default to the tile the table
    holds for ``(T, D, dtype)`` (:func:`default_blocks`); given, ``T`` must
    divide by them (clamped to ``T``).  ``window=W`` (causal only) lets a
    query see the ``W`` keys up to and including its own position; ``k`` and
    ``v`` may be ``[B, T, H_kv, D]`` with ``H`` a multiple of ``H_kv``
    (query head ``h`` reads KV head ``h // (H // H_kv)``).
    """
    return _bthd_call(_flash_bhtd, q, k, v, causal, scale, block_q, block_k, interpret, window)


def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Blockwise attention returning ``(out [B,T,H,D], lse [B,H,T])``.

    ``lse[b,h,t] = logsumexp_j(scale·q_t·k_j)`` (with the causal mask
    applied) — the statistic a blockwise consumer needs to merge partial
    attention over K/V blocks it sees one at a time (ring attention's
    log-sum-exp combine).  Fully differentiable in both outputs.
    """
    out, lse = _bthd_call(_flash_bhtd_lse, q, k, v, causal, scale, block_q, block_k, interpret)
    return out, lse.reshape(q.shape[0], q.shape[2], q.shape[1])
