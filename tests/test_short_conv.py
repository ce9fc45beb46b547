"""``ops/short_conv.py`` in the Pallas interpreter, against the plain
``jax.numpy`` form it took the place of: value and every gradient at rows
inside one block, over several and filling none, at channels that fill one
lane tile, several, 34 and none; without a bias; a batch of two; bfloat16;
causality and the halo across a block's edge; no reset at a packed join; what
it records about itself; the two kernels' names and operand counts.  With a
per-head L2 norm riding the kernels (``norm_heads``): the same against
``l2norm(silu(conv))``, heads of 128, of two lane tiles and of sixteen channels.

Every shape is computed once (``case``): a shape is a compile of two kernels
in the interpreter.  A block of rows is up to 4,096 long on the chip; the
cases over several blocks shorten it here (``rows_at_most``: the test steers
the plan, the program has no option for it), so that they stay a thousand rows.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.models.kimi_linear import L2_EPS, l2norm
from adapcc_tpu.ops import short_conv as sc
from adapcc_tpu.ops.kernel_mode import interpret_decisions
from adapcc_tpu.ops.short_conv import plan_for, short_conv
from adapcc_tpu.utils.observability import default_registry

K = 4


def plain(x, taps, bias=None, heads=None):
    """The oracle: the parent's eight lines, the bias and the silu, and the
    mixer's own ``l2norm`` behind them where ``heads`` says so, float32
    throughout."""
    T = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    y = sum(taps[j].astype(jnp.float32) * padded[:, j:j + T] for j in range(taps.shape[0]))
    y = jax.nn.silu(y if bias is None else y + bias.astype(jnp.float32))
    return y if heads is None else l2norm(y, heads)


def arrays(B, T, C, biased, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed + 7 * T + C), 4)
    x = jax.random.normal(k[0], (B, T, C), jnp.float32).astype(dtype)
    dy = jax.random.normal(k[1], (B, T, C), jnp.float32).astype(dtype)
    taps = jax.random.uniform(k[2], (K, C), jnp.float32, -0.5, 0.5)
    return x, dy, (taps, 0.3 * jax.random.normal(k[3], (C,), jnp.float32)) if biased else (taps,)


@contextlib.contextmanager
def rows_at_most(rows):
    """``plan_for`` with blocks of ``rows`` rows at most (None: as the program has it)."""
    with pytest.MonkeyPatch.context() as patch:
        if rows is not None:
            patch.setattr(sc, "_ROWS", rows)
        yield


@functools.lru_cache(maxsize=None)
def case(B, T, C, biased, rows=None, heads=None, dtype=jnp.float32):
    """``(y, gradients)`` of the kernel and of the oracle on the same arrays
    (the oracle's on the arrays in float32)."""
    x, dy, params = arrays(B, T, C, biased, dtype)
    with rows_at_most(rows):
        y, vjp = jax.vjp(lambda x, *p: short_conv(x, *p, norm_heads=heads, norm_eps=L2_EPS), x, *params)
    want, vjp_plain = jax.vjp(lambda x, *p: plain(x, *p, heads=heads), x.astype(jnp.float32), *params)
    return (y, vjp(dy)), (want, vjp_plain(dy.astype(jnp.float32)))


def close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=tol * float(np.abs(want).max()), rtol=0)


#: B, T, C, biased, the longest block: rows inside one block (24 of 32), over several whole ones (1,024 = 2 x 512),
#: over several and padded (1,040 = 3 x 352 less 16); 96 channels (the tiny models'), one lane tile, 34 (cell 6's
#: 4,352: two a grid step, seventeen steps), a width that fills none (200); no bias; a batch of two
SHAPES = [
    (1, 24, 96, True, None), (1, 24, 128, False, None), (2, 40, 200, True, None), (1, 48, 4352, True, None),
    (1, 1024, 128, True, 512), (2, 1040, 96, False, 512), (1, 1040, 384, True, 512),
]

#: and the heads normed: heads of 128 at 256 channels (one chunk of lanes a grid step) and at 512 (two), one at 128; a
#: head of two lane tiles; three heads of 128 over padded rows and several blocks; the tiny models' two heads of 16
#: (the interpreter's alone: eight heads to a lane tile, six of them padding); a batch of two; with and without a bias
NORMED = [
    (1, 24, 256, False, None, 2), (1, 24, 512, True, None, 4), (2, 40, 128, True, None, 1), (1, 24, 512, False, None, 2),
    (1, 1040, 384, False, 512, 3), (1, 1040, 384, True, 512, 3), (2, 40, 32, False, None, 2),
]


def _case_id(s):
    return "B{}-T{}-C{}-{}".format(*s[:3], "bias" if s[3] else "nobias") + (f"-H{s[5]}" if len(s) > 5 else "")


@pytest.mark.parametrize("shape", SHAPES + NORMED, ids=_case_id)
def test_value_and_every_gradient_match_the_plain_form_in_float32(shape):
    (y, grads), (want, want_grads) = case(*shape)
    assert y.shape == want.shape and y.dtype == jnp.float32 and len(grads) == (3 if shape[3] else 2)
    close(y, want, 1e-5)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        close(got, ref, 1e-5)


def test_the_plans_of_the_tested_shapes_cover_one_block_several_and_a_padded_one():
    assert plan_for(24, 96)[0].rows == 32 and plan_for(24, 96)[1:] == (32, 128)
    assert plan_for(48, 4352)[0].tiles == 2 and plan_for(40, 200)[2] == 256
    with rows_at_most(512):
        assert plan_for(1024, 128)[0].rows == 512 and plan_for(1024, 128)[1] == 1024
        assert plan_for(1040, 384)[0].rows == 352 and plan_for(1040, 384)[1:] == (1056, 384)
    # the published widths: whole blocks and whole lane tiles at T = 8,192 (no copy), about a million elements a block
    for C, rows, tiles in ((4352, 4096, 2), (4096, 1024, 8), (5120, 1024, 8)):
        plan, Tp, Cp = plan_for(8192, C)
        assert (plan.rows, plan.tiles, plan.chunk, Tp, Cp) == (rows, tiles, 256, 8192, C)
    # a norm moves no block of Kimi-Linear's q and k (32 heads of 128: two heads a chunk of lanes) and writes eight
    # groups out a turn for two; a head of two lane tiles is a chunk, of four two chunks' lanes walked at once; heads of
    # sixteen channels pad to a lane tile of them, of 48 to three lane tiles
    normed = plan_for(8192, 4096, head=128, eps=L2_EPS)[0]
    assert normed[:4] == plan_for(8192, 4096)[0][:4] and (normed.per, plan_for(8192, 4096)[0].per) == (8, 2)
    assert [plan_for(24, C, head=head)[0].chunk for C, head in ((256, 128), (128, 128), (512, 256), (1024, 512))] == [256, 128, 256, 512]
    assert plan_for(40, 32, head=16)[0].chunk == 128 and plan_for(40, 32, head=16)[1:] == (128, 128)
    assert plan_for(40, 96, head=48)[1:] == (128, 384) and plan_for(40, 96, head=48)[0].chunk == 384
    with rows_at_most(512):
        assert plan_for(1040, 384, head=128)[0].rows == 384 and plan_for(1040, 384, head=128)[1] == 1152


@pytest.mark.parametrize("heads", [None, 3], ids=["plain", "normed"])
def test_bfloat16_is_rounded_once_at_the_output_and_its_gradients_within_its_step(heads):
    """The sum, the bias and the silu in float32, one rounding: the result is
    the float32 oracle's on the same (bfloat16) inputs, rounded; the parent
    rounded after the sum, after the bias and after the silu.  The norm rides
    the same float32 ``y``: no rounding stands between the silu and it."""
    (y, grads), (want, want_grads) = case(1, 1040, 384, True, 512, heads, jnp.bfloat16)
    assert y.dtype == jnp.bfloat16 and grads[0].dtype == jnp.bfloat16 and grads[1].dtype == grads[2].dtype == jnp.float32
    rounded = np.asarray(want.astype(jnp.bfloat16), np.float32)
    got = np.asarray(y, np.float32)
    assert (got != rounded).mean() < 1e-3           # a sum that lands on a rounding boundary may fall either way
    np.testing.assert_allclose(got, rounded, rtol=2 ** -7, atol=1e-6)
    close(grads[0], want_grads[0], 2 ** -8)         # dx is rounded to bfloat16: half a step of its largest entry
    close(grads[1], want_grads[1], 1e-5)            # the parameters' gradients are summed and handed out in float32
    close(grads[2], want_grads[2], 1e-5)


def test_an_input_moves_no_output_before_it_and_the_halo_crosses_a_blocks_edge():
    """Row 351 is the last of the first block of rows at T = 1,040: changing
    ``x`` there moves rows 351 to 354 (the next block's first three among
    them) by what the oracle says, in that channel alone, and nothing else."""
    x, _, (taps, bias) = arrays(1, 1040, 384, True)
    moved = x.at[0, 351, 5].add(1.0)
    with rows_at_most(512):
        assert plan_for(1040, 384)[0].rows == 352
        y, y2 = np.asarray(short_conv(x, taps, bias)), np.asarray(short_conv(moved, taps, bias))
    changed = np.argwhere(y2 != y)
    assert sorted(set(changed[:, 1])) == [351, 352, 353, 354] and set(changed[:, 2]) == {5}
    close(y2, plain(moved, taps, bias), 1e-5)


def test_a_packed_join_resets_nothing():
    """Two documents packed into one row: the second one's first ``K - 1``
    outputs see the first one's last inputs, as the references' do."""
    x, _, (taps,) = arrays(2, 40, 200, False)
    first, second = x[:1], x[1:]
    packed = np.asarray(short_conv(jnp.concatenate([first, second], axis=1), taps))
    alone = np.asarray(short_conv(second, taps))
    close(packed, plain(jnp.concatenate([first, second], axis=1), taps), 1e-5)
    assert np.abs(packed[0, 40:43] - alone[0, :3]).max() > 1e-3
    np.testing.assert_allclose(packed[0, 43:], alone[0, 3:], atol=1e-6)


def test_the_shapes_it_refuses():
    x, _, (taps, bias) = arrays(1, 24, 96, True)
    for bad in ((x, taps[:, :95]), (x, taps, bias[:95]), (x, jnp.zeros((8, 96)))):
        with pytest.raises(ValueError, match="short_conv shapes"):
            short_conv(*bad)
    with pytest.raises(ValueError, match="short_conv shapes: 96 channels are no 5 heads"):
        short_conv(x, taps, norm_heads=5)


def test_a_normed_head_that_is_no_whole_lane_tile_is_refused_through_mosaic_and_taken_by_the_interpreter():
    """Heads of 48 channels: Mosaic's path raises before any kernel is built
    (a lane reduction there is over whole lane tiles), the interpreter walks
    them three lane tiles, eight heads, at once."""
    x, dy, (taps,) = arrays(1, 24, 96, False)
    with pytest.raises(ValueError, match=r"through Mosaic norms heads of whole lane tiles.*2 heads of 48"):
        short_conv(x, taps, interpret=False, norm_heads=2)
    close(short_conv(x, taps, norm_heads=2, norm_eps=L2_EPS), plain(x, taps, heads=2), 1e-5)


#: sha256 of the two kernels' traced jaxprs at 1f4764e (the parent of PR 43, which gave them the norm), two rows of
#: bfloat16 ``[48, 4352]`` biased (cell 6's width) and of float32 ``[1056, 384]`` unbiased over three blocks of rows (``rows_at_most(512)``)
_PARENT_TRACED = {
    (48, 4352, True, jnp.bfloat16): "29e9d2c9c933df16f95c25f3ee2c53ec9867be0acc0cd3592c5d3de3de359e6f",
    (1040, 384, False, jnp.float32): "35873e08af1d8210c4c684d92d8f9da0d2e6ffb98ca3fe9a3253216cdc646330",
}


@pytest.mark.parametrize("shape", sorted(_PARENT_TRACED, key=str), ids=lambda s: f"T{s[0]}-C{s[1]}")
def test_without_a_norm_the_kernels_trace_to_what_they_traced_to(shape):
    """``norm_heads=None`` is the parent's program: the two kernels' jaxprs
    (bodies, grids, block maps) are the parent's character for character, so
    the values are the parent's to the bit."""
    T, C, biased, dtype = shape
    with rows_at_most(512):
        plan, Tp, Cp = plan_for(T, C, K, biased)
        x, w = jax.ShapeDtypeStruct((2, Tp, Cp), dtype), jax.ShapeDtypeStruct((8, Cp), jnp.float32)
        text = str(jax.make_jaxpr(lambda x, w, dy: (sc._fwd_call(x, w, plan, True), sc._bwd_call(x, w, dy, plan, True)))(x, w, x))
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TRACED[shape]


def test_it_records_that_it_engaged_and_on_what_blocks():
    """``conv.calls`` counts the call sites JAX traced (a call run eagerly is
    one trace); the two gauges are the last call's blocks."""
    metrics = default_registry()
    before = {name: metrics.snapshot()["counters"].get(name, 0) for name in ("conv.calls", "conv.norm_calls")}
    x, _, (taps, bias) = arrays(1, 48, 4352, True)
    jax.eval_shape(lambda x, t, b: (short_conv(x, t, norm_heads=34), short_conv(x, t, b), short_conv(x, t)), x, taps, bias)
    snap = metrics.snapshot()
    assert snap["counters"]["conv.calls"] == before["conv.calls"] + 3
    assert snap["counters"]["conv.norm_calls"] == before["conv.norm_calls"] + 1       # the calls among them with a norm
    assert snap["gauges"]["conv.block_rows"] == 64 and snap["gauges"]["conv.lane_tiles"] == 2
    assert interpret_decisions()["short_conv"] is True          # off the chip; a chip run wants False


def _pallas_calls(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], len(eqn.invars), len(eqn.outvars)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)          # a ClosedJaxpr's jaxpr, a jit's ClosedJaxpr
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


@pytest.mark.parametrize("biased, heads", [(True, None), (False, None), (False, 2)], ids=["bias", "nobias", "normed"])
def test_the_two_kernels_carry_their_names_and_no_flash_kernels_signature(biased, heads):
    """``chipbench/trace_reduce.flash_kernel`` takes a Mosaic call of three
    operands for ``flash_fwd`` and one of six for a flash backward kernel.
    The norm adds no operand and no result: no statistic leaves the forward
    kernel and the backward one forms it again."""
    x, dy, params = arrays(1, 24, 96, biased)
    op = functools.partial(short_conv, norm_heads=heads)
    calls = _pallas_calls(jax.make_jaxpr(lambda x, dy, *p: jax.vjp(op, x, *p)[1](dy))(x, dy, *params).jaxpr, [])
    assert calls == [("short_conv_fwd", 2, 1), ("short_conv_bwd", 4, 2)]
    assert all(operands not in (3, 6) for _, operands, _ in calls)
    assert sc._fwd_call.__wrapped__ is not None and sc._bwd_call.__wrapped__ is not None     # each behind one jax.jit
