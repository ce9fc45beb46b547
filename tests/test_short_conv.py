"""``ops/short_conv.py`` in the Pallas interpreter, against the plain
``jax.numpy`` form it took the place of: value and every gradient at rows
inside one block, over several and filling none, at channels that fill one
lane tile, several, 34 and none; without a bias; a batch of two; bfloat16;
causality and the halo across a block's edge; no reset at a packed join; what
it records about itself; the two kernels' names and operand counts.

Every shape is computed once (``case``): a shape is a compile of two kernels
in the interpreter.  A block of rows is up to 4,096 long on the chip; the
cases over several blocks shorten it here (``rows_at_most``: the test steers
the plan, the program has no option for it), so that they stay a thousand rows.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.ops import short_conv as sc
from adapcc_tpu.ops.kernel_mode import interpret_decisions
from adapcc_tpu.ops.short_conv import plan_for, short_conv
from adapcc_tpu.utils.observability import default_registry

K = 4


def plain(x, taps, bias=None):
    """The oracle: the parent's eight lines, the bias and the silu, float32 throughout."""
    T = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    y = sum(taps[j].astype(jnp.float32) * padded[:, j:j + T] for j in range(taps.shape[0]))
    return jax.nn.silu(y if bias is None else y + bias.astype(jnp.float32))


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
def case(B, T, C, biased, rows=None, dtype=jnp.float32):
    """``(y, gradients)`` of the kernel and of the oracle on the same arrays
    (the oracle's on the arrays in float32)."""
    x, dy, params = arrays(B, T, C, biased, dtype)
    with rows_at_most(rows):
        y, vjp = jax.vjp(short_conv, x, *params)
    want, vjp_plain = jax.vjp(plain, x.astype(jnp.float32), *params)
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


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "B{}-T{}-C{}-{}".format(*s[:3], "bias" if s[3] else "nobias"))
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
        assert (plan.rows, plan.tiles, Tp, Cp) == (rows, tiles, 8192, C)


def test_bfloat16_is_rounded_once_at_the_output_and_its_gradients_within_its_step():
    """The sum, the bias and the silu in float32, one rounding: the result is
    the float32 oracle's on the same (bfloat16) inputs, rounded; the parent
    rounded after the sum, after the bias and after the silu."""
    (y, grads), (want, want_grads) = case(1, 1040, 384, True, 512, jnp.bfloat16)
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


def test_it_records_that_it_engaged_and_on_what_blocks():
    """``conv.calls`` counts the call sites JAX traced (a call run eagerly is
    one trace); the two gauges are the last call's blocks."""
    metrics = default_registry()
    before = metrics.snapshot()["counters"].get("conv.calls", 0)
    x, _, (taps, bias) = arrays(1, 48, 4352, True)
    jax.eval_shape(lambda x, t, b: (short_conv(x, t, b), short_conv(x, t)), x, taps, bias)
    snap = metrics.snapshot()
    assert snap["counters"]["conv.calls"] == before + 2
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


@pytest.mark.parametrize("biased", [True, False], ids=["bias", "nobias"])
def test_the_two_kernels_carry_their_names_and_no_flash_kernels_signature(biased):
    """``chipbench/trace_reduce.flash_kernel`` takes a Mosaic call of three
    operands for ``flash_fwd`` and one of six for a flash backward kernel."""
    x, dy, params = arrays(1, 24, 96, biased)
    calls = _pallas_calls(jax.make_jaxpr(lambda x, dy, *p: jax.vjp(short_conv, x, *p)[1](dy))(x, dy, *params).jaxpr, [])
    assert calls == [("short_conv_fwd", 2, 1), ("short_conv_bwd", 4, 2)]
    assert all(operands not in (3, 6) for _, operands, _ in calls)
    assert sc._fwd_call.__wrapped__ is not None and sc._bwd_call.__wrapped__ is not None     # each behind one jax.jit
