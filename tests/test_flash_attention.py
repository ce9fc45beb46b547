"""Oracle tests for the blockwise (flash) attention Pallas kernels.

The dense oracle materializes the full ``[T, T]`` attention matrix — what
the reference's HF GPT-2 does in HBM (SURVEY §2.4) and what
ops/flash_attention.py exists to avoid.  Forward and all three gradients
must match it; the Pallas interpreter runs on the CPU pod (Mosaic lowering
is covered separately by tests/test_tpu_smoke.py).
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.ops import flash_attention, flash_attention_with_lse
from adapcc_tpu.ops.flash_attention import (
    TILE_TABLE,
    default_blocks,
    looped_tiles,
    resolve_block,
    visited_tiles,
)
from adapcc_tpu.utils.observability import default_registry

#: the module (the package's attribute of that name is the function)
flash_module = sys.modules["adapcc_tpu.ops.flash_attention"]


def _dense_attention_lse(q, k, v, causal=True, scale=None):
    """The fp32 oracle with its logsumexp ``[B, H, T]``."""
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        att = jnp.where(mask[None, None], att, -1e30)
    lse = jax.nn.logsumexp(att, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(att - lse[..., None]), v.astype(jnp.float32))
    return out, lse


def _dense_attention(q, k, v, causal=True, scale=None):
    return _dense_attention_lse(q, k, v, causal, scale)[0].astype(q.dtype)


def _qkv(T=128, B=2, H=2, D=16, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)) * 0.5, dtype)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense_oracle(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense_oracle(causal):
    q, k, v = _qkv(T=64)
    do = jnp.asarray(np.random.default_rng(9).normal(size=q.shape), jnp.float32)

    def flash_loss(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal, block_q=32, block_k=32), do)

    def dense_loss(q, k, v):
        return jnp.vdot(_dense_attention(q, k, v, causal=causal), do)

    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("q k v".split(), gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name} mismatch"
        )


def test_bfloat16_forward_and_grads_close_to_fp32_oracle():
    q, k, v = _qkv(T=64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref), atol=0.05
    )

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
                       .astype(jnp.float32) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert g.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(g, dtype=np.float32)).all()


def test_uneven_block_split_raises():
    q, k, v = _qkv(T=48)
    with pytest.raises(ValueError, match="divide into blocks"):
        flash_attention(q, k, v, block_q=32, block_k=32)


def test_mismatched_shapes_raise():
    q, k, v = _qkv(T=32)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k[:, :16], v)


def test_custom_scale_respected():
    q, k, v = _qkv(T=32)
    out = flash_attention(q, k, v, causal=True, scale=0.5, block_q=32, block_k=32)
    ref = _dense_attention(q, k, v, causal=True, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gpt2_flash_config_trains():
    """The model-level flash branch (models/gpt2.py attention == "flash"):
    one grad step, finite loss, and forward parity with the XLA-attention
    config on identical params."""
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

    base = dict(vocab_size=128, max_seq=32, n_layer=2, n_head=2, d_model=32,
                dtype=jnp.float32)
    cfg_flash = GPT2Config(**base, attention="flash")
    cfg_xla = GPT2Config(**base, attention="xla")
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 128, size=(2, 32)), jnp.int32
    )
    model_f, model_x = GPT2(cfg_flash), GPT2(cfg_xla)
    params = model_f.init(jax.random.PRNGKey(0), tokens)

    out_f = model_f.apply(params, tokens)
    out_x = model_x.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_x), atol=2e-4,
        err_msg="flash and xla attention configs diverge on identical params",
    )

    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(model_f.apply(p, tokens), tokens)
    )(params)
    assert np.isfinite(float(loss))
    finite = jax.tree_util.tree_all(
        jax.tree_util.tree_map(lambda g: bool(np.isfinite(np.asarray(g)).all()), grads)
    )
    assert finite, "non-finite grads through the flash branch"


def test_unaligned_block_raises_clearly():
    # bq=12 divides T=24 but violates Mosaic's 8-sublane alignment for the
    # lane-padded lse/delta block specs; must fail at trace time with the
    # real reason, not deep inside Mosaic on hardware (ADVICE r4)
    q, k, v = _qkv(T=24)
    with pytest.raises(ValueError, match="multiple of 8"):
        flash_attention(q, k, v, block_q=12, block_k=24)
    with pytest.raises(ValueError, match="multiple of 8"):
        flash_attention(q, k, v, block_q=24, block_k=12)
    # degenerate full-sequence block is exempt even when unaligned
    q4, k4, v4 = _qkv(T=4)
    out = flash_attention(q4, k4, v4, block_q=4, block_k=4)
    assert out.shape == q4.shape


# --- PR 25: only the causal tiles, in the inputs' own dtype, tile by shape ----


#: (block_q, block_k) at T=64: equal, bq > bk, bq < bk, one block
_BLOCKS = [(16, 16), (32, 16), (16, 32), (64, 64)]


#: ``(_STRAIGHT_LINE_ELEMENTS, _TRACED_BASE_ELEMENTS)`` by the way a causal
#: kernel walks its tiles: the module's own (every shape of this file is
#: written out position by position); nothing written out (one body, one tile
#: a turn of a loop); one body with four 16 x 16 tiles of code (at T=64 with
#: such tiles runs of two in a loop, then one under the count's bit; with
#: larger tiles one tile a turn)
_LIMITS = {"static": None, "traced": (0, 0), "runs": (0, 1024)}


def _forget():
    """The jitted kernel calls cache their trace by shape, not by the limits."""
    flash_module._fwd_call.clear_cache()
    flash_module._bwd_call.clear_cache()


def _limits(monkeypatch, bodies, traced_base):
    monkeypatch.setattr(flash_module, "_STRAIGHT_LINE_ELEMENTS", bodies)
    monkeypatch.setattr(flash_module, "_TRACED_BASE_ELEMENTS", traced_base)
    _forget()


@pytest.fixture(params=list(_LIMITS))
def program_index(request, monkeypatch):
    """The three ways a causal kernel learns its grid position and walks its
    tiles: a Python integer for each position (short sequences); or, once the
    written-out code would pass ``_STRAIGHT_LINE_ELEMENTS``, the traced
    ``program_id`` with one tile a turn of a loop, or with written-out runs
    of tiles by what the code sees of their count."""
    if _LIMITS[request.param] is not None:
        _limits(monkeypatch, *_LIMITS[request.param])
    _forget()
    yield request.param
    _forget()


@pytest.mark.parametrize("with_lse", [False, True], ids=["out", "out+lse"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", _BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
def test_forward_and_grads_match_oracle_at_every_block_shape(blocks, causal, with_lse, program_index):
    """fp32 inputs keep fp32 products: forward within 2e-5, the three
    gradients within 5e-5, with and without an ``lse`` cotangent (the ring's
    ``delta - dlse`` path), whichever of the two blocks is the larger."""
    bq, bk = blocks
    q, k, v = _qkv(T=64)
    rng = np.random.default_rng(11)
    do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    dl = jnp.asarray(rng.normal(size=(q.shape[0], q.shape[2], q.shape[1])), jnp.float32)

    def flash(q, k, v):
        if not with_lse:
            return flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk), None
        return flash_attention_with_lse(q, k, v, causal=causal, block_q=bq, block_k=bk)

    def loss_of(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.vdot(out, do) + (jnp.vdot(lse, dl) if with_lse else 0.0)
        return loss

    out, lse = flash(q, k, v)
    ref_out, ref_lse = _dense_attention_lse(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)
    if with_lse:
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)
    gf = jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_of(lambda q, k, v: _dense_attention_lse(q, k, v, causal)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("q k v".split(), gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)], ids=lambda b: f"{b[0]}x{b[1]}")
def test_bfloat16_head_size_64_close_to_fp32_oracle(blocks, program_index):
    """bf16 inputs at GPT-2's head size: bf16 operands, fp32 accumulation,
    forward and gradients against the fp32 oracle on the same rounded
    inputs, within the file's bf16 tolerance."""
    bq, bk = blocks
    q, k, v = _qkv(T=64, B=1, D=64, dtype=jnp.bfloat16)
    do = jnp.asarray(np.random.default_rng(5).normal(size=q.shape), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def flash_loss(q, k, v):
        return jnp.vdot(f32(flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)), do)

    def dense_loss(q, k, v):
        return jnp.vdot(_dense_attention(q, k, v), do)

    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(f32(out)), np.asarray(_dense_attention(f32(q), f32(k), f32(v))), atol=0.05
    )
    gf = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
    for name, a, b in zip("q k v".split(), gf, gd):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(b), atol=0.05, err_msg=f"d{name}")


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, loop and branch bodies included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _grad_jaxpr(dtype, **kw):
    q, k, v = _qkv(T=64, B=1, D=64, dtype=dtype)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw).astype(jnp.float32))

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr


def test_three_kernels_with_the_signature_the_trace_reader_tells_them_by():
    """``chipbench/trace_reduce.flash_kernel`` knows the kernels by operands
    and results (forward 3 -> 2, dq 6 -> 1, dk/dv 6 -> 2), three custom calls
    an attention: no scalar prefetch, no fused or split kernel may change it."""
    calls = _pallas_calls(_grad_jaxpr(jnp.bfloat16, block_q=32, block_k=32))
    assert [(c.params["name"], len(c.invars), len(c.outvars)) for c in calls] == [
        ("flash_fwd", 3, 2), ("flash_bwd_dq", 6, 1), ("flash_bwd_dkv", 6, 2),
    ]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_every_product_takes_the_inputs_dtype_and_accumulates_in_fp32(dtype, program_index):
    """bf16 inputs reach every product of the three kernels (2 in the
    forward's loop body, 3 in dq's, 4 in dk/dv's, each body traced once for
    the masked and once for the unmasked range) as bf16; fp32 inputs keep
    fp32 products."""
    for call in _pallas_calls(_grad_jaxpr(dtype, block_q=32, block_k=16)):
        names = [e.primitive.name for e in _eqns(call.params["jaxpr"])]
        # a query block has none or two unmasked key blocks, a key block none or one query block: a loop of one tile a turn
        looped = looped_tiles(64, 32, 16, True, key_side=call.params["name"] == "flash_bwd_dkv")
        assert looped == (0 if program_index == "static" else 2)
        assert ("while" in names) == (looped > 0), call.params["name"]
        dots = [e for e in _eqns(call.params["jaxpr"]) if e.primitive.name == "dot_general"]
        assert dots, call.params["name"]
        for dot in dots:
            assert {v.aval.dtype for v in dot.invars} == {jnp.dtype(dtype)}, call.params["name"]
            assert dot.outvars[0].aval.dtype == jnp.float32


def _brute_force_tiles(T, bq, bk, causal):
    allowed = np.tril(np.ones((T, T), bool)) if causal else np.ones((T, T), bool)
    return int(allowed.reshape(T // bq, bq, T // bk, bk).any(axis=(1, 3)).sum())


@pytest.mark.parametrize(
    "T,bq,bk",
    [(1024, 128, 128), (1024, 256, 256), (1024, 512, 128), (1024, 128, 512), (1024, 1024, 1024),
     (64, 16, 16), (64, 32, 16), (64, 16, 32), (64, 8, 64), (96, 24, 32), (96, 32, 24), (8, 8, 8)],
)
def test_visited_tiles_is_the_count_of_tiles_with_an_unmasked_element(T, bq, bk):
    assert visited_tiles(T, bq, bk, True) == _brute_force_tiles(T, bq, bk, True)
    assert visited_tiles(T, bq, bk, False) == (T // bq) * (T // bk)


def test_causal_visits_36_of_64_tiles_at_t1024_with_128_tiles():
    assert visited_tiles(1024, 128, 128, True) == 36
    assert visited_tiles(1024, 128, 128, False) == 64


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32)], ids=lambda b: f"{b[0]}x{b[1]}")
def test_tile_gauges_follow_the_trace(blocks, causal, program_index):
    """Trace time records the tiles the call's kernels visit: one kernel's
    after a forward pass, all three's after a backward pass; and of them the
    tiles reached from inside a loop with a traced trip count: none where the
    code is written out, the unmasked ones where a turn is one tile."""
    bq, bk = blocks
    q, k, v = _qkv(T=64, B=1, H=1)
    gauges = lambda: default_registry().snapshot()["gauges"]  # noqa: E731
    one = visited_tiles(64, bq, bk, causal)
    by_query, by_key = looped_tiles(64, bq, bk, causal), looped_tiles(64, bq, bk, causal, key_side=True)
    if not causal or program_index == "static":
        assert (by_query, by_key) == (0, 0)
    elif bq == bk:      # 4 positions, 0..3 unmasked tiles: each a turn, or in turns of two with the odd one under a condition
        assert (by_query, by_key) == ((6, 6) if program_index == "traced" else (4, 4))
    else:               # two unmasked tiles or none on the side of the smaller tiles, one or none on the other: a turn each
        assert (by_query, by_key) == (2, 2)

    jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk))(q, k, v)
    g = gauges()
    assert (g["flash.tiles_visited"], g["flash.tiles_total"]) == (one, (64 // bq) * (64 // bk))
    assert (g["flash.block_q"], g["flash.block_k"]) == (bq, bk)
    assert g["flash.tiles_looped"] == by_query

    jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g = gauges()
    assert (g["flash.tiles_visited"], g["flash.tiles_total"]) == (3 * one, 3 * (64 // bq) * (64 // bk))
    assert g["flash.tiles_looped"] == 2 * by_query + by_key


@functools.cache
def _walk(parts):
    """``bounds -> (visits, steps)``: ``_band``'s walk with ``parts`` as one
    compiled program, shared by every position and shape whose runs are the
    same (run eagerly, every call compiles its loops and conditions anew)."""

    def tile(j, carry, masked):
        visits, step = carry
        return visits.at[step].set(jnp.stack([jnp.asarray(j, jnp.int32), jnp.int32(masked)])), step + 1

    return jax.jit(lambda bounds: flash_module._band(
        bounds, tile, (jnp.full((64, 2), -1, jnp.int32), jnp.int32(0)), parts=parts
    ))


def _walked(span, i, parts):
    """``(block, masked)`` in the order ``_band`` visits them for position
    ``i``, its bounds handed over as arrays (as a traced ``program_id``
    gives them) so that the runs, conditions and loops decide."""
    visits, steps = _walk(tuple(parts))(tuple(jnp.int32(x) for x in span(i)))
    return [tuple(int(x) for x in row) for row in np.asarray(visits[: int(steps)])]


@pytest.mark.parametrize("limit", [None, 0, 2, 4, 8, 16, 64], ids=lambda x: f"limit-{x}-tiles")
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_loop_bounds_visit_exactly_the_counted_tiles(causal, limit, monkeypatch):
    """The kernels' own loop bounds, run as Python integers, cover each
    counted tile once and no other, from the query side and the key side.
    And so does the walk the one body with traced bounds makes of them,
    whatever ``_TRACED_BASE_ELEMENTS`` lets it write out (``limit`` tiles:
    one tile a turn, or runs of 2, 4 and 8 in a loop and the rest under the
    count's bits): each position's tiles once, ascending, masked where the
    diagonal crosses them."""
    from adapcc_tpu.ops.flash_attention import _key_blocks, _key_side, _query_blocks, _query_side, _runs

    for T, bq, bk in [(64, 16, 16), (64, 32, 16), (64, 16, 32), (96, 24, 32), (96, 32, 24), (128, 8, 8)]:
        n_q, n_k = T // bq, T // bk
        allowed = np.tril(np.ones((T, T), bool)).reshape(n_q, bq, n_k, bk)
        want = {(i, j) for i in range(n_q) for j in range(n_k) if allowed[i, :, j].any() or not causal}
        whole = {(i, j) for i in range(n_q) for j in range(n_k) if allowed[i, :, j].all()}
        if not causal:
            assert visited_tiles(T, bq, bk, False) == len(want)
            continue
        if limit is not None:
            monkeypatch.setattr(flash_module, "_TRACED_BASE_ELEMENTS", limit * bq * bk)
            for side, transpose in ((_query_side, False), (_key_side, True)):
                n, span = side(T, bq, bk, True, None)
                parts = _runs(n, span, bq, bk)
                code = sum(2 * run - 1 if run else most for most, run in parts)
                assert code <= limit or max(run for _, run in parts) <= 1, (T, bq, bk, parts)
                walked = {i: _walked(span, i, parts) for i in range(n)}
                seen = [(j, i) if transpose else (i, j) for i in walked for j, _ in walked[i]]
                assert len(seen) == len(want) and set(seen) == want, (T, bq, bk)
                for i, tiles in walked.items():
                    lo, a, b, end = span(i)
                    assert [j for j, _ in tiles] == list(range(lo, end))
                    assert [m for _, m in tiles] == [int(not a <= j < b) for j in range(lo, end)]
            continue
        by_q = {(i, j) for i in range(n_q) for j in range(_key_blocks(i, bq, bk)[1])}
        by_k = {(i, j) for j in range(n_k) for i in range(_query_blocks(j, bq, bk)[0], n_q)}
        assert by_q == want and by_k == want, (T, bq, bk)
        # the unmasked ranges hold only tiles the diagonal does not cross
        assert {(i, j) for i in range(n_q) for j in range(_key_blocks(i, bq, bk)[0])} == whole
        assert {(i, j) for j in range(n_k) for i in range(_query_blocks(j, bq, bk)[1], n_q)} == whole


def test_the_long_cells_shapes_run_written_out_runs():
    """T = 8,192 with 512-tiles (cells 3-5's full-causal layers): 15 unmasked
    tiles at most are runs of 4 in a loop of up to three turns, then 2 and 1
    under the count's bits, beside the diagonal's one: 8 tiles of code, and 96
    of a kernel's 136 tiles are reached from the loop (the one-tile loop
    reached 120, a tile a turn).  A ring shard of 4,096 has 7 (a run of 4, 2,
    1), 16,384 has 31 (up to seven turns); at GPT-2's 1,024 every position has
    its own body and a band's positions share theirs: no loop."""
    from adapcc_tpu.ops.flash_attention import _key_side, _query_side, _runs, _written_out

    for side in (_query_side, _key_side):
        for T, most in ((4096, 7), (8192, 15), (16384, 31)):
            n, span = side(T, 512, 512, True, None)
            assert _written_out(n, span, 512, 512, True, None) is None
            # nothing, the unmasked tiles four to a turn, the diagonal's one
            assert sorted(_runs(n, span, 512, 512)) == [(0, 0), (1, 0), (most, 4)]
        assert len(_written_out(*side(1024, 512, 512, True, None), 512, 512, True, None)) == 2
    for key_side in (False, True):
        assert looped_tiles(8192, 512, 512, True, key_side=key_side) == sum(n // 4 * 4 for n in range(16)) == 96
        assert looped_tiles(4096, 512, 512, True, key_side=key_side) == 16
        assert looped_tiles(16384, 512, 512, True, key_side=key_side) == 448
        assert looped_tiles(1024, 512, 512, True, key_side=key_side) == 0
        assert looped_tiles(8192, 512, 512, True, 2048, key_side=key_side) == 0        # the band's shared bodies


@pytest.mark.parametrize(
    "limit,dtype,head",
    [(limit, dtype, (16, 16)) for limit in (16, 8, 4) for dtype in (jnp.float32, jnp.bfloat16)]
    + [(16, jnp.float32, (16, 8)), (8, jnp.bfloat16, (16, 8))],
    ids=lambda x: {16: "runs-8-4-2-1", 8: "loop-of-4-then-2-1", 4: "loop-of-2-then-1"}.get(x)
    or (f"d{x[0]}-dv{x[1]}" if isinstance(x, tuple) else jnp.dtype(x).name),
)
def test_runs_equal_the_one_tile_loop_bit_for_bit_at_every_count_of_tiles(limit, dtype, head, monkeypatch):
    """T = 128 with 8-tiles: 16 positions, query block ``i`` with ``i``
    unmasked key blocks and key block ``j`` with ``15 - j`` unmasked query
    blocks, so every count 0..15 in each of the three kernels.  Written out as
    runs (8, then 4, 2, 1 under the count's bits; or, where only 8 or 4 tiles
    of code fit, a loop of runs of 4 or 2 with the rest by the bits), the same
    tiles in the same order give the output and the three gradients of the
    loop of one tile a turn, to the bit."""
    d, dv = head
    rng = np.random.default_rng(36)
    q, k = (jnp.asarray(rng.normal(size=(1, 128, 2, d)) * 0.5, dtype) for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=(1, 128, 2, dv)) * 0.5, dtype) for _ in range(2))

    def outputs(tiles_of_code):
        _limits(monkeypatch, 0, tiles_of_code * 64)
        out, pull = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=8, block_k=8), q, k, v)
        looped = looped_tiles(128, 8, 8, True), looped_tiles(128, 8, 8, True, key_side=True)
        return [np.asarray(x.astype(jnp.float32)) for x in (out, *pull(do))], looped

    try:
        want, looped = outputs(2)
        assert looped == (120, 120)         # every unmasked tile a turn of its own
        got, looped = outputs(limit)
        assert looped == {16: (64, 64), 8: (96, 96), 4: (112, 112)}[limit]
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            assert a.shape == b.shape and np.array_equal(a, b), name
    finally:
        _forget()


def test_tile_resolves_by_shape_through_one_table():
    """``GPT2Config.flash_block`` (None by default) and a bare
    ``flash_attention`` call both read ``TILE_TABLE``."""
    from adapcc_tpu.models.gpt2 import GPT2Config

    assert GPT2Config().flash_block is None
    cells = default_blocks(1024, 64, jnp.bfloat16)
    assert cells == next(tile for _, tile in TILE_TABLE)           # the first row is the cells' shape
    assert default_blocks(384, 64, jnp.bfloat16) == tuple(
        resolve_block(384, b) for b in cells                       # cut to a divisor of T
    )
    assert default_blocks(1 << 20, 256, "float64") == TILE_TABLE[-1][1]     # the last row holds every shape
    q, k, v = _qkv(T=64, B=1, H=1, dtype=jnp.bfloat16)
    jax.make_jaxpr(flash_attention)(q, k, v)
    g = default_registry().snapshot()["gauges"]
    assert (g["flash.block_q"], g["flash.block_k"]) == default_blocks(64, 16, jnp.bfloat16)


# --- PR 47: lse and delta cross the kernels' boundary as rows [B.H, 1, T] --------


#: what calls the kernels: ``(entry, its keywords, H_kv, whether lse gets a cotangent)``
_BOUNDARIES = {
    "causal": (flash_attention, {}, 2, False),
    "full": (flash_attention, {"causal": False}, 2, False),
    "band": (flash_attention, {"window": 40}, 2, False),
    "grouped-kv": (flash_attention, {}, 1, False),
    "band-grouped-kv": (flash_attention, {"window": 40}, 1, False),
    "band-of-one": (flash_attention, {"window": 1}, 2, False),
    "with-lse": (flash_attention_with_lse, {}, 2, False),
    "with-lse-and-dlse": (flash_attention_with_lse, {}, 2, True),
    "with-lse-and-dlse-full": (flash_attention_with_lse, {"causal": False}, 2, True),
}


@pytest.mark.parametrize("case", sorted(_BOUNDARIES))
def test_the_statistics_cross_every_kernel_boundary_as_rows_and_nothing_pads_them(case, program_index):
    """Through ``jax.grad`` of every caller's form: the forward's second
    result and the last two operands of both backward kernels are
    ``float32[B.H, 1, T]``; no float32 array anywhere outside or inside the
    kernels has a minor dimension of 8 (the padded form was ``[B.H, T, 8]``),
    and no ``broadcast_in_dim`` makes a ``[B.H, T]`` statistic larger than it
    was (an axis of one is all it may add)."""
    entry, kw, h_kv, dlse = _BOUNDARIES[case]
    B, T, H, D = 2, 64, 2, 16
    rng = np.random.default_rng(47)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, h, D)), jnp.bfloat16) for h in (H, h_kv, h_kv))

    def loss(q, k, v):
        out = entry(q, k, v, block_q=32, block_k=32, **kw)
        if entry is flash_attention:
            return jnp.sum(out.astype(jnp.float32))
        out, lse = out
        return jnp.sum(out.astype(jnp.float32)) + (jnp.sum(lse * lse) if dlse else 0.0)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr
    fwd, dq, dkv = _pallas_calls(jaxpr)
    row = (B * H, 1, T)
    statistics = [fwd.outvars[1], *dq.invars[4:], *dkv.invars[4:]]
    assert [(s.aval.shape, s.aval.dtype) for s in statistics] == [(row, jnp.float32)] * 5
    for eqn in _eqns(jaxpr):
        for aval in (var.aval for var in [*eqn.invars, *eqn.outvars]):
            assert not (aval.dtype == jnp.float32 and len(aval.shape) >= 2 and aval.shape[-1] == 8), (eqn.primitive.name, aval)
        if eqn.primitive.name == "broadcast_in_dim" and eqn.invars[0].aval.shape == (B * H, T):
            assert eqn.outvars[0].aval.shape == row, eqn


@pytest.mark.parametrize("rows", [8, 128, 512])
def test_the_turn_between_column_and_row_keeps_every_bit(rows):
    """The two helpers the kernels call, in the interpreter: a column that
    holds ``_NEG_INF`` rows, a subnormal, a negative zero and neighbours in
    their last bit comes back as the row of the same bits, and the row as the
    column (a product with a selector under ``highest`` precision would fail
    the neighbours or the subnormal)."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(rows)
    bits = rng.integers(0, 1 << 32, size=rows, dtype=np.uint64).astype(np.uint32)
    bits[(bits & 0x7F800000) == 0x7F800000] = 0x3F800000      # no NaN or infinity: they have no order to keep
    values = bits.view(np.float32).copy()
    values[0] = flash_module._NEG_INF
    values[1] = np.float32(1e-42)                               # subnormal
    values[2:5] = np.float32(9.123456), np.nextafter(np.float32(9.123456), np.float32(10)), -0.0
    values[5] = np.nextafter(np.float32(flash_module._NEG_INF), np.float32(0))
    column = jnp.asarray(values.reshape(rows, 1))

    def through(turn, x, shape):
        def kernel(x_ref, o_ref):
            o_ref[...] = turn(x_ref[...])
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.float32), interpret=True)(x)

    row = through(flash_module._as_row, column, (1, rows))
    back = through(flash_module._as_column, row, (rows, 1))
    as_bits = lambda x: np.asarray(x).view(np.uint32).ravel()  # noqa: E731
    assert np.array_equal(as_bits(row), values.view(np.uint32))
    assert np.array_equal(as_bits(back), values.view(np.uint32))


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", [(128, 128), (128, 64), (64, 128), (256, 256)], ids=lambda b: f"{b[0]}x{b[1]}")
def test_lse_matches_the_dense_oracles_logsumexp_where_a_block_is_whole_lane_tiles(blocks, causal):
    """``flash_attention_with_lse``'s ``lse`` against the float32 oracle's
    logsumexp at blocks of one and two lane tiles and of the whole row, within
    the tolerance the file holds it to at the small blocks."""
    q, k, v = _qkv(T=256, B=1, H=2)
    out, lse = flash_attention_with_lse(q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1])
    ref_out, ref_lse = _dense_attention_lse(q, k, v, causal)
    assert lse.shape == ref_lse.shape == (1, 2, 256)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)


# --- PR 48: the kernels read the model's [B, T, H·D] where the shapes allow -------


#: ``(B, T, H, H_kv, D, D_v, dtype, causal, window, heads a program holds or 0 where the call falls back)``
_WHERE = {
    "pair-of-64-causal": (2, 64, 4, 4, 64, 64, jnp.float32, True, None, 2),
    "pair-of-64-causal-bf16": (1, 64, 2, 2, 64, 64, jnp.bfloat16, True, None, 2),
    "pair-of-64-band": (1, 128, 2, 2, 64, 64, jnp.float32, True, 40, 2),       # three blocks of four resident
    "pair-of-64-odd-rows-bf16": (1, 5, 2, 2, 64, 64, jnp.bfloat16, True, None, 2),     # no whole 32-bit words of rows: masked as they are
    "pair-of-64-shard": (1, 64, 2, 2, 64, 64, jnp.float32, False, None, 2),    # a ring's off-diagonal block
    "four-of-32": (1, 64, 4, 4, 32, 32, jnp.float32, True, None, 4),
    "one-of-128": (1, 64, 3, 3, 128, 128, jnp.float32, True, None, 1),
    "odd-heads-of-64": (1, 64, 3, 3, 64, 64, jnp.float32, True, None, 0),
    "grouped-kv": (1, 64, 4, 2, 64, 64, jnp.float32, True, None, 0),
    "v-of-its-own-size": (1, 64, 2, 2, 64, 128, jnp.float32, True, None, 0),
    "head-of-192": (1, 64, 2, 2, 192, 192, jnp.float32, True, None, 0),
}


@pytest.mark.parametrize("case", sorted(_WHERE))
def test_the_in_place_entry_agrees_with_the_heads_first_entry_and_the_shapes_choose(case):
    """``_bthd_call`` hands the kernels the model's arrays where heads are
    equal, ``D == D_v``, a lane block is whole heads and they divide ``H``
    (gauge ``flash.in_place`` 1), and ``[B.H, T, D]`` copies everywhere else
    (0).  Either way o and lse are the ``[B.H, T, D]`` entry's to the bit
    (values addressed, never recomputed: a masked head adds exact zeros) and
    dq, dk, dv, under a cotangent for o and a random one for lse, agree with
    it to float32 rounding (in place delta is summed by a product)."""
    B, T, H, h_kv, D, d_v, dtype, causal, window, pair = _WHERE[case]
    rng = np.random.default_rng(48)
    q, k, v, do = (jnp.asarray(rng.normal(size=(B, T, h, d)), dtype) for h, d in ((H, D), (h_kv, D), (h_kv, d_v), (H, d_v)))
    dlse = jnp.asarray(rng.normal(size=(B * H, T)), jnp.float32)
    static = (float(1 / np.sqrt(D)), causal, 32, 32, True, window)
    heads_first = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, T, x.shape[-1])  # noqa: E731
    back = lambda x: x.reshape(B, -1, T, x.shape[-1]).transpose(0, 2, 1, 3)  # noqa: E731

    def chosen(q, k, v):
        return flash_module._bthd_call(flash_module._flash_bhtd_lse, q, k, v, causal, None, 32, 32, True, window)

    def copies(q, k, v):
        out, lse = flash_module._flash_bhtd_lse(*map(heads_first, (q, k, v)), *static)
        return back(out), lse

    def with_grads(entry):
        (out, lse), pull = jax.vjp(entry, q, k, v)
        return (out, lse, *pull((do, dlse)))

    _forget()
    jaxpr = jax.make_jaxpr(lambda q, k, v: with_grads(chosen))(q, k, v).jaxpr
    g = default_registry().snapshot()["gauges"]
    assert g["flash.in_place"] == (1 if pair else 0)
    calls = _pallas_calls(jaxpr)
    assert [(c.params["name"], len(c.invars), len(c.outvars)) for c in calls] == [
        ("flash_fwd", 3, 2), ("flash_bwd_dq", 6, 1), ("flash_bwd_dkv", 6, 2),
    ]
    kernel_q = (B, T, H * D) if pair else (B * H, T, D)
    assert all(c.invars[0].aval.shape == kernel_q for c in calls)
    assert all(c.params["grid_mapping"].grid[0] == B * H // (pair or 1) for c in calls)
    # the residuals are the model's arrays: nothing transposed anywhere when the call is in place
    assert any(e.primitive.name == "transpose" and e.invars[0].aval.ndim == 4 for e in _eqns(jaxpr)) == (not pair)

    got, want = with_grads(chosen), with_grads(copies)
    for name, a, b in zip(("o", "lse"), got, want):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b)), name
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    for name, a, b in zip(("dq", "dk", "dv"), got[2:], want[2:]):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=tol, err_msg=name)
    _forget()


def test_a_written_out_tile_counts_once_for_each_head_a_program_holds():
    """The code of a tile is that of every head of the pair: T = 1,024 keeps
    its straight-line bodies at a pair of two, a T = 2,048 triangle of
    512-tiles (10 tiles: 2.6 M elements a head) passes to the one traced body,
    whose runs are half as long as a single head's."""
    from adapcc_tpu.ops.flash_attention import _query_side, _runs, _written_out

    for T, alone, paired in ((1024, True, True), (2048, True, False), (4096, False, False)):
        n, span = _query_side(T, 512, 512, True, None)
        assert (_written_out(n, span, 512, 512, True, None) is not None) == alone
        assert (_written_out(n, span, 512, 512, True, None, pair=2) is not None) == paired
    n, span = _query_side(8192, 512, 512, True, None)
    assert [run for _, run in _runs(n, span, 512, 512)] == [0, 4, 0]
    assert [run for _, run in _runs(n, span, 512, 512, pair=2)] == [0, 2, 0]
    assert looped_tiles(2048, 512, 512, True) == 0 and looped_tiles(2048, 512, 512, True, pair=2) > 0
