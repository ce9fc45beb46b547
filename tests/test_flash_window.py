"""Windowed, grouped-head flash attention against a dense oracle.

``window=W`` lets query ``t`` see key ``s`` only where ``0 <= t - s < W``;
``k`` and ``v`` may carry fewer heads than ``q``.  Forward and the three
gradients, in the Pallas interpreter, for a window under the tile, one that
is no multiple of it and one past the sequence, by both ways a kernel learns
its position (written-out bodies, the interior of the band sharing one; the
traced ``program_id``).  GPT-2's shapes read the counts and gauges they
read before the window existed.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adapcc_tpu.ops import flash_attention
from adapcc_tpu.ops.flash_attention import (
    _bodies,
    _key_span,
    _query_span,
    visited_tiles,
)
from adapcc_tpu.utils.observability import default_registry

flash_module = sys.modules["adapcc_tpu.ops.flash_attention"]


def dense_oracle(q, k, v, window=None):
    """fp32, the mask written out; query head ``h`` reads KV head ``h // G``."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(D)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    att = jnp.where(seen[None, None], att, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v, precision="highest")


def _qkv(T, H, Hkv, D=16, B=2, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda h: jnp.asarray(rng.normal(size=(B, T, h, D)) * 0.5, jnp.float32)  # noqa: E731
    return mk(H), mk(Hkv), mk(Hkv)


@pytest.fixture(params=["static", "traced", "runs"])
def program_index(request, monkeypatch):
    """Written-out bodies; one body with traced bounds and one tile a turn;
    that body with four 16 x 16 tiles of code, so that a band's three runs of
    tiles, each of a count that differs with the position, go by its bits."""
    def forget():
        flash_module._fwd_call.clear_cache()
        flash_module._bwd_call.clear_cache()

    if request.param != "static":
        monkeypatch.setattr(flash_module, "_STRAIGHT_LINE_ELEMENTS", 0)
        monkeypatch.setattr(flash_module, "_TRACED_BASE_ELEMENTS", 0 if request.param == "traced" else 1024)
    forget()
    yield request.param
    forget()


@pytest.mark.parametrize("heads", [(4, 2), (2, 2), (4, 1)], ids=lambda h: f"{h[0]}q{h[1]}kv")
@pytest.mark.parametrize(
    "window,blocks",
    [
        (5, (16, 16)), (16, (16, 16)), (24, (16, 16)), (40, (16, 32)), (33, (32, 16)), (64, (16, 16)), (200, (16, 16)),
        (1, (16, 16)),      # all band: every position has the bounds (0, 1, 1, 1) from itself, one body for them all
    ],
    ids=lambda x: str(x).replace(" ", ""),
)
def test_windowed_grouped_attention_matches_the_dense_oracle(window, blocks, heads, program_index):
    bq, bk = blocks
    q, k, v = _qkv(64, *heads)
    do = jnp.asarray(np.random.default_rng(3).normal(size=q.shape), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window, block_q=bq, block_k=bk)

    out, pull = jax.vjp(flash, q, k, v)
    want, pull_want = jax.vjp(lambda q, k, v: dense_oracle(q, k, v, window), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(pull(do), pull_want(do), "qkv"):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("window,blocks", [(1, (16, 16)), (1, (16, 32)), (5, (16, 16)), (24, (16, 16)), (200, (16, 16))],
                         ids=lambda x: str(x).replace(" ", ""))
def test_the_forwards_lse_under_a_window_is_the_dense_logsumexp_of_the_band(window, blocks, program_index):
    """Every query block writes its own lanes of the statistic's row, whoever
    wrote its body (a window of one key on square tiles: one body at the
    traced position for all four blocks)."""
    q, k, v = _qkv(64, 2, 2)
    B, T, H, D = q.shape
    heads_first = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)  # noqa: E731
    _, lse = flash_module._fwd_call(*map(heads_first, (q, k, v)), 1 / np.sqrt(D), True, *blocks, True, window)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(D)
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    att = jnp.where(((ahead >= 0) & (ahead < window))[None, None], att, -jnp.inf)
    want = jax.nn.logsumexp(att, axis=-1).reshape(B * H, T)
    assert lse.shape == want.shape
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), atol=2e-5)


def test_a_group_of_seven_under_a_window_of_half_the_row_matches_the_dense_oracle(program_index):
    """The first group that is no power of two (SmallThinker's 28 query heads
    on 4 K/V heads, here 14 on 2) with the window half of T, as its cell runs
    it (4,096 of 8,192): the K/V block index ``h // 7`` and the seven fp32
    ``dk`` / ``dv`` partials the wrapper sums, forward and the three gradients."""
    q, k, v = _qkv(64, 14, 2, seed=7)
    do = jnp.asarray(np.random.default_rng(5).normal(size=q.shape), jnp.float32)
    out, pull = jax.vjp(lambda q, k, v: flash_attention(q, k, v, causal=True, window=32, block_q=16, block_k=16), q, k, v)
    want, pull_want = jax.vjp(lambda q, k, v: dense_oracle(q, k, v, 32), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(pull(do), pull_want(do), "qkv"):
        assert got.shape == ref.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def test_bf16_grouped_windowed_gradients_keep_their_dtype_and_stay_close():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(64, 4, 2))
    loss = lambda q, k, v: jnp.sum(  # noqa: E731
        flash_attention(q, k, v, window=24, block_q=16, block_k=16).astype(jnp.float32) ** 2
    )
    want = jax.grad(
        lambda q, k, v: jnp.sum(dense_oracle(q, k, v, 24) ** 2), argnums=(0, 1, 2)
    )(*(x.astype(jnp.float32) for x in (q, k, v)))
    for got, ref in zip(jax.grad(loss, argnums=(0, 1, 2))(q, k, v), want):
        assert got.dtype == jnp.bfloat16
        gap = np.linalg.norm(np.asarray(got, np.float32) - np.asarray(ref)) / np.linalg.norm(np.asarray(ref))
        assert gap < 0.03, gap


@pytest.mark.parametrize("bad", [dict(window=0), dict(window=8, causal=False)])
def test_a_window_needs_the_causal_mask_and_a_position(bad):
    q, k, v = _qkv(32, 2, 2)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, **bad)


def test_kv_heads_must_divide_the_query_heads():
    q, k, v = _qkv(32, 4, 3)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k, v)


def _brute(T, bq, bk, window):
    tiles = set()
    for t in range(T):
        for s in range(max(0, t - window + 1) if window else 0, t + 1):
            tiles.add((t // bq, s // bk))
    return tiles


@pytest.mark.parametrize("window", [None, 1, 5, 16, 24, 33, 64, 100])
@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32), (8, 64)], ids=lambda b: f"{b[0]}x{b[1]}")
def test_spans_cover_exactly_the_tiles_with_a_seen_element(blocks, window):
    """By query block and by key block, the visited tiles are those with an
    element the band leaves; the unmasked run holds only tiles it leaves whole."""
    T, (bq, bk) = 64, blocks
    n_q, n_k = T // bq, T // bk
    want = _brute(T, bq, bk, window)
    by_q = {(i, j) for i in range(n_q) for j in range(*_key_span(i, bq, bk, window)[0::3])}
    by_k = {(i, j) for j in range(n_k) for i in range(*_query_span(j, bq, bk, window, n_q)[0::3])}
    assert by_q == want and by_k == want
    assert visited_tiles(T, bq, bk, True, window) == len(want)
    whole = {
        (i, j) for i, j in want
        if j * bk + bk - 1 <= i * bq and (window is None or i * bq + bq - 1 - j * bk < window)
    }
    for i in range(n_q):
        lo, a, b, end = _key_span(i, bq, bk, window)
        assert lo <= a <= b <= end
        assert {(i, j) for j in range(a, b)} <= whole
    inner = {(i, j) for j in range(n_k) for i in range(*_query_span(j, bq, bk, window, n_q)[1:3])}
    assert inner <= whole


def test_the_band_interior_shares_one_written_out_body():
    """T=8,192, window 2,048, 512-tiles: four leading bodies and one shared by
    the twelve interior positions, 15 tiles of code for 70 visited."""
    runs = _bodies(16, lambda i: _key_span(i, 512, 512, 2048), share=True)
    assert [(first, last) for first, last, _ in runs] == [(0, 0), (1, 1), (2, 2), (3, 3), (4, 15)]
    assert sum(b[3] - b[0] for _, _, b in runs) == 15
    assert visited_tiles(8192, 512, 512, True, 2048) == 70
    assert visited_tiles(8192, 512, 512, True) == 136
    by_key = _bodies(16, lambda j: _query_span(j, 512, 512, 2048, 16), share=True)
    assert (by_key[0][0], by_key[0][1]) == (0, 11) and len(by_key) == 5
    # without a window every position is its own body, as before
    assert len(_bodies(8, lambda i: _key_span(i, 128, 128, None), share=False)) == 8


def test_gpt2_shapes_read_the_counts_and_gauges_they_read_before():
    assert visited_tiles(1024, 128, 128, True) == 36
    assert visited_tiles(1024, 512, 512, True) == 3
    assert visited_tiles(1024, 128, 128, False) == 64
    q, k, v = _qkv(64, 2, 2)
    jax.make_jaxpr(
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16)))
    )(q, k, v)
    g = default_registry().snapshot()["gauges"]
    assert g["flash.tiles_visited"] == 3 * 10 and g["flash.tiles_total"] == 3 * 16
    assert (g["flash.block_q"], g["flash.block_k"]) == (16, 16)
    q, k, v = _qkv(64, 4, 2)
    jax.make_jaxpr(lambda q, k, v: flash_attention(q, k, v, window=24, block_q=16, block_k=16))(q, k, v)
    g = default_registry().snapshot()["gauges"]
    assert g["flash.tiles_visited"] == visited_tiles(64, 16, 16, True, 24) == 9
