"""Flash-attention tile autotuning.

The static tile is not kept here: :data:`adapcc_tpu.ops.flash_attention.TILE_TABLE`
holds it by ``(T, D, dtype)``, measured on a TPU v5e in PR 25 (PERF.md §6 has
the tiles tried and their times: 512 beat 256 beat 128 at T=1,024 and at
T=4,096), and the kernels, ``GPT2Config.flash_block = None`` and
:data:`DEFAULT_BLOCK` all read that one table.  Round 4's "256 beat 128 by
~11% at T=512" was a manual A/B on kernels that visited every tile; on today's
kernels the tiles are level at T=512 (same readings).  This module is the
sweep that refreshes the table: for a given (seq, d_head, dtype) it times a
short jitted forward+backward of the real kernel at each candidate tile and
returns the fastest.

Each candidate runs ``warmup`` untimed executions (the first compiles)
before the timed ones, and a host read of the scalar result closes every
timed window.  A candidate the chip's compiler refuses (a 512 tile over
VMEM) loses the sweep — and says so on stderr, naming the block and the
error; if every candidate is refused the sweep raises.

Off-TPU the sweep is skipped entirely — the Pallas interpreter's timings
say nothing about Mosaic and would take minutes — and the static default
resolution is returned.
"""

from __future__ import annotations

import sys
import time
from typing import Dict, Optional, Sequence, Tuple

from adapcc_tpu.ops.flash_attention import default_blocks, resolve_block  # noqa: F401 — re-exported

#: the static default at the flagship shape (T=1,024, head size 64, bf16),
#: read from the one table the kernels themselves resolve through
DEFAULT_BLOCK = default_blocks(1024, 64, "bfloat16")[0]

#: candidate tile edges swept by the autotuner
CANDIDATES = (128, 256, 512)

_cache: Dict[Tuple, Tuple[int, Dict[int, float]]] = {}


def autotune_flash_block(
    seq: int,
    d_head: int = 64,
    dtype=None,
    batch: int = 2,
    heads: int = 8,
    candidates: Sequence[int] = CANDIDATES,
    warmup: int = 1,
    iters: int = 3,
    causal: bool = True,
) -> int:
    """Fastest seq-compatible flash tile for this backend, measured.

    Returns the winning block edge; the per-candidate timings are kept in
    :func:`last_timings` for artifact/bench reporting.  Results are cached
    per (platform, seq, d_head, dtype, causal, batch, heads) for the process
    lifetime — the sweep runs once per full problem shape, not once per call.
    """
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    platform = jax.devices()[0].platform
    # batch/heads are part of the key: timings depend on the full problem
    # shape, and a second call at a different batch/head count must re-sweep
    # rather than silently reuse the first shape's winner (ADVICE r5)
    key = (platform, seq, d_head, jnp.dtype(dtype).name, causal, batch, heads)
    if key in _cache:
        return _cache[key][0]

    resolved = []
    for c in candidates:
        r = resolve_block(seq, c)
        if r not in resolved:
            resolved.append(r)
    if platform != "tpu" or len(resolved) == 1:
        # interpreter timings are meaningless for Mosaic tile choice
        best = default_blocks(seq, d_head, dtype)[0]
        _cache[key] = (best, {})
        return best

    from adapcc_tpu.ops import flash_attention

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, seq, heads, d_head), dtype)
    timings: Dict[int, float] = {}
    for block in resolved:
        def loss(q, k, v, block=block):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=causal, block_q=block, block_k=block
                ).astype(jnp.float32)
            )

        try:
            fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            for _ in range(max(warmup, 1)):  # the first execution compiles
                jax.block_until_ready(fn(x, x, x))
            t0 = time.perf_counter()
            for _ in range(iters):
                val, _ = fn(x, x, x)
                jax.device_get(val)  # host read closes the window
            timings[block] = (time.perf_counter() - t0) / iters
        except Exception as e:  # noqa: BLE001 — e.g. VMEM overflow at 512
            print(
                f"adapcc: flash autotune: block {block} refused at "
                f"B{batch}/T{seq}/H{heads}/D{d_head}: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )
            timings[block] = float("inf")
    finite = {b: t for b, t in timings.items() if t != float("inf")}
    if not finite:
        raise RuntimeError(
            f"flash autotune: every candidate tile {resolved} was refused "
            f"at B{batch}/T{seq}/H{heads}/D{d_head} (errors above)"
        )
    best = min(finite, key=finite.get)
    _cache[key] = (best, timings)
    return best


def last_timings(
    seq: int,
    d_head: int = 64,
    dtype=None,
    causal: bool = True,
    batch: int = 2,
    heads: int = 8,
) -> Optional[Dict[int, float]]:
    """Per-candidate seconds from the cached sweep for this shape (None if
    the sweep has not run; empty dict if it was skipped off-TPU).  The
    ``batch``/``heads`` defaults mirror :func:`autotune_flash_block` so the
    bare lookup matches the bare sweep."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    key = (
        jax.devices()[0].platform, seq, d_head, jnp.dtype(dtype).name, causal,
        batch, heads,
    )
    hit = _cache.get(key)
    return hit[1] if hit else None
