"""Block-wise wire codecs: shrink the bytes a collective ships.

AdapCC adapts the *shape* of the communication to the fabric; this module
adapts the *density*.  EQuARX (PAPERS.md) shows block-wise int8 with dual
quantization recovers near-full accuracy at 2-4x wire savings inside XLA
collectives; GC3-style strategy separation says the codec belongs in the
strategy/IR layer, not hard-coded in kernels.  Accordingly everything here
is a pure jittable function plus a registry the strategy plane names codecs
by (``Strategy.wire_dtype``), so the same codec definition serves the DDP
gradient hook, the engine's quantized ring, the simulator's pricing term,
and the XML artifact.

The int8 wire format
--------------------

A flat fp32 payload of ``n`` elements is padded to whole blocks of
``block_size`` elements and quantized per block:

    scale_b = max(|x| over block b) / 127        (fp32, one per block)
    q_i     = round(x_i / scale_b)               (int8, clipped to [-127, 127])

Wire bytes per element: ``1 + 4 / block_size`` (the int8 payload plus the
amortized fp32 scale) vs 4 for fp32 — a ~3.9x reduction at the default
block of 256.  An all-zero block keeps ``scale = 1`` so dequantization is
total.

Two rounding modes:

- **deterministic** (default): ``jnp.round`` (half-to-even).  Bit-exact
  across calls and ranks — the mode the data plane runs, so a replayed
  collective is reproducible.
- **stochastic**: ``floor(y + u)``, ``u ~ U[0, 1)`` from a caller-provided
  PRNG key.  Unbiased (``E[q·scale] = x``), the property gradient
  averaging over many steps prefers when no error feedback is running.

Error feedback
--------------

Quantization error is not noise to discard but signal to defer:
``compensated = grad + residual``, the wire carries
``decode(encode(compensated))``, and ``residual = compensated - wire`` is
folded into the *next* step.  The invariant (tested):
``sum(wire values over steps) + residual == sum(true gradients)`` — no
gradient mass is ever lost, which is what closes the accuracy gap of
deterministic int8 on real training loops.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

#: default quantization block (elements per fp32 scale).  Mirrored by the
#: simulator's pricing term (sim/cost_model.DEFAULT_QUANT_BLOCK — drift is
#: pinned by a test).
DEFAULT_BLOCK_SIZE = 256

#: env override for the wire codec (sweeps / operator pin); wins over both
#: the caller's value and the strategy's synthesized wire_dtype — the same
#: precedence contract as ADAPCC_RING_CHUNK_BYTES
WIRE_DTYPE_ENV = "ADAPCC_WIRE_DTYPE"


# --------------------------------------------------------------------------- #
# block-wise int8 quantize / dequantize
# --------------------------------------------------------------------------- #

def _as_blocks(flat: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """[n] -> [nblocks, block_size], zero-padded to whole blocks."""
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    n = flat.shape[0]
    nblocks = -(-n // block_size) if n else 1
    pad = nblocks * block_size - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nblocks, block_size)


def quantize_int8(
    flat: jnp.ndarray,
    block_size: int = DEFAULT_BLOCK_SIZE,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Block-wise int8 quantization of a flat float payload.

    Returns ``(q [nblocks, block_size] int8, scales [nblocks] fp32)``.
    Deterministic rounding is bit-exact across calls; stochastic rounding
    needs ``key`` and is unbiased in expectation.
    """
    blocks = _as_blocks(flat.astype(jnp.float32), block_size)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    y = blocks / scales[:, None]
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        u = jax.random.uniform(key, y.shape, dtype=jnp.float32)
        q = jnp.floor(y + u)
    else:
        q = jnp.round(y)
    q = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)
    return q, scales


def dequantize_int8(
    q: jnp.ndarray, scales: jnp.ndarray, n: Optional[int] = None
) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8`; ``n`` trims the block padding."""
    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)
    return flat if n is None else flat[:n]


def int8_roundtrip(
    flat: jnp.ndarray,
    block_size: int = DEFAULT_BLOCK_SIZE,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """The wire *value* of a payload: decode(encode(x)).  Jittable."""
    q, scales = quantize_int8(flat, block_size, stochastic, key)
    return dequantize_int8(q, scales, flat.shape[0])


def int8_error_bound(
    flat, block_size: int = DEFAULT_BLOCK_SIZE, stochastic: bool = False
):
    """Elementwise |x - roundtrip(x)| bound: half a quantization step per
    block under deterministic rounding (a full step stochastic).  The bound
    scales with the block max — the property the block-wise format exists
    for (one outlier only coarsens its own block)."""
    import numpy as np

    blocks = np.asarray(_as_blocks(jnp.asarray(flat, jnp.float32), block_size))
    absmax = np.max(np.abs(blocks), axis=1)
    step = np.where(absmax > 0, absmax / 127.0, 1.0)
    per_block = step * (1.0 if stochastic else 0.5)
    n = np.asarray(flat).reshape(-1).shape[0]
    return np.repeat(per_block, block_size)[:n]


# --------------------------------------------------------------------------- #
# codec registry: the one place codecs are named
# --------------------------------------------------------------------------- #

@dataclass(frozen=True)
class WireCodec:
    """One wire codec: value semantics (``apply``), transport arrays
    (``encode``/``decode``), and the wire density the simulator prices.

    ``apply(x, block_size)`` is the jittable quantize->dequantize round
    trip in the input's shape and dtype — the value every rank's collective
    contribution takes when this codec is on the wire.  ``encode`` returns
    the tuple of arrays that actually crosses the fabric (each one
    ppermute-able); ``decode(wire, n)`` reverses it to a flat fp32 payload.
    """

    name: str
    apply: Callable[..., jnp.ndarray]
    encode: Callable[..., Tuple[jnp.ndarray, ...]]
    decode: Callable[..., jnp.ndarray]
    #: (block_size, elem_bytes) -> wire bytes per payload element
    wire_bytes_per_element: Callable[[int, float], float]


def _identity_apply(x, block_size: int = DEFAULT_BLOCK_SIZE):
    return x


def _bf16_apply(x, block_size: int = DEFAULT_BLOCK_SIZE):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _int8_apply(x, block_size: int = DEFAULT_BLOCK_SIZE):
    flat = x.reshape(-1).astype(jnp.float32)
    return int8_roundtrip(flat, block_size).reshape(x.shape).astype(x.dtype)


_REGISTRY: Dict[str, WireCodec] = {}


def register_codec(codec: WireCodec) -> WireCodec:
    """Add a codec to the registry (idempotent for an identical name is NOT
    allowed — a silent re-register would let two meanings of one wire_dtype
    coexist across artifacts)."""
    if codec.name in _REGISTRY:
        raise ValueError(f"codec {codec.name!r} already registered")
    _REGISTRY[codec.name] = codec
    return codec


def codec_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def get_codec(name: str) -> WireCodec:
    """Registry lookup; unknown names fail loudly with the known set (the
    GradSyncHook / Strategy / XML validation funnel)."""
    codec = _REGISTRY.get(name)
    if codec is None:
        raise ValueError(
            f"unknown wire codec {name!r}; registered codecs: "
            f"{'|'.join(codec_names())}"
        )
    return codec


register_codec(WireCodec(
    name="off",
    apply=_identity_apply,
    encode=lambda flat, block_size=DEFAULT_BLOCK_SIZE: (flat,),
    decode=lambda wire, n, block_size=DEFAULT_BLOCK_SIZE: wire[0][:n],
    wire_bytes_per_element=lambda block_size=DEFAULT_BLOCK_SIZE, elem_bytes=4.0: float(elem_bytes),
))

register_codec(WireCodec(
    name="bf16",
    apply=_bf16_apply,
    encode=lambda flat, block_size=DEFAULT_BLOCK_SIZE: (
        flat.astype(jnp.bfloat16),
    ),
    decode=lambda wire, n, block_size=DEFAULT_BLOCK_SIZE: (
        wire[0].astype(jnp.float32)[:n]
    ),
    wire_bytes_per_element=lambda block_size=DEFAULT_BLOCK_SIZE, elem_bytes=4.0: 2.0,
))

register_codec(WireCodec(
    name="int8",
    apply=_int8_apply,
    encode=lambda flat, block_size=DEFAULT_BLOCK_SIZE: quantize_int8(
        flat, block_size
    ),
    decode=lambda wire, n, block_size=DEFAULT_BLOCK_SIZE: dequantize_int8(
        wire[0], wire[1], n
    ),
    wire_bytes_per_element=lambda block_size=DEFAULT_BLOCK_SIZE, elem_bytes=4.0: (
        1.0 + 4.0 / block_size
    ),
))


def resolve_wire_dtype(wire_dtype: Optional[str] = None) -> str:
    """The wire codec actually in force: the ``ADAPCC_WIRE_DTYPE`` sweep /
    operator override wins, then the caller's (synthesized) value, then
    ``"off"``.  A malformed override raises — a typo silently falling back
    to the default would invalidate an A/B (the ADAPCC_RING_CHUNK_BYTES
    policy)."""
    env = os.environ.get(WIRE_DTYPE_ENV)
    if env is not None and env.strip():
        name = env.strip()
        if name not in _REGISTRY:
            raise ValueError(
                f"{WIRE_DTYPE_ENV}={env!r}: expected one of "
                f"{'|'.join(codec_names())}"
            )
        return name
    if wire_dtype is None:
        return "off"
    return get_codec(wire_dtype).name


# --------------------------------------------------------------------------- #
# error feedback
# --------------------------------------------------------------------------- #

def error_feedback_step(
    grads: Any,
    residual: Any,
    apply_fn: Callable[[jnp.ndarray], jnp.ndarray],
) -> Tuple[Any, Any]:
    """One error-feedback round over a pytree: returns ``(wire,
    new_residual)`` with ``wire = apply(grads + residual)`` and
    ``new_residual = (grads + residual) - wire``.

    Exact invariant (same-rounding fp32 arithmetic): ``wire + new_residual
    == grads + residual``, so across steps the synced wire values plus the
    carried residual always sum to the true gradient mass.
    """
    compensated = jax.tree_util.tree_map(lambda g, r: g + r, grads, residual)
    wire = jax.tree_util.tree_map(apply_fn, compensated)
    new_residual = jax.tree_util.tree_map(
        lambda c, w: c - w, compensated, wire
    )
    return wire, new_residual


# --------------------------------------------------------------------------- #
# host-side codec timing (observability satellite)
# --------------------------------------------------------------------------- #

def timed_roundtrip(
    name: str,
    x: jnp.ndarray,
    block_size: int = DEFAULT_BLOCK_SIZE,
    registry=None,
) -> jnp.ndarray:
    """Eagerly encode+decode ``x`` through codec ``name``, recording wall
    times as ``quant.<name>.quantize`` / ``quant.<name>.dequantize`` in the
    metrics registry (the process-wide default when none given; its
    ``snapshot()`` exposes p50/p99 over a bounded reservoir).  Host-side only —
    inside a jitted program the codec is fused and has no separable time;
    this is the microbenchmark surface ``make quant-bench`` and the docs
    snippets use."""
    if registry is None:
        from adapcc_tpu.utils.observability import default_registry

        registry = default_registry()
    codec = get_codec(name)
    flat = jnp.asarray(x).reshape(-1).astype(jnp.float32)
    with registry.timer(f"quant.{name}.quantize"):
        wire = jax.block_until_ready(codec.encode(flat, block_size))
    with registry.timer(f"quant.{name}.dequantize"):
        out = jax.block_until_ready(
            codec.decode(wire, flat.shape[0], block_size)
        )
    return out.reshape(jnp.asarray(x).shape)
