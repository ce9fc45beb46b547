"""Forward-only pipeline building block: one compiled GPipe fill/drain.

This is the pipeline plane's *inference* primitive — a shape-static,
branch-free microbatch pipeline compiled into ONE XLA program, with
activations hopping stage→stage via ``lax.ppermute`` inside a single
``lax.scan``.  The training executor (:mod:`adapcc_tpu.pipe.executor`)
deliberately does NOT use it: training needs per-stage ``jax.vjp``
stashes, a 1F1B-bounded memory window, and per-hop trace events, all of
which live outside one fused scan.  What this block is for is cheap
forward sweeps (evaluation, pipelined inference over a block stack)
where one compiled program beats a host-driven tick loop.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _pipeline_shard(
    stage_params: Any,
    x: jnp.ndarray,
    *,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    axis_name: str,
):
    """Per-shard pipeline body.

    ``stage_params``: this rank's stage slice (leading stage axis stripped to
    size 1 by shard_map; squeezed here).  ``x``: the full microbatched input
    ``[M, mb, ...]``, replicated across the stage axis.  Returns ``[M, mb, ...]``
    outputs, valid on every rank.  Output gather design: the last stage could
    broadcast each microbatch result back through the drain ticks of the same
    ppermute ring (zero extra collectives, but it couples the scan carry to
    the emit schedule and costs ``stages − 1`` extra ticks of latency);
    instead every non-last stage contributes zeros and ONE ``lax.psum`` over
    the stage axis at the end replicates the last stage's buffer — one extra
    collective, no extra ticks, and the scan body stays oblivious to
    draining.
    """
    params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
    stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = x.shape[0]
    ticks = M + stages - 1

    # send stage i -> i+1 (the last stage's send wraps to 0 and is ignored)
    fwd = [(i, (i + 1) % stages) for i in range(stages)]

    out0 = jnp.zeros(x.shape, jax.eval_shape(lambda p, b: stage_fn(p, b), params, x[0]).dtype)
    carry0 = jnp.zeros_like(x[0])

    def tick(carry, t):
        incoming, outputs = carry
        # stage 0 ingests microbatch t while filling; afterwards it computes
        # on zeros whose results are never collected
        feed_idx = jnp.clip(t, 0, M - 1)
        inp = jnp.where(stage == 0, x[feed_idx], incoming)
        out = stage_fn(params, inp)
        # the last stage owns microbatch t-(stages-1) at tick t
        emit_idx = jnp.clip(t - (stages - 1), 0, M - 1)
        is_emit = jnp.logical_and(stage == stages - 1, t >= stages - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_emit, out, lax.dynamic_index_in_dim(outputs, emit_idx, 0, False)),
            emit_idx,
            0,
        )
        incoming = lax.ppermute(out, axis_name, fwd)
        return (incoming, outputs), None

    (_, outputs), _ = lax.scan(tick, (carry0, out0), jnp.arange(ticks))

    # only the last stage holds real outputs; replicate them to every stage
    # so the caller sees a replicated result (one psum over the stage axis)
    outputs = jnp.where(stage == stages - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    batch: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "stages",
    num_microbatches: int = 4,
) -> jnp.ndarray:
    """Run ``stage_fn`` as a forward pipeline over ``mesh[axis_name]``.

    ``stacked_params``: pytree whose leaves have a leading ``num_stages`` axis
    (stage s uses ``leaf[s]``).  ``batch [B, ...]`` with ``B`` divisible by
    ``num_microbatches``; microbatch size ``B // num_microbatches`` must keep
    ``stage_fn`` shape-preserving (same in/out shape), as in a transformer
    block stack.  Returns ``[B, ...]`` outputs, replicated.
    """
    B = batch.shape[0]
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible by microbatches {num_microbatches}")
    x = batch.reshape(num_microbatches, B // num_microbatches, *batch.shape[1:])

    fn = shard_map(
        partial(_pipeline_shard, stage_fn=stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stacked_params, x)
    return out.reshape(B, *out.shape[2:])
