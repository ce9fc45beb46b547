"""Mixture-of-Experts: one dropless expert layer, told which experts it holds.

The reference benchmarks a fastmoe ``FMoETransformerMLP`` whose all-to-all
dispatch is done by fastmoe/NCCL — *not* by AdapCC, whose ALLTOALL primitive
was an unimplemented stub (SURVEY §2.3; models/moe/train_moe.py:20-41).

:func:`routed_experts` is the layer.  The router (the caller's: softmax here,
sigmoid in :mod:`adapcc_tpu.models.trinity`) scores every token against ALL
experts; the layer holds ``held`` of them, experts ``offset … offset + held``,
as stacked weights.  It sorts the ``tokens × top_k`` assignments by expert,
gathers the rows that fall to its own experts, runs each projection as ONE
grouped product (``jax.lax.ragged_dot``: on a TPU XLA lowers it to a Mosaic
kernel that visits only the tiles the group sizes fill), and gathers the
weighted results back to their tokens.  Shapes are static by a bound no
routing can exceed, ``tokens × min(top_k, held)`` rows: nothing is dropped and
there is no capacity.  A layer that holds a share of the experts and is told
how many there are in all sizes its rows by twice its balanced share
(:func:`short_rows`) whenever the assignments it counted fit them, and by the
bound when they do not: the choice is made on the device, step by step.  On
one chip there is no exchange; what absent experts would have added is simply
not there.

:class:`MoEMLP` (the toy switch MLP of ``workloads/train_moe.py``) holds all
its experts and runs through the same layer; across chips
:mod:`adapcc_tpu.parallel.expert` reuses the same sort and the same grouped
product around its all-to-all, whose fixed buffers are the one place a
capacity remains.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.utils.observability import default_registry


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    d_model: int = 256
    d_hidden: int = 1024
    top_k: int = 2
    #: size of the all-to-all's per-(rank, expert) buffers in
    #: :mod:`adapcc_tpu.parallel.expert`; a single device drops nothing and
    #: does not read it
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    #: ST-MoE router z-loss: penalizes large router logits (mean logsumexp²
    #: over tokens), keeping the fp32 softmax well-scaled.  The term rides
    #: inside the returned aux scalar, so its EFFECTIVE weight on the
    #: objective is this coefficient × the consumer's aux-loss weight —
    #: with train_moe's default ``--aux-weight 0.01``, a 0.1 here lands on
    #: ST-MoE's recommended effective 1e-3.  Defaults to 0 (disabled) so the
    #: aux objective is opt-in; workloads that want it set it explicitly
    #: (train_moe passes 0.1).
    router_z_coef: float = 0.0

    @staticmethod
    def tiny() -> "MoEConfig":
        return MoEConfig(num_experts=4, d_model=32, d_hidden=64, top_k=2)


# --------------------------------------------------------------------------- #
# the expert layer
# --------------------------------------------------------------------------- #


class Assignments(NamedTuple):
    """The ``tokens × top_k`` assignments, sorted by held expert.

    ``order [M]``: the assignment (``token · top_k + choice``) in sorted row
    ``m``; rows past ``sum(sizes)`` hold assignments of experts not held.
    ``slot [N, k]``: the sorted row of each assignment, cut to ``[0, M)``.
    ``here [N, k]``: whether the assignment's expert is held.
    ``sizes [held]``: rows of each held expert, in order."""

    order: jnp.ndarray
    slot: jnp.ndarray
    here: jnp.ndarray
    sizes: jnp.ndarray


def assignment_bound(tokens: int, top_k: int, held: int) -> int:
    """Rows the held experts can be given at most: a token picks ``top_k``
    different experts, so at most ``min(top_k, held)`` of them are held."""
    return tokens * min(top_k, held)


#: the short rows are this many times the held experts' balanced share of the
#: assignments (``tokens × top_k × held ÷ num_experts``), in whole row tiles
SHORT_ROWS_OVER_SHARE = 2
ROW_TILE = 128


def short_rows(tokens: int, top_k: int, held: int, num_experts: Optional[int]) -> int:
    """Rows that take the held experts' assignments of any routing near
    balance; the bound itself where that is no fewer (every expert held, or
    a caller that does not say how many there are)."""
    bound = assignment_bound(tokens, top_k, held)
    if num_experts is None:
        return bound
    share = -(-tokens * top_k * held // num_experts)
    return min(bound, -(-SHORT_ROWS_OVER_SHARE * share // ROW_TILE) * ROW_TILE)


def held_assignments(ids: jnp.ndarray, offset: int, held: int) -> Assignments:
    """Sort ``ids [N, k]`` (expert of each assignment, over ALL experts) by
    held expert ``offset … offset + held``; the others sort to the end."""
    n, k = ids.shape
    bound = assignment_bound(n, k, held)
    local = ids.reshape(n * k).astype(jnp.int32) - offset
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True)
    slot = jnp.argsort(order)          # the inverse permutation, without a scatter
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :], axis=0)
    return Assignments(
        order[:bound].astype(jnp.int32),
        jnp.minimum(slot, bound - 1).reshape(n, k).astype(jnp.int32),
        here.reshape(n, k),
        sizes.astype(jnp.int32),
    )


def _rows_to_tokens(rows, sent: Assignments, weights):
    """``y[n] = sum_j weights[n, j] · rows[slot[n, j]]`` over the held
    assignments of token ``n``, accumulated in float32."""
    picked = jnp.take(rows, sent.slot.reshape(-1), axis=0).reshape(*sent.slot.shape, rows.shape[-1])
    # select, never multiply by zero: rows of no expert may hold anything.
    # Gather, select, scale and sum fuse into one pass over the picked rows
    # (a dot here would have them written out in float32 first)
    picked = jnp.where(sent.here[..., None], picked, 0).astype(jnp.float32)
    return jnp.sum(weights.astype(jnp.float32)[..., None] * picked, axis=1)


@jax.custom_vjp
def dispatch(x, sent: Assignments):
    """``x [N, D]`` → the sorted rows ``[M, D]``: row ``m`` is the token of
    assignment ``order[m]``.  Its transpose is a gather too (every held
    assignment has one row), so the backward pass scatters nothing."""
    return jnp.take(x, sent.order // sent.slot.shape[1], axis=0)


def _dispatch_fwd(x, sent):
    return dispatch(x, sent), sent


def _dispatch_bwd(sent, d_rows):
    dx = _rows_to_tokens(d_rows, sent, jnp.ones(sent.slot.shape, jnp.float32))
    return dx.astype(d_rows.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, weights, sent: Assignments):
    """The sorted rows' results ``[M, D]`` back to their tokens, each times
    its assignment's weight (``weights [N, k]``, float32): ``[N, D]`` float32.
    Rows past ``sum(sizes)`` are never read into the result, and get no
    cotangent."""
    return _rows_to_tokens(rows, sent, weights)


def _combine_fwd(rows, weights, sent):
    return combine(rows, weights, sent), (rows, weights, sent)


def _combine_bwd(res, dy):
    rows, weights, sent = res
    k = sent.slot.shape[1]
    filled = jnp.arange(rows.shape[0]) < jnp.sum(sent.sizes)
    dy_rows = jnp.take(dy, sent.order // k, axis=0)                      # [M, D] float32
    w_rows = jnp.take(weights.reshape(-1), sent.order).astype(jnp.float32)
    d_rows = jnp.where(filled[:, None], w_rows[:, None] * dy_rows, 0.0)
    dw_rows = jnp.sum(rows.astype(jnp.float32) * dy_rows, axis=-1)       # [M]
    dw = jnp.where(sent.here, jnp.take(dw_rows, sent.slot.reshape(-1)).reshape(sent.slot.shape), 0.0)
    return d_rows.astype(rows.dtype), dw.astype(weights.dtype), None


combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_ffn(rows, sizes, stacked: Dict[str, jnp.ndarray], act: Callable, dtype):
    """Every held expert's MLP over its run of ``rows``: ``act(x W1) W2``,
    gated by ``x W3`` where the stack has one.  One grouped product for each
    projection; rows past ``sum(sizes)`` belong to no expert and come out
    undefined (the caller masks them)."""
    product = functools.partial(jax.lax.ragged_dot, group_sizes=sizes)
    rows = rows.astype(dtype)
    h = act(product(rows, stacked["w1"].astype(dtype)))
    if "w3" in stacked:
        h = h * product(rows, stacked["w3"].astype(dtype))
    return product(h, stacked["w2"].astype(dtype))


def _cut(sent: Assignments, rows: int) -> Assignments:
    """``sent`` with its sorted list cut to ``rows``: the same assignments
    where ``sum(sizes) <= rows``."""
    return Assignments(sent.order[:rows], jnp.minimum(sent.slot, rows - 1), sent.here, sent.sizes)


def _experts(x, weights, stacked, sent: Assignments, act: Callable, dtype):
    out = grouped_ffn(dispatch(x, sent), sent.sizes, stacked, act, dtype)
    return combine(out, weights, sent)


def _two_paths(sent: Assignments, short: int, path: Callable):
    """What the layer's ``lax.cond`` takes first: whether the assignments
    fit ``short`` rows, ``path(sent cut to them)`` and ``path(sent)``, each
    with a barrier behind its results.  The barrier keeps a branch whole.
    Without it the compiler moves what both branches end with out of them
    (the combine's masked sum, which then reads the picked rows, ``[tokens,
    top_k, D]``, back from memory) and what reads their results into them
    (the widening of the weights' gradients, which then leave a branch in
    float32 and stay that wide until the optimizer has them)."""
    def whole(sent):
        return lambda *a: jax.lax.optimization_barrier(path(sent)(*a))

    return jnp.sum(sent.sizes) <= short, whole(_cut(sent, short)), whole(sent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _short_or_bound(x, weights, stacked, sent: Assignments, short: int, act: Callable, dtype):
    """:func:`_experts` over ``short`` rows where the assignments fit them,
    over all of ``sent``'s where they do not.  Differentiating a ``cond``
    would have each branch write zeros for the other's residuals, the
    bound-sized ones too: so the forward pass keeps the inputs alone and the
    backward pass chooses again, recomputing inside the branch it takes
    (what ``jax.checkpoint`` does for the layer of one path)."""
    forward = lambda sent: lambda *a: _experts(*a, sent, act, dtype)  # noqa: E731
    return jax.lax.cond(*_two_paths(sent, short, forward), x, weights, stacked)


def _short_or_bound_fwd(x, weights, stacked, sent, short, act, dtype):
    return _short_or_bound(x, weights, stacked, sent, short, act, dtype), (x, weights, stacked, sent)


def _short_or_bound_bwd(short, act, dtype, res, dy):
    x, weights, stacked, sent = res

    def backward(sent):
        def run(dy, x, weights, stacked):
            # down to the stacked weights as the products read them: their
            # gradients leave the branch in that dtype, as the products give them
            low = jax.tree_util.tree_map(lambda w: w.astype(dtype), stacked)
            return jax.vjp(lambda *a: _experts(*a, sent, act, dtype), x, weights, low)[1](dy)

        return run

    dx, dweights, dlow = jax.lax.cond(*_two_paths(sent, short, backward), dy, x, weights, stacked)
    return dx, dweights, jax.tree_util.tree_map(lambda g, w: g.astype(w.dtype), dlow, stacked), None


_short_or_bound.defvjp(_short_or_bound_fwd, _short_or_bound_bwd)


def routed_experts(
    x: jnp.ndarray,
    ids: jnp.ndarray,
    weights: jnp.ndarray,
    stacked: Dict[str, jnp.ndarray],
    *,
    offset: int = 0,
    num_experts: Optional[int] = None,
    act: Callable = nn.gelu,
    dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of a routed layer.

    ``x [N, D]`` tokens; ``ids [N, k]`` the experts each token chose among
    ALL ``num_experts`` experts and ``weights [N, k]`` their float32 weights
    (applied to the expert's output); ``stacked`` the held experts' weights
    (``w1 [held, D, H]``, ``w2 [held, H, D]``, optionally the gate's ``w3``),
    experts ``offset … offset + held``.  Returns ``(y [N, D] float32, sizes
    [held])``: the sum over each token's held assignments, and how many
    assignments each held expert was given.  No assignment of a held expert
    is dropped.

    The rows' intermediates are recomputed in the backward pass.  They are
    sized by the bound (``tokens × min(top_k, held)`` rows), eight times what
    a balanced router fills at 16 experts of 128: where ``num_experts`` says
    the held experts are a share, a step whose assignments fit
    :func:`short_rows` runs over that many rows instead, forward and backward
    (one ``conditional`` each); any other step, and a layer whose short rows
    would reach the bound, runs over the bound.
    """
    held = stacked["w1"].shape[0]
    bound = assignment_bound(x.shape[0], ids.shape[1], held)
    short = short_rows(x.shape[0], ids.shape[1], held, num_experts)
    metrics = default_registry()
    metrics.gauge("moe.assignment_bound", bound)
    metrics.gauge("moe.short_rows", short)
    weights = weights.astype(jnp.float32)

    @jax.checkpoint
    def one_path(x, weights, stacked):
        sent = held_assignments(ids, offset, held)
        return _experts(x, weights, stacked, sent, act, dtype), sent.sizes

    def two_paths(x, weights, stacked):
        sent = held_assignments(ids, offset, held)
        return _short_or_bound(x, weights, stacked, sent, short, act, dtype), sent.sizes

    with jax.named_scope("moe_experts"):
        return (one_path if short == bound else two_paths)(x, weights, stacked)


def record_routing(sizes, dropped=0, metrics=None) -> None:
    """Per-step samples from what a compiled step returned beside its loss:
    ``sizes [layers, held]`` assignments of each held expert in each expert
    layer.  ``moe.assignments_here``, ``moe.load_max_over_mean`` (fullest
    held expert ÷ mean) and ``moe.rows_fit`` (1.0 where the layer's
    assignments fit its short rows, the gauge ``moe.short_rows`` that the
    layer left when it was traced; no sample before that) once for each
    layer; ``moe.dropped`` counts what an exchange's buffers could not take
    (0 on one chip, by construction)."""
    metrics = metrics or default_registry()
    short = metrics.snapshot()["gauges"].get("moe.short_rows")
    for layer in np.atleast_2d(np.asarray(sizes, np.float64)):
        metrics.sample("moe.assignments_here", float(layer.sum()))
        if short is not None:
            metrics.sample("moe.rows_fit", float(layer.sum() <= short))
        if layer.sum() > 0:
            metrics.sample("moe.load_max_over_mean", float(layer.max() / layer.mean()))
    metrics.incr("moe.dropped", float(np.sum(np.asarray(dropped))))


# --------------------------------------------------------------------------- #
# the toy switch MLP
# --------------------------------------------------------------------------- #


def softmax_router(logits: jnp.ndarray, top_k: int, z_coef: float, mean=lambda x: x):
    """``(ids [N, k], weights [N, k], aux)`` of a softmax router: the top-k
    probabilities as weights, the switch load-balancing loss (times
    ``num_experts``) plus the optional z-loss.  ``mean`` averages the
    per-shard statistics across shards where tokens are sharded."""
    num_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    me = mean(jnp.mean(probs, axis=0))
    ce = mean(jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), num_experts), axis=0))
    aux = num_experts * jnp.sum(me * ce)
    if z_coef:
        z = jax.nn.logsumexp(logits, axis=-1)
        aux = aux + z_coef * mean(jnp.mean(z**2))
    weights, ids = jax.lax.top_k(probs, top_k)
    return ids, weights, aux


class MoEMLP(nn.Module):
    """Top-k routed expert MLP (softmax router, GELU experts), every expert
    held here."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        """``x [B, T, D]`` → ``(y [B, T, D], aux_loss scalar)``."""
        cfg = self.cfg
        B, T, D = x.shape
        tokens = x.reshape(B * T, D)

        # routing (fp32 for a stable softmax)
        with jax.named_scope("moe_route"):
            gate_logits = nn.Dense(cfg.num_experts, dtype=jnp.float32, name="router")(
                tokens.astype(jnp.float32)
            )
            ids, weights, aux_loss = softmax_router(gate_logits, cfg.top_k, cfg.router_z_coef)

        w1 = self.param(
            "w1", nn.initializers.normal(0.02), (cfg.num_experts, D, cfg.d_hidden)
        )
        w2 = self.param(
            "w2", nn.initializers.normal(0.02), (cfg.num_experts, cfg.d_hidden, D)
        )
        y, _ = routed_experts(tokens, ids, weights, {"w1": w1, "w2": w2}, dtype=cfg.dtype)
        return y.reshape(B, T, D).astype(x.dtype), aux_loss
