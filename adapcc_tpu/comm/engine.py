"""Schedule-driven collective engine on a `jax.sharding.Mesh`.

The TPU-native replacement for the reference's native transmission contexts
(csrc/allreduce.cu / reduce.cu / boardcast.cu): where the reference spawns two
persistent pthreads per tree that move 4 MB chunks through IPC staging buffers
(allreduce.cu:430-659), here every strategy tree lowers to a static sequence
of masked ``jax.lax.ppermute`` rounds inside one jitted ``shard_map`` program.
XLA owns chunking, overlap, and ICI routing; the strategy owns the *shape* of
the communication (which links carry data, in what order, rooted where).

Relay semantics (reference control.cu): the active set arrives as a runtime
``[world]`` mask, so step-to-step relay decisions never trigger recompilation.
Inactive ranks contribute the reduction identity but remain on the data path
as forwarders — the masked-collective formulation of the reference's
``<hasRecv, hasLocal, hasKernel, hasSend>`` role algebra.

Full-world allreduce additionally has an XLA fast path (``lax.psum``), which
is the optimal program on an ICI torus; the schedule path exists for subset /
relay semantics and for topology-shaped strategies.  ``ALLGATHER`` /
``ALLTOALL`` / ``REDUCESCATTER`` — enum stubs the reference never implemented
(commu.py:65-69 maps only three primitives) — are provided natively via XLA
collectives.
"""

from __future__ import annotations

import functools
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from adapcc_tpu.primitives import ReduceOp
from adapcc_tpu.strategy.ir import CommRound, Strategy, Tree
from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.ops.kernel_mode import resolve_interpret


#: default KV-stream granularity: one DCN chunk per ~4 MiB of wire payload
#: (the reference's transmission contexts moved 4 MB IPC chunks; the trace
#: records the chunk count so a future live window can sweep it)
KV_TRANSFER_CHUNK_BYTES = 4 << 20


class EpochMismatch(RuntimeError):
    """A collective was issued against a world epoch that is no longer
    current (the coordinator advanced the WorldView — a rank died, was
    demoted, or recovered — and the engine swapped plans).

    Retryable by construction: the caller refreshes its epoch token (the
    exception carries ``current``) and re-issues; the
    :class:`~adapcc_tpu.communicator.Communicator` layer does exactly that
    with bounded retry + backoff.  This is the hang-free contract — a
    stale issuer gets a loud, catchable signal instead of running a
    schedule the world has moved past.
    """

    def __init__(self, issued: int, current: int) -> None:
        super().__init__(
            f"collective issued against dead epoch {issued} (current epoch "
            f"is {current}); refresh the epoch token and retry"
        )
        self.issued = issued
        self.current = current


def _identity_for(op: ReduceOp, dtype) -> jnp.ndarray:
    if op is ReduceOp.MAX:
        return jnp.asarray(-jnp.inf if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo(dtype).min, dtype)
    return jnp.asarray(0, dtype)


def _dst_mask(round_: CommRound, world: int) -> np.ndarray:
    m = np.zeros((world,), dtype=bool)
    for _, d in round_.edges:
        m[d] = True
    return m


def _segment_sizes(n: int, shares: Sequence[float]) -> List[int]:
    """Static split of ``n`` elements across trees, proportional to shares.

    Mirrors the reference's 1/numTrans sharding (allreduce.cu:310,536), except
    shares may be non-uniform when the MILP solver optimized them.
    """
    sizes = [int(n * s) for s in shares]
    rem = n - sum(sizes)
    i = 0
    while rem > 0:
        sizes[i % len(sizes)] += 1
        rem -= 1
        i += 1
    return sizes


# --------------------------------------------------------------------------- #
# per-shard (inside shard_map) schedule execution
# --------------------------------------------------------------------------- #

def _run_reduce_rounds(
    acc: jnp.ndarray,
    rounds: Sequence[CommRound],
    axis_name: str,
    world: int,
    op: ReduceOp,
) -> jnp.ndarray:
    """Push partial reductions up the tree, one ppermute per round.

    ppermute delivers zeros to ranks that are not a destination, so for SUM
    the combine is a plain add; MAX needs an explicit destination mask.
    """
    for rnd in rounds:
        recvd = lax.ppermute(acc, axis_name, list(rnd.edges))
        if op is ReduceOp.MAX:
            is_dst = jnp.asarray(_dst_mask(rnd, world))[lax.axis_index(axis_name)]
            acc = jnp.where(is_dst, jnp.maximum(acc, recvd), acc)
        else:
            acc = acc + recvd
    return acc


def _run_broadcast_rounds(
    acc: jnp.ndarray,
    rounds: Sequence[CommRound],
    axis_name: str,
    world: int,
) -> jnp.ndarray:
    """Stream the rooted value down the tree; destinations adopt what lands."""
    for rnd in rounds:
        recvd = lax.ppermute(acc, axis_name, list(rnd.edges))
        is_dst = jnp.asarray(_dst_mask(rnd, world))[lax.axis_index(axis_name)]
        acc = jnp.where(is_dst, recvd, acc)
    return acc


def _mask_contribution(
    seg: jnp.ndarray, active_mask: jnp.ndarray, axis_name: str, op: ReduceOp
) -> jnp.ndarray:
    """Relay masking: inactive ranks contribute the reduction identity while
    staying on the forwarding path (reference hasLocal gate, control.cu)."""
    my_active = active_mask[lax.axis_index(axis_name)]
    return jnp.where(my_active, seg, _identity_for(op, seg.dtype))


# --------------------------------------------------------------------------- #
# merged multi-tree execution: one ppermute per round ACROSS trees
# --------------------------------------------------------------------------- #
#
# The reference gets tree-level concurrency from one pthread pair per tree
# (allreduce.cu:735-742): all trees' round-k transfers ride different links
# at the same wall-clock time.  The naive XLA lowering loses that — each
# tree's round chain runs sequentially inside the traced program.  Rotated
# trees (ring / binary / ParTrees) have isomorphic round structures, so the
# merged executor stacks the per-tree segments into one [T, seg] buffer and
# combines every tree's round-k edges into as few ppermutes as the
# partial-permutation contract allows (greedy coloring: within one ppermute
# each rank sends at most once and receives at most once).  Each rank
# *selects* which tree's row it sends from a static per-round table, so the
# per-link bytes are identical to the sequential path — only the dispatch
# count drops, by ~num_trans.  A ring strategy with T=world merged this way
# IS the bandwidth-optimal segmented ring allreduce (reduce-scatter shape up,
# all-gather shape down).


class _MergedPlan:
    """Static per-round send/receive tables for merged multi-tree execution.

    Each group is ``(perm, src_row, dst_row, is_dst)``: the ppermute edge
    list plus, per rank, which stacked row it sends / receives into.
    """

    def __init__(self, reduce_groups, broadcast_groups):
        self.reduce_groups = reduce_groups
        self.broadcast_groups = broadcast_groups


def _color_rounds(per_tree_rounds: Sequence[Sequence[CommRound]], world: int):
    """Align trees' rounds by index and split each union into valid partial
    permutations; returns the group table list."""
    groups = []
    depth = max((len(r) for r in per_tree_rounds), default=0)
    for k in range(depth):
        edges: List[Tuple[int, int, int]] = []  # (src, dst, tree)
        for ti, rounds in enumerate(per_tree_rounds):
            if k < len(rounds):
                edges.extend((s, d, ti) for s, d in rounds[k].edges)
        colors: List[List[Tuple[int, int, int]]] = []
        for e in edges:
            for c in colors:
                if all(e[0] != s and e[1] != d for s, d, _ in c):
                    c.append(e)
                    break
            else:
                colors.append([e])
        for c in colors:
            perm = tuple((s, d) for s, d, _ in c)
            src_row = np.zeros((world,), np.int32)
            dst_row = np.zeros((world,), np.int32)
            is_dst = np.zeros((world,), bool)
            for s, d, t in c:
                src_row[s] = t
                dst_row[d] = t
                is_dst[d] = True
            groups.append((perm, src_row, dst_row, is_dst))
    return groups


_MERGED_PLANS: Dict[Tuple, Optional[_MergedPlan]] = {}

#: one deprecation warning per process for the reference's "boardcast"
#: spelling (satellite of the latency PR; see CollectiveEngine.boardcast)
_BOARDCAST_WARNED = False


def _merged_env_disabled() -> bool:
    """``ADAPCC_MERGE_ROUNDS=0`` disables round merging everywhere — the A/B
    knob for measuring the merged executor against sequential per-tree
    chains on hardware (flat and two-level paths share it).  Unknown values
    raise: a typo silently enabling the default would invalidate the
    A/B."""
    import os

    val = os.environ.get("ADAPCC_MERGE_ROUNDS", "1").strip().lower()
    if val in ("0", "off", "false", "no"):
        return True
    if val in ("", "1", "on", "true", "yes"):
        return False
    raise ValueError(
        f"ADAPCC_MERGE_ROUNDS={val!r}: expected 1/on/true or 0/off/false"
    )


def _merged_plan(strategy: Strategy) -> Optional[_MergedPlan]:
    """Build (and cache) the merged plan, or None when merging buys nothing:
    a single tree (groups == rounds) or heavily skewed MILP shares (stacking
    pads every segment to the largest, wasting bandwidth)."""
    return _build_merged_plan(
        strategy,
        strategy.world_size,
        lambda: (
            [t.reduce_rounds() for t in strategy.trees],
            [t.broadcast_rounds() for t in strategy.trees],
        ),
        _MERGED_PLANS,
    )


def _build_merged_plan(
    strategy: Strategy,
    world: int,
    rounds_of: Callable[[], Tuple[list, list]],
    cache: Dict,
    key_extra: Tuple = (),
) -> Optional[_MergedPlan]:
    """Shared gate + coloring + cache for merged plans (flat and two-level
    differ only in the rounds source and the permutation world).

    Returns None when merging buys nothing: env kill-switch, a single tree
    (groups == rounds), heavily skewed MILP shares (stacking pads every
    segment to the largest, wasting bandwidth), or a coloring that fails to
    reduce the round count.
    """
    if _merged_env_disabled():
        return None
    shares = strategy.tree_shares()
    key = (
        strategy.fingerprint(), *key_extra,
        tuple(round(s, 6) for s in shares),
    )
    if key in cache:
        return cache[key]
    plan: Optional[_MergedPlan] = None
    if len(strategy.trees) > 1 and max(shares) <= 2.0 * min(shares):
        reduce_rounds, bcast_rounds = rounds_of()
        rg = _color_rounds(reduce_rounds, world)
        bg = _color_rounds(bcast_rounds, world)
        n_sequential = sum(len(r) for r in reduce_rounds) + sum(
            len(r) for r in bcast_rounds
        )
        if len(rg) + len(bg) < n_sequential:
            plan = _MergedPlan(rg, bg)
    cache[key] = plan
    return plan


def _stack_segments(
    flat: jnp.ndarray, sizes: Sequence[int], pad_value
) -> jnp.ndarray:
    """[n] → [T, max(sizes)] with each tree's segment padded to the max."""
    pad = max(sizes)
    rows = []
    off = 0
    for size in sizes:
        seg = flat[off : off + size]
        if size < pad:
            seg = jnp.concatenate([seg, jnp.full((pad - size,), pad_value, flat.dtype)])
        rows.append(seg)
        off += size
    return jnp.stack(rows)


def _unstack_segments(stacked: jnp.ndarray, sizes: Sequence[int]) -> jnp.ndarray:
    return jnp.concatenate([stacked[t, :size] for t, size in enumerate(sizes)])


def _run_merged_groups(
    stacked: jnp.ndarray,
    groups,
    axis_name: str,
    combine: str,
) -> jnp.ndarray:
    """Run one phase's merged rounds: each group is one ppermute where rank r
    sends its ``src_row[r]``-th stacked row and folds the received segment
    into its ``dst_row[r]``-th row (``combine``: add | max | adopt)."""
    me = lax.axis_index(axis_name)
    for perm, src_row, dst_row, is_dst in groups:
        send = lax.dynamic_index_in_dim(
            stacked, jnp.asarray(src_row)[me], 0, keepdims=False
        )
        recvd = lax.ppermute(send, axis_name, perm)
        row = jnp.asarray(dst_row)[me]
        sel = jnp.asarray(is_dst)[me]
        cur = lax.dynamic_index_in_dim(stacked, row, 0, keepdims=False)
        if combine == "add":
            new = jnp.where(sel, cur + recvd, cur)
        elif combine == "max":
            new = jnp.where(sel, jnp.maximum(cur, recvd), cur)
        else:  # adopt (broadcast)
            new = jnp.where(sel, recvd, cur)
        stacked = lax.dynamic_update_index_in_dim(stacked, new, row, 0)
    return stacked


def _run_merged(
    x: jnp.ndarray,
    strategy: Strategy,
    plan: _MergedPlan,
    axis_name: str,
    op: ReduceOp,
    phases: str,  # "reduce" | "broadcast" | "both"
    active_mask: Optional[jnp.ndarray],
) -> jnp.ndarray:
    flat = x.reshape(-1)
    if flat.size == 0:
        return x
    if active_mask is not None:
        flat = _mask_contribution(flat, active_mask, axis_name, op)
    sizes = _segment_sizes(flat.size, strategy.tree_shares())
    pad_value = _identity_for(op, flat.dtype)
    stacked = _stack_segments(flat, sizes, pad_value)
    if phases in ("reduce", "both"):
        combine = "max" if op is ReduceOp.MAX else "add"
        stacked = _run_merged_groups(stacked, plan.reduce_groups, axis_name, combine)
    if phases in ("broadcast", "both"):
        stacked = _run_merged_groups(stacked, plan.broadcast_groups, axis_name, "adopt")
    return _unstack_segments(stacked, sizes).reshape(x.shape)


def _run_segments(
    x: jnp.ndarray,
    strategy: Strategy,
    per_segment: Callable[[jnp.ndarray, Tree], jnp.ndarray],
) -> jnp.ndarray:
    """Shared scaffolding: flatten, split across trees by share, run each
    tree's segment program, reassemble in the original shape."""
    flat = x.reshape(-1)
    if flat.size == 0:
        return x
    sizes = _segment_sizes(flat.size, strategy.tree_shares())
    outs: List[jnp.ndarray] = []
    off = 0
    for tree, size in zip(strategy.trees, sizes):
        if size == 0:
            continue
        outs.append(per_segment(flat[off : off + size], tree))
        off += size
    result = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    return result.reshape(x.shape)


def _avg_normalize(result: jnp.ndarray, active_mask: jnp.ndarray, op: ReduceOp) -> jnp.ndarray:
    if op is not ReduceOp.AVG:
        return result
    n_active = jnp.maximum(jnp.sum(active_mask.astype(result.dtype)), 1)
    return result / n_active


def masked_psum_shard(
    x: jnp.ndarray,
    active_mask: jnp.ndarray,
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
) -> jnp.ndarray:
    """Subset allreduce as one XLA collective: ``psum(where(active, x, id))``.

    On a flat ICI mesh this is the optimal program for the reference's
    subset-collective semantics — the masked contribution *is* the relay
    algebra (inactive ranks forward zeros through the torus), and XLA's
    all-reduce already uses the bandwidth-optimal schedule.  The tree-schedule
    path (:func:`allreduce_shard`) earns its keep on hierarchical/irregular
    topologies where the synthesized strategy beats the flat collective.
    """
    contrib = _mask_contribution(x, active_mask, axis_name, op)
    n_active = jnp.maximum(jnp.sum(active_mask.astype(x.dtype)), 1)
    return _fused_reduce(contrib, axis_name, op, n_active)


def allreduce_shard(
    x: jnp.ndarray,
    active_mask: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
) -> jnp.ndarray:
    """Strategy-shaped allreduce over ``axis_name``; call inside shard_map.

    ``x`` is this rank's contribution (any shape); ``active_mask`` is a
    ``[world]`` bool/int array.  Result lands on every rank, active or not
    (relays receive too, matching the reference broadcast phase).
    """
    world = strategy.world_size
    plan = _merged_plan(strategy)
    if plan is not None:
        result = _run_merged(x, strategy, plan, axis_name, op, "both", active_mask)
        return _avg_normalize(result, active_mask, op)

    def per_segment(seg, tree):
        acc = _mask_contribution(seg, active_mask, axis_name, op)
        acc = _run_reduce_rounds(acc, tree.reduce_rounds(), axis_name, world, op)
        return _run_broadcast_rounds(acc, tree.broadcast_rounds(), axis_name, world)

    return _avg_normalize(_run_segments(x, strategy, per_segment), active_mask, op)


def _chunk_bounds(nelems: int, chunk_elems: int) -> List[Tuple[int, int]]:
    """Static ``(offset, length)`` split of a flat payload at the chunk
    granularity; the tail chunk keeps the remainder."""
    return [
        (off, min(chunk_elems, nelems - off))
        for off in range(0, nelems, chunk_elems)
    ]


def _tree_allreduce_chunk(
    seg: jnp.ndarray,
    tree: Tree,
    active_mask: jnp.ndarray,
    axis_name: str,
    world: int,
    op: ReduceOp,
) -> jnp.ndarray:
    """One chunk's allreduce through ONE tree's round schedule — the unit
    the chunked dispatch (and its dispatch-count tests) fan out over."""
    acc = _mask_contribution(seg, active_mask, axis_name, op)
    acc = _run_reduce_rounds(acc, tree.reduce_rounds(), axis_name, world, op)
    return _run_broadcast_rounds(acc, tree.broadcast_rounds(), axis_name, world)


def chunked_allreduce_shard(
    x: jnp.ndarray,
    active_mask: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
    chunk_bytes: Optional[int] = None,
) -> jnp.ndarray:
    """Bucket-rolling strategy allreduce: the payload splits into
    independent per-chunk collectives of at most ``chunk_bytes`` each
    (``ADAPCC_RING_CHUNK_BYTES`` overrides, the one chunk-knob precedence
    ladder), so XLA's async collectives can interleave chunk transfers
    with whatever compute still runs — the engine half of the per-bucket
    rolling sync (docs/OVERLAP.md §2, the reference's 4 MB chunk pipeline,
    commu.py:401-403).

    Bitwise contract: the payload is first split across trees by share at
    the SAME boundaries as the unchunked dispatch (``_segment_sizes`` over
    the whole payload), and only then chunked within each tree's segment —
    so every element rides the same tree and the same per-round add order
    as :func:`allreduce_shard`, and the result is bitwise-identical on
    single- and multi-tree strategies alike.  Chunking the flat payload
    directly would shift the element→tree assignment and change last-bit
    reduction order on multi-tree strategies."""
    from adapcc_tpu.comm.pallas_ring import resolve_chunk_bytes

    flat = x.reshape(-1)
    if flat.size == 0:
        return x
    chunk_elems = max(1, resolve_chunk_bytes(chunk_bytes) // flat.dtype.itemsize)
    if flat.size <= chunk_elems:
        return allreduce_shard(x, active_mask, strategy, axis_name=axis_name, op=op)
    world = strategy.world_size
    sizes = _segment_sizes(flat.size, strategy.tree_shares())
    outs: List[jnp.ndarray] = []
    off = 0
    for tree, size in zip(strategy.trees, sizes):
        if size == 0:
            continue
        seg = flat[off : off + size]
        off += size
        outs.extend(
            _tree_allreduce_chunk(
                seg[o : o + n], tree, active_mask, axis_name, world, op
            )
            for o, n in _chunk_bounds(size, chunk_elems)
        )
    result = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    return _avg_normalize(result, active_mask, op).reshape(x.shape)


def chunked_psum_shard(
    x: jnp.ndarray,
    active_mask: Optional[jnp.ndarray],
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
    chunk_bytes: Optional[int] = None,
    world: Optional[int] = None,
) -> jnp.ndarray:
    """Bucket-rolling XLA-collective allreduce: the psum-plane twin of
    :func:`chunked_allreduce_shard`.  ``active_mask=None`` is the
    statically-full-world case (``world`` supplies the AVG denominator);
    a mask routes each chunk through :func:`masked_psum_shard` with the
    usual relay semantics."""
    from adapcc_tpu.comm.pallas_ring import resolve_chunk_bytes

    flat = x.reshape(-1)
    if flat.size == 0:
        return x
    if active_mask is None and world is None:
        raise ValueError("chunked_psum_shard needs world when active_mask is None")
    chunk_elems = max(1, resolve_chunk_bytes(chunk_bytes) // flat.dtype.itemsize)

    def one(seg: jnp.ndarray) -> jnp.ndarray:
        if active_mask is None:
            return _fused_reduce(seg, axis_name, op, world)
        return masked_psum_shard(seg, active_mask, axis_name, op)

    if flat.size <= chunk_elems:
        return one(flat).reshape(x.shape)
    outs = [
        one(flat[off : off + n])
        for off, n in _chunk_bounds(flat.size, chunk_elems)
    ]
    return jnp.concatenate(outs).reshape(x.shape)


def reduce_shard(
    x: jnp.ndarray,
    active_mask: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
) -> jnp.ndarray:
    """Reduce-to-root: each tree's segment is valid on that tree's root only
    (reference reduceContext keeps the result at the root, reduce.cu:258-269);
    other ranks hold partial sums for their segment."""
    world = strategy.world_size
    plan = _merged_plan(strategy)
    if plan is not None:
        result = _run_merged(x, strategy, plan, axis_name, op, "reduce", active_mask)
        return _avg_normalize(result, active_mask, op)

    def per_segment(seg, tree):
        acc = _mask_contribution(seg, active_mask, axis_name, op)
        return _run_reduce_rounds(acc, tree.reduce_rounds(), axis_name, world, op)

    return _avg_normalize(_run_segments(x, strategy, per_segment), active_mask, op)


def _fused_reduce(x: jnp.ndarray, axis_name: str, op: ReduceOp, denom) -> jnp.ndarray:
    """One XLA collective for the op: pmax for MAX, psum for SUM, psum/denom
    for AVG.  ``denom`` is the caller's averaging base — the full world on
    fast paths, the active count on masked paths."""
    if op is ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    s = lax.psum(x, axis_name)
    if op is ReduceOp.AVG:
        s = s / denom
    return s


def reduce_fastpath_shard(
    x: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
    op: ReduceOp = ReduceOp.SUM,
) -> jnp.ndarray:
    """Full-world reduce as one fused XLA collective per tree segment: psum
    (or pmax), result kept on that segment's root — same contract as the
    schedule path (root holds the total, others keep their local partial)
    without the per-round ppermute overhead on a healthy pod."""
    me = lax.axis_index(axis_name)

    def per_segment(seg, tree):
        total = _fused_reduce(seg, axis_name, op, strategy.world_size)
        return jnp.where(me == tree.root, total, seg)

    return _run_segments(x, strategy, per_segment)


def broadcast_fastpath_shard(
    x: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
) -> jnp.ndarray:
    """Full-world broadcast as one masked psum per tree segment: only the
    root contributes, so the sum IS the root's value on every rank."""
    me = lax.axis_index(axis_name)

    def per_segment(seg, tree):
        contrib = jnp.where(me == tree.root, seg, jnp.zeros_like(seg))
        # psum promotes bool to int32; the schedule path preserves dtype
        return lax.psum(contrib, axis_name).astype(seg.dtype)

    return _run_segments(x, strategy, per_segment)


def broadcast_shard(
    x: jnp.ndarray,
    strategy: Strategy,
    axis_name: str = RANKS_AXIS,
) -> jnp.ndarray:
    """Broadcast from each tree's root: the root's segment replaces everyone
    else's (reference boardcastContext reads the user tensor at the root,
    boardcast.cu:279-282)."""
    world = strategy.world_size
    plan = _merged_plan(strategy)
    if plan is not None:
        return _run_merged(
            x, strategy, plan, axis_name, ReduceOp.SUM, "broadcast", None
        )

    def per_segment(seg, tree):
        return _run_broadcast_rounds(seg, tree.broadcast_rounds(), axis_name, world)

    return _run_segments(x, strategy, per_segment)


# --------------------------------------------------------------------------- #
# host-level engine: compiled-program cache + stacked-array entry points
# --------------------------------------------------------------------------- #

class CollectiveEngine:
    """Compiled, cached collective programs over one world mesh.

    The analog of the reference's persistent transmission context
    (SURVEY.md §3.2): creating one is cheap; the first call per
    (primitive, shape, dtype, op) compiles and caches, later calls replay the
    executable.  ``clear()`` drops the cache — the analog of
    ``exitThreads`` tearing contexts down before re-synthesis
    (reconstruct_topology, adapcc.py:63-67).

    Entry points take **stacked** arrays of shape ``[world, ...]`` where row
    ``r`` is rank ``r``'s contribution, and return the same shape (row ``r``
    = rank ``r``'s result).  This is the single-controller view; training
    loops instead call the ``*_shard`` functions inside their own shard_map.
    """

    def __init__(
        self,
        mesh: Mesh,
        strategy: Strategy,
        axis_name: str = RANKS_AXIS,
        use_xla_fastpath: bool = True,
        trace: Optional[Any] = None,
        tuner: Optional[Any] = None,
    ) -> None:
        if mesh.devices.size != strategy.world_size:
            raise ValueError(
                f"mesh has {mesh.devices.size} devices but strategy world is "
                f"{strategy.world_size}"
            )
        # fail fast on a typo'd A/B knob: dying here costs nothing, dying at
        # the first traced collective costs the whole backend/model setup
        _merged_env_disabled()
        from adapcc_tpu.tuner import CollectiveTuner, tuner_mode

        # same fail-fast policy for ADAPCC_TUNER; additionally, a non-off
        # mode with no caller-provided tuner auto-builds one for this mesh,
        # so `ADAPCC_TUNER=record benchmarks.collectives ...` measures into
        # the database with zero wiring at the call site
        if tuner is None and tuner_mode() != "off":
            tuner = CollectiveTuner.for_mesh(mesh)
        #: optional CollectiveTuner: consulted by ring_allreduce when
        #: ADAPCC_TUNER=choose, fed dispatch walltimes when record|choose
        self.tuner = tuner
        self.mesh = mesh
        self.strategy = strategy
        # two-level world: a ("dcn", "ici") mesh executes strategies
        # hierarchically — intra-slice traffic on the ICI axis, master trees
        # on the DCN axis (comm/two_level.py); flat meshes keep the single
        # ``ranks`` axis.  XLA-native primitives reduce over all mesh axes.
        from adapcc_tpu.comm.two_level import is_two_level

        self.two_level = is_two_level(mesh)
        if self.two_level:
            self.num_slices, self.ici_size = (int(s) for s in mesh.devices.shape)
            self.axis_name = tuple(mesh.axis_names)
        else:
            self.axis_name = axis_name
        self.use_xla_fastpath = use_xla_fastpath
        #: optional CollectiveTrace recording every dispatch (track.txt analog)
        self.trace = trace
        self._cache: Dict[Tuple, Callable] = {}
        #: world epoch (adapcc_tpu.elastic): bumped by :meth:`advance_epoch`
        #: on every membership change; collectives issued with a stale
        #: ``epoch=`` token raise :class:`EpochMismatch` instead of running
        self.epoch = 0
        # fail fast on a typo'd ADAPCC_COLL_ALGO, same policy as the merge
        # and tuner knobs above
        from adapcc_tpu.comm.latency import resolve_coll_algo

        resolve_coll_algo(None)
        #: lazily computed sim crossover (ring vs recursive doubling) the
        #: `auto` algorithm selector consults; None = not yet computed
        self._algo_crossover: Optional[float] = None
        #: the ScheduleProgram executed by ``algo="ir"`` dispatches; None =
        #: derive from the strategy on first use (docs/COMPILER.md).  An
        #: explicit :meth:`set_schedule_program` pin survives strategy
        #: hot-swaps; a derived program is re-derived after one.
        self._ir_program: Optional[Any] = None
        self._ir_program_explicit = False
        #: program fingerprints already certified by compiler.verify — a
        #: program is verified once, not per compiled shape
        self._ir_verified: set = set()
        #: (base fingerprint, resolved passes) -> optimized program memo
        #: (compiler/optimize.py); keyed by fingerprint so a strategy
        #: hot-swap or re-pin misses naturally instead of needing a flush
        self._ir_optimized: Dict[Tuple, Any] = {}
        #: whether the last strategy-derived IR program came from the
        #: Strategy.schedule_program memo (dispatch-trace extra); None
        #: until something derives
        self._ir_derived_cache_hit: Optional[bool] = None

    # -- elastic plan failover -------------------------------------------------

    def advance_epoch(self, strategy: Optional[Strategy] = None) -> int:
        """World change: bump the epoch and optionally hot-swap the
        executing strategy.

        Compiled programs stay cached under their strategy fingerprint
        (``_schedule_variant``), so swapping to a pre-warmed standby plan
        (:class:`adapcc_tpu.elastic.standby.StandbyPlanCache`) is a
        dispatch-time cache-key switch — no cold recompile stall on the
        failover step.  Unlike :meth:`clear`, nothing is dropped: the old
        epoch's programs remain warm for the recovery swap back.
        """
        if strategy is not None:
            if strategy.world_size != self.world_size:
                raise ValueError(
                    f"standby strategy world {strategy.world_size} != engine "
                    f"world {self.world_size}; elastic swaps keep the mesh "
                    "and mask dead ranks (relay semantics), they do not "
                    "shrink the device set"
                )
            self.strategy = strategy
            # a strategy-derived IR program belongs to the old strategy;
            # re-derive lazily (an explicit set_schedule_program pin stays)
            if not self._ir_program_explicit:
                self._ir_program = None
        self.epoch += 1
        return self.epoch

    def _check_epoch(self, epoch: Optional[int]) -> None:
        if epoch is not None and epoch != self.epoch:
            raise EpochMismatch(epoch, self.epoch)

    def _record(
        self, primitive: str, impl: str, stacked: jnp.ndarray, **extra: Any
    ) -> None:
        if self.trace is not None:
            self.trace.record(
                primitive, impl, int(stacked.nbytes), epoch=self.epoch, **extra
            )

    @property
    def world_size(self) -> int:
        return self.strategy.world_size

    def clear(self) -> None:
        self._cache.clear()
        if self.tuner is not None:
            # dropped programs recompile on next dispatch; the timer must
            # re-discard those first calls or a compile walltime lands in
            # the database as a steady-state sample
            self.tuner.timer.reset()

    def _active_to_mask(self, active_gpus: Optional[Sequence[int]]) -> jnp.ndarray:
        if active_gpus is None:
            return jnp.ones((self.world_size,), dtype=jnp.bool_)
        ranks = list(active_gpus)
        bad = [r for r in ranks if not 0 <= r < self.world_size]
        if bad:
            raise ValueError(f"active ranks {bad} outside world [0, {self.world_size})")
        m = np.zeros((self.world_size,), dtype=bool)
        m[ranks] = True
        return jnp.asarray(m)

    def _check_world_dim(self, stacked: jnp.ndarray, what: str) -> None:
        if stacked.shape[0] != self.world_size:
            raise ValueError(
                f"{what} expects a stacked [world, ...] array with leading dim "
                f"{self.world_size}, got shape {stacked.shape}"
            )

    def _schedule_variant(self) -> Tuple[str, bool]:
        """Cache-key component for schedule-path programs: the strategy
        fingerprint plus whether the trace will take the merged-round path —
        flipping ADAPCC_MERGE_ROUNDS mid-process must miss the cache, not
        replay a program traced under the other setting."""
        if self.two_level:
            from adapcc_tpu.comm.two_level import _two_level_merged_plan

            merged = (
                _two_level_merged_plan(
                    self.strategy, self.num_slices, self.ici_size
                )
                is not None
            )
        else:
            merged = _merged_plan(self.strategy) is not None
        return (self.strategy.fingerprint(), merged)

    def _shard_mapped(self, key: Tuple, per_shard: Callable, n_args: int) -> Callable:
        fn = self._cache.get(key)
        if fn is None:
            specs = (P(self.axis_name),) + (P(),) * (n_args - 1)
            fn = jax.jit(
                jax.shard_map(
                    per_shard,
                    mesh=self.mesh,
                    in_specs=specs,
                    out_specs=P(self.axis_name),
                    # collective results flow through ppermute/RDMA, whose
                    # replication jax cannot infer
                    check_vma=False,
                )
            )
            self._cache[key] = fn
        return fn

    # -- latency plane (adapcc_tpu/comm/latency): size-adaptive algorithm ------

    #: the tuner-grid narrowing a pinned algorithm implies: a dispatch that
    #: can only execute one plane must not offer the others' cells (they
    #: would starve the explorer — the wire-pin collapse, algorithm flavor)
    _ALGO_NARROW = {"ring": ("ring",), "rd": ("rd",), "tree": ("tree",)}

    def _allreduce_crossover_bytes(self) -> float:
        """Sim crossover (ring vs recursive doubling) for this world — the
        analytic half of the ``auto`` selector.  With a tuner attached,
        the TUNER's policy owns the number (it may carry an injected
        custom cost model, and its candidate-grid gate must agree with
        the auto decision on every payload); standalone engines compute
        it from the calibrated α-β model, cached per engine."""
        if self.tuner is not None:
            return self.tuner.policy.algo_crossover_bytes()
        if self._algo_crossover is None:
            from adapcc_tpu.sim.calibrate import load_or_default
            from adapcc_tpu.sim.cost_model import (
                allreduce_crossover_bytes,
                bottleneck_ring_coeffs,
            )

            model = load_or_default(world=self.world_size)
            self._algo_crossover = allreduce_crossover_bytes(
                self.world_size,
                bottleneck_ring_coeffs(model, self.world_size),
            )
        return self._algo_crossover

    def _auto_algo(
        self, per_rank_bytes: int, wire_dtype: Optional[str] = None
    ) -> Optional[str]:
        """The ``auto`` selector's analytic decision: recursive doubling
        for sub-crossover payloads where the latency plane can run, None
        (= stay on the ring plane) otherwise.  Trees never win allreduce
        on the model (full payload every hop), so they are executed only
        by pin or by a measured tuner cell.

        ``auto`` is NOT an explicit rd pin: a pinned wire codec (env or
        the caller's ``wire_dtype`` argument) keeps auto on the
        codec-capable ring planes instead of tripping the loud
        algo-vs-codec conflict guard — that guard exists for two
        *explicit* pins in contradiction."""
        from adapcc_tpu.comm.latency import latency_algo_unsupported_reason

        if self.two_level or self.world_size < 2:
            return None
        if self._wire_pinned_non_off(wire_dtype):
            return None
        if latency_algo_unsupported_reason(
            self.world_size, "rd", self.two_level
        ) is not None:
            return None
        if per_rank_bytes < self._allreduce_crossover_bytes():
            return "rd"
        return None

    def _wire_pinned_non_off(self, wire_dtype: Optional[str]) -> bool:
        """Whether an EXPLICIT wire-codec pin (env or argument — never the
        strategy's synthesized default) resolves to a real codec."""
        import os

        from adapcc_tpu.quant import resolve_wire_dtype
        from adapcc_tpu.quant.codec import WIRE_DTYPE_ENV

        env = os.environ.get(WIRE_DTYPE_ENV)
        if wire_dtype is None and (env is None or not env.strip()):
            return False
        return resolve_wire_dtype(wire_dtype) != "off"

    def _check_algo_wire_conflict(
        self, algo: str, wire_dtype: Optional[str]
    ) -> None:
        """Two explicit pins in conflict reject loudly: the latency plane
        has no wire-codec variants, so a pinned non-"off" codec cannot
        ride a pinned rd/tree dispatch (silently running fp32 under a
        codec label is the lie the fused-wire work eliminated).  Only
        explicit pins conflict — the strategy's synthesized default, the
        auto selector, and the tuner all stand down instead."""
        if self._wire_pinned_non_off(wire_dtype):
            raise ValueError(
                f"collective algo {algo!r} has no wire-codec plane but a "
                "wire_dtype is pinned (env or argument); pin one knob or "
                "the other — codecs ride the ring planes only"
            )

    def _latency_allreduce(
        self,
        stacked: jnp.ndarray,
        algo: str,
        mask: Optional[jnp.ndarray] = None,
        op: ReduceOp = ReduceOp.SUM,
    ) -> Tuple[jnp.ndarray, Tuple, bool]:
        """Dispatch one latency-plane allreduce (``rd`` | ``tree``);
        returns ``(result, cache_key, cache_hit)``.  Rejects loudly where
        the plane cannot run — reachable only via an explicit pin (the
        auto selector and the tuner grid both consult the same support
        funnel first)."""
        from adapcc_tpu.comm import latency as lat

        reason = lat.latency_algo_unsupported_reason(
            self.world_size, algo, self.two_level
        )
        if reason is not None:
            raise ValueError(f"allreduce algo={algo!r} cannot run here: {reason}")
        world = self.world_size
        axis = self.axis_name
        fn = (
            lat.rd_allreduce_shard if algo == "rd" else lat.tree_allreduce_shard
        )
        if mask is None:
            mask = jnp.ones((world,), dtype=jnp.bool_)

        def per_shard(x, m):  # x: [1, *payload]
            return fn(x[0], m, world, axis, op=op)[None]

        key = (f"{algo}_allreduce", stacked.shape, stacked.dtype.name, op)
        cache_hit = key in self._cache
        return self._shard_mapped(key, per_shard, 2)(stacked, mask), key, cache_hit

    # -- IR plane (adapcc_tpu/compiler): the compiled ScheduleProgram executor -

    def _certify_program(self, program) -> None:
        """Verify a ScheduleProgram once per fingerprint (the verifier is
        pure; re-running it per compiled shape would be dispatch noise)."""
        from adapcc_tpu.compiler.verify import verify_program

        fp = program.fingerprint()
        if fp not in self._ir_verified:
            verify_program(program)
            self._ir_verified.add(fp)

    def set_schedule_program(self, program) -> None:
        """Pin the :class:`~adapcc_tpu.compiler.ir.ScheduleProgram` that
        ``algo="ir"`` dispatches execute — the entry point for synthesized
        schedules with no Strategy spelling (docs/COMPILER.md).  The
        program is verified here, before anything compiles; a bad program
        dies at the pin, not at the first traced collective."""
        if program.world != self.world_size:
            raise ValueError(
                f"schedule program {program.name!r} is for world "
                f"{program.world}, engine world is {self.world_size}"
            )
        self._certify_program(program)
        self._ir_program = program
        self._ir_program_explicit = True

    def schedule_program(self):
        """The pre-optimization ScheduleProgram ``algo="ir"`` dispatches
        resolve: the pinned one, else a verified program derived from the
        engine's strategy (memoized in ``Strategy.schedule_program`` —
        whether that memo hit is surfaced in the dispatch-trace extras).
        On a two-level ``(dcn, ici)`` mesh the derived program is the
        composed two-level schedule, the hierarchy the mesh can actually
        execute.  ``sim/replay.simulate_program`` takes this same object —
        pricing and execution share the schedule by construction."""
        if self._ir_program is None:
            if self.two_level:
                from adapcc_tpu.compiler.builders import (
                    two_level_allreduce_program,
                )

                program = two_level_allreduce_program(
                    self.num_slices,
                    self.ici_size,
                    wire_dtype=self.strategy.wire_dtype,
                )
                self._ir_derived_cache_hit = False
            else:
                program = self.strategy.schedule_program()
                self._ir_derived_cache_hit = bool(
                    self.strategy.__dict__.get("_last_program_cache_hit")
                )
            self._certify_program(program)
            self._ir_program = program
        return self._ir_program

    def optimized_schedule_program(self):
        """The post-optimization program ``algo="ir"`` actually lowers:
        :meth:`schedule_program` through the ``compiler/optimize.py`` pass
        pipeline in force (``ADAPCC_IR_OPT``), memoized per (base
        fingerprint, resolved passes).  Every pass verifies pass-in and
        pass-out inside ``optimize_program``, so the result joins the
        certified set; an already-optimal program comes back as the SAME
        object (the passes are identity on it)."""
        from adapcc_tpu.compiler.optimize import (
            optimize_program,
            resolve_ir_opt,
        )

        base = self.schedule_program()
        passes = resolve_ir_opt()
        key = (base.fingerprint(), passes)
        program = self._ir_optimized.get(key)
        if program is None:
            program = optimize_program(base, passes=passes)
            self._ir_verified.add(program.fingerprint())
            self._ir_optimized[key] = program
        return program

    def _ir_allreduce(
        self,
        stacked: jnp.ndarray,
        op: ReduceOp,
        per_rank_bytes: int,
        active_gpus: Optional[Sequence[int]],
    ) -> jnp.ndarray:
        """Dispatch one allreduce through the compiled ScheduleProgram
        executor (``compiler/lower.py``): resolve the program, run the
        optimizer pipeline in force, lower the POST-optimization object —
        flat mesh or native two-level — with the executed program's
        fingerprint, pass list and dispatch count in the trace, and
        record-mode timings under the tuner's ``IR_PATH`` /
        ``IR_OPT_PATH`` cells."""
        from adapcc_tpu.compiler import lower as ir_lower
        from adapcc_tpu.tuner.policy import IR_OPT_PATH, IR_PATH, NO_CHUNK

        if active_gpus is not None:
            raise ValueError(
                "algo='ir' executes the program's own relay masks; "
                "active_gpus subsets are not expressible on this path — "
                "build a program with relays= and set_schedule_program it"
            )
        base = self.schedule_program()
        program = self.optimized_schedule_program()
        # two explicit pins in conflict reject loudly (the rd/tree wire
        # policy): on the IR path the wire codec is a PROGRAM property,
        # so an env/argument pin that disagrees with the program's
        # first-class annotation cannot be honored silently
        if self._wire_pinned_non_off(None):
            from adapcc_tpu.quant import resolve_wire_dtype

            pinned = resolve_wire_dtype(None)
            if pinned != program.wire_dtype:
                raise ValueError(
                    f"algo='ir' program {program.name!r} carries "
                    f"wire_dtype={program.wire_dtype!r} but {pinned!r} is "
                    "pinned; IR wire codecs are program properties — "
                    "rebuild the program with that codec or drop the pin"
                )
        tuner = self.tuner
        key = (
            "ir_allreduce", program.fingerprint(), stacked.shape,
            stacked.dtype.name, op,
        )
        if self.two_level:
            # native hierarchy execution: every color ships over exactly
            # the (dcn | ici) axis its classification names — rejects
            # loudly (naming the round) for programs that do not
            # decompose, BEFORE anything compiles
            dcn_axis, ici_axis = self.axis_name
            ir_lower.two_level_color_axes(
                program, self.num_slices, self.ici_size
            )
            per_shard = ir_lower.allreduce_per_shard_two_level(
                program, self.num_slices, self.ici_size,
                dcn_axis, ici_axis, op,
            )
        else:
            per_shard = ir_lower.allreduce_per_shard(
                program, self.axis_name, op
            )
        cache_hit = key in self._cache
        timing = tuner is not None and tuner.recording
        t0 = time.perf_counter()
        out = self._shard_mapped(key, per_shard, 1)(stacked)
        extras: Dict[str, Any] = {
            "algo": "ir",
            "program": program.name,
            "program_fingerprint": program.fingerprint(),
            "wire_dtype": program.wire_dtype,
            "passes": list(program.applied_passes),
            "dispatches": ir_lower.dispatch_count(program),
        }
        if program is not base:
            extras["base_fingerprint"] = base.fingerprint()
        if not self._ir_program_explicit and (
            self._ir_derived_cache_hit is not None
        ):
            extras["program_cache_hit"] = self._ir_derived_cache_hit
        if self.two_level:
            extras["hier"] = f"{self.num_slices}x{self.ici_size}"
        if timing:
            jax.block_until_ready(out)
            duration = time.perf_counter() - t0
            extras["duration_s"] = duration
            # optimized and naive lowerings are different executables:
            # they live in different tuner cells so measured medians can
            # arbitrate the opt axis (the ADAPCC_IR_OPT A/B)
            path = IR_PATH if program is base else IR_OPT_PATH
            tuner.observe_dispatch(
                tuner.key_for(
                    "allreduce", per_rank_bytes, path, NO_CHUNK,
                    program.wire_dtype,
                ),
                key,
                duration,
            )
        self._record(
            "allreduce", "ir", stacked, cache_hit=cache_hit, **extras
        )
        return out

    def all_reduce(
        self,
        stacked: jnp.ndarray,
        *,
        active_gpus: Optional[Sequence[int]] = None,
        op: ReduceOp = ReduceOp.SUM,
        epoch: Optional[int] = None,
        algo: Optional[str] = None,
    ) -> jnp.ndarray:
        """Allreduce with subset semantics and a size-adaptive algorithm
        selector (docs/LATENCY.md): ``algo`` is one of
        ``auto|ring|rd|tree|ir`` under the precedence **env > explicit arg >
        tuner > sim-crossover** — ``ADAPCC_COLL_ALGO`` wins, then the
        argument, then (for ``auto``/unset with a choosing tuner) a
        measured algorithm cell, then the calibrated crossover decides
        ``auto``.  Unset everywhere keeps the legacy ring/XLA plane.  The
        executed algorithm is recorded in the dispatch trace next to the
        impl, like ``wire_dtype``."""
        # keyword-only for the same reason as reduce_scatter: a positional
        # all_reduce(t, ReduceOp.AVG) must fail at the call site, not bind
        # the enum to active_gpus
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "all_reduce")
        from adapcc_tpu.comm.latency import resolve_coll_algo
        from adapcc_tpu.tuner.policy import ALGO_OF_PATH, NO_CHUNK

        algo_req = resolve_coll_algo(algo)
        per_rank_bytes = (
            int(np.prod(stacked.shape[1:])) * stacked.dtype.itemsize
        )
        if algo_req == "ir":
            return self._ir_allreduce(stacked, op, per_rank_bytes, active_gpus)
        mask = self._active_to_mask(active_gpus)
        tuner = self.tuner
        tplan = None
        executed_algo: Optional[str] = None
        if algo_req in ("rd", "tree"):
            executed_algo = algo_req  # pinned: loud reject if unsupported
        elif (
            algo_req in (None, "auto")
            and not self.two_level
            and tuner is not None
            and tuner.choosing
            # an env-pinned codec collapses the policy's grid to that
            # codec's cells, none of which this plane's {xla, rd, tree}
            # arbitration can offer (all fp32) — stand down like
            # _auto_algo does, instead of dying on an empty grid
            and not self._wire_pinned_non_off(None)
        ):
            # the measured slot of the ladder: rank THE CELLS THIS PLANE
            # CAN RUN — the XLA-plane baseline cell against the rd/tree
            # cells — READ-ONLY (rank_only: no exploration, no incumbent
            # write).  An exploring choose() over the full Pallas grid
            # would pin the explorer on chunk/codec cells whose trial
            # budget can never drain from this entry point, and without
            # the xla cell a measured rd sample would beat every
            # unmeasurable alternative forever.  Only an rd/tree winner
            # reroutes; the xla winner keeps the fastpath below.
            tplan = tuner.rank_only(
                "allreduce", per_rank_bytes, stacked.dtype.name,
                algos=("xla", "rd", "tree"),
            )
            executed_algo = ALGO_OF_PATH.get(tplan.key.path)
        elif algo_req == "auto":
            executed_algo = self._auto_algo(per_rank_bytes)
        if executed_algo is not None:
            self._check_algo_wire_conflict(executed_algo, None)
            timing = tuner is not None and tuner.recording
            t0 = time.perf_counter()
            out, key, cache_hit = self._latency_allreduce(
                stacked, executed_algo, mask, op
            )
            extras: Dict[str, Any] = {"algo": executed_algo}
            if timing:
                jax.block_until_ready(out)
                duration = time.perf_counter() - t0
                extras["duration_s"] = duration
                tuner.observe_dispatch(
                    tuner.key_for(
                        "allreduce", per_rank_bytes, executed_algo,
                        NO_CHUNK, "off",
                    ),
                    key,
                    duration,
                )
            if tplan is not None:
                extras["tuner"] = tplan.trace_extra(
                    applied=tplan.key.path == executed_algo
                )
            self._record(
                "allreduce", executed_algo, stacked,
                cache_hit=cache_hit, **extras,
            )
            return out
        plan2l = None
        if (
            self.two_level
            and op is not ReduceOp.MAX
            # an explicit "ring" pin (env or argument) names the LEGACY
            # ring/psum plane — the composed plan must stand down like
            # every other unpinned selector, or the pin's A/B (e.g. the
            # small_msg_crossover battery arms) silently times the wrong
            # program under the pinned label
            and algo_req in (None, "auto")
        ):
            from adapcc_tpu.strategy.hierarchy import plan_of

            candidate = plan_of(self.strategy)
            # only the RS/AG pod algorithm has a composed data plane; a
            # "replicate" plan IS the projected schedule path below, and
            # MAX has no psum_scatter spelling — both ride the fixed path
            if candidate is not None and candidate.pod_algo == "rs-ag":
                plan2l = candidate
        if plan2l is not None:
            from adapcc_tpu.comm.two_level import (
                allreduce_two_level_composed_shard,
            )

            per_shard = functools.partial(
                allreduce_two_level_composed_shard,
                plan=plan2l,
                num_slices=self.num_slices,
                ici_size=self.ici_size,
                op=op,
            )
            key = (
                "allreduce2l-composed", self.strategy.fingerprint(),
                plan2l.leader_algo, stacked.shape, stacked.dtype.name, op,
            )
            cache_hit = key in self._cache
            timing = tuner is not None and tuner.recording
            t0 = time.perf_counter()
            out = self._shard_mapped(key, per_shard, 2)(stacked, mask)
            extras = {
                "algo": "two-level",
                # the EXECUTED plan is an artifact, not a guess: which
                # levels ran which schedule, on what sketch
                "hier": {
                    "pods": plan2l.sketch.num_pods,
                    "pod_size": plan2l.sketch.pod_size,
                    "pod_algo": plan2l.pod_algo,
                    "leader_algo": plan2l.leader_algo,
                    "resolved_level": plan2l.resolved_level,
                },
            }
            if timing:
                from adapcc_tpu.tuner.policy import TWO_LEVEL_PATH

                jax.block_until_ready(out)
                duration = time.perf_counter() - t0
                extras["duration_s"] = duration
                tuner.observe_dispatch(
                    tuner.key_for(
                        "allreduce", per_rank_bytes, TWO_LEVEL_PATH,
                        NO_CHUNK, "off",
                    ),
                    key,
                    duration,
                )
            self._record(
                "allreduce", "two_level[composed]", stacked,
                cache_hit=cache_hit, **extras,
            )
            return out
        if self.use_xla_fastpath and active_gpus is None:
            per_shard = functools.partial(self._psum_shard, op=op)
            key = ("psum", stacked.shape, stacked.dtype.name, op)
        elif self.two_level:
            from adapcc_tpu.comm.two_level import allreduce_two_level_shard

            per_shard = functools.partial(
                allreduce_two_level_shard,
                strategy=self.strategy,
                num_slices=self.num_slices,
                ici_size=self.ici_size,
                op=op,
            )
            key = ("allreduce2l", self._schedule_variant(), stacked.shape, stacked.dtype.name, op)
        else:
            per_shard = functools.partial(
                allreduce_shard,
                strategy=self.strategy,
                axis_name=self.axis_name,
                op=op,
            )
            key = ("allreduce", self._schedule_variant(), stacked.shape, stacked.dtype.name, op)
        from adapcc_tpu.tuner.policy import XLA_PATH

        is_psum = key[0] == "psum"
        cache_hit = key in self._cache
        # the psum fastpath is the xla cell's measurable arm: record-mode
        # timings close the loop the rank_only arbitration reads
        timing = tuner is not None and tuner.recording and is_psum
        t0 = time.perf_counter()
        out = self._shard_mapped(key, per_shard, 2)(stacked, mask)
        ring_extras: Dict[str, Any] = {"algo": "ring"}
        if timing:
            jax.block_until_ready(out)
            duration = time.perf_counter() - t0
            ring_extras["duration_s"] = duration
            tuner.observe_dispatch(
                tuner.key_for(
                    "allreduce", per_rank_bytes, XLA_PATH, NO_CHUNK, "off"
                ),
                key,
                duration,
            )
        if tplan is not None:
            # applied only when the chosen cell's plane actually ran: the
            # xla cell over the psum fastpath.  A masked/two-level
            # schedule dispatch is NOT that plane, and a chunk/codec cell
            # can never run here — the trace must say so (PR 6's
            # executed-impl honesty).
            ring_extras["tuner"] = tplan.trace_extra(
                applied=tplan.key.path == XLA_PATH and is_psum
            )
        self._record(
            "allreduce", "xla" if is_psum else "schedule", stacked,
            cache_hit=cache_hit, **ring_extras,
        )
        return out

    def _psum_shard(self, x: jnp.ndarray, mask: jnp.ndarray, op: ReduceOp) -> jnp.ndarray:
        return _fused_reduce(x, self.axis_name, op, self.world_size)

    def reduce(
        self,
        stacked: jnp.ndarray,
        *,
        active_gpus: Optional[Sequence[int]] = None,
        op: ReduceOp = ReduceOp.SUM,
        epoch: Optional[int] = None,
    ) -> jnp.ndarray:
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "reduce")
        if self.use_xla_fastpath and active_gpus is None and not self.two_level:
            per_shard = functools.partial(
                reduce_fastpath_shard,
                strategy=self.strategy, axis_name=self.axis_name, op=op,
            )
            key = ("reduce_fast", self.strategy.fingerprint(), stacked.shape, stacked.dtype.name, op)
            self._record("reduce", "xla", stacked, cache_hit=key in self._cache)
            return self._shard_mapped(key, per_shard, 1)(stacked)
        if self.two_level:
            from adapcc_tpu.comm.two_level import reduce_two_level_shard

            per_shard = functools.partial(
                reduce_two_level_shard,
                strategy=self.strategy,
                num_slices=self.num_slices,
                ici_size=self.ici_size,
                op=op,
            )
            key = ("reduce2l", self._schedule_variant(), stacked.shape, stacked.dtype.name, op)
        else:
            per_shard = functools.partial(
                reduce_shard, strategy=self.strategy, axis_name=self.axis_name, op=op
            )
            key = ("reduce", self._schedule_variant(), stacked.shape, stacked.dtype.name, op)
        self._record("reduce", "schedule", stacked, cache_hit=key in self._cache)
        return self._shard_mapped(key, per_shard, 2)(stacked, self._active_to_mask(active_gpus))

    def broadcast(
        self,
        stacked: jnp.ndarray,
        active_gpus: Optional[Sequence[int]] = None,
        *,
        epoch: Optional[int] = None,
    ) -> jnp.ndarray:
        """Broadcast from each tree's root (the reference's ``boardcast``
        context; the typo'd spelling survives as a deprecated alias —
        :meth:`boardcast`).

        ``active_gpus`` mirrors the reference C ABI (run.cu:150 takes the
        active set for every collective).  Broadcast *values* are
        unaffected by relay roles — inactive ranks still forward and
        receive — but the tree roots SOURCE the value, so the active set
        is enforced against them: a stale set naming a dead root rejects
        loudly here instead of silently broadcasting that root's garbage
        (the elastic failover path swaps to a standby plan rooted on an
        alive rank first).  The mask then rides the schedule program as a
        real operand — the same plumbing as :meth:`reduce` — so a masked
        dispatch can never replay the unmasked full-world fastpath."""
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "broadcast")
        mask = self._active_to_mask(active_gpus)
        if active_gpus is not None:
            act = {int(r) for r in active_gpus}
            dead_roots = sorted(
                {t.root for t in self.strategy.trees} - act
            )
            if dead_roots:
                # conservative by design: the engine cannot distinguish a
                # DEAD root (broadcasting its stale row is the silent
                # corruption this guard closes) from a merely demoted-slow
                # one (alive; broadcast values are mask-independent, so
                # including it in the set is always sound).  Callers with
                # the distinction pass alive∪relays for broadcast; the
                # elastic failover path swaps to a re-rooted standby plan.
                raise ValueError(
                    f"broadcast roots {dead_roots} are not in the active set "
                    f"{sorted(act)}: a dead root cannot source the broadcast "
                    "— swap to a degraded plan rooted on alive ranks "
                    "(adapcc_tpu.elastic.standby), or, if the root is only "
                    "demoted-slow, include it in active_gpus (broadcast "
                    "values are unaffected by relay roles)"
                )
        if self.use_xla_fastpath and active_gpus is None and not self.two_level:
            per_shard = functools.partial(
                broadcast_fastpath_shard,
                strategy=self.strategy, axis_name=self.axis_name,
            )
            key = ("broadcast_fast", self.strategy.fingerprint(), stacked.shape, stacked.dtype.name)
            self._record("broadcast", "xla", stacked, cache_hit=key in self._cache)
            return self._shard_mapped(key, per_shard, 1)(stacked)
        masked = active_gpus is not None
        if self.two_level:
            from adapcc_tpu.comm.two_level import broadcast_two_level_shard

            inner = functools.partial(
                broadcast_two_level_shard,
                strategy=self.strategy,
                num_slices=self.num_slices,
                ici_size=self.ici_size,
            )
            key = ("broadcast2l", self._schedule_variant(), stacked.shape, stacked.dtype.name, masked)
        else:
            inner = functools.partial(
                broadcast_shard, strategy=self.strategy, axis_name=self.axis_name
            )
            key = ("broadcast", self._schedule_variant(), stacked.shape, stacked.dtype.name, masked)

        if masked:
            # the mask is a real operand of the compiled program (reduce's
            # plumbing): broadcast values are mask-independent by the relay
            # contract (forwarders still deliver), but the masked dispatch
            # compiles its own keyed program, so a later degraded plan can
            # consume the mask without a silent full-world replay
            def per_shard(x, m):
                return inner(x)
        else:
            per_shard = inner
        self._record("broadcast", "schedule", stacked, cache_hit=key in self._cache)
        if masked:
            return self._shard_mapped(key, per_shard, 2)(stacked, mask)
        return self._shard_mapped(key, per_shard, 1)(stacked)

    def boardcast(
        self,
        stacked: jnp.ndarray,
        active_gpus: Optional[Sequence[int]] = None,
        *,
        epoch: Optional[int] = None,
    ) -> jnp.ndarray:
        """Deprecated: the reference's typo'd spelling of
        :meth:`broadcast` (adapcc.py:55-57, boardcast.cu), kept as an
        alias so reference-shaped callers keep working.  Warns ONCE per
        process — a long training loop must not drown in a warning per
        step — then delegates unchanged."""
        global _BOARDCAST_WARNED
        if not _BOARDCAST_WARNED:
            _BOARDCAST_WARNED = True
            warnings.warn(
                "CollectiveEngine.boardcast (the reference's spelling) is "
                "deprecated; call CollectiveEngine.broadcast instead",
                DeprecationWarning,
                stacklevel=2,
            )
        return self.broadcast(stacked, active_gpus, epoch=epoch)

    # -- primitives the reference only declared (trans.h:27-36 enum stubs) ----
    # implemented here at full adaptive depth: active-subset masking with the
    # same relay contract as all_reduce (inactive ranks contribute identity
    # but stay on the forwarding path and receive results), plus hierarchical
    # DCN×ICI shaping on two-level worlds

    def _my_flat_rank(self):
        """Flat rank inside shard_map, on flat or two-level meshes."""
        if self.two_level:
            dcn_axis, ici_axis = self.axis_name
            return lax.axis_index(dcn_axis) * self.ici_size + lax.axis_index(ici_axis)
        return lax.axis_index(self.axis_name)

    def _latency_variant(
        self, primitive: str, algo: Optional[str]
    ) -> Optional[str]:
        """Resolve the latency-plane algorithm for an RS/AG dispatch
        (docs/LATENCY.md §5): ``ADAPCC_COLL_ALGO`` env > the explicit
        argument, validated against the SAME support funnel the allreduce
        selector and the tuner grid consult — a pinned variant the plane
        cannot run rejects loudly, never a silent fallback to the default
        plane under the pinned label.  ``auto``/``ring``/unset keep the
        legacy XLA/two-level plane (the allreduce crossover is an
        allreduce-shaped decision; these primitives adopt a variant only
        by pin or by the re-ranking loop)."""
        from adapcc_tpu.comm.latency import (
            latency_algo_unsupported_reason,
            resolve_coll_algo,
        )

        algo_req = resolve_coll_algo(algo)
        if algo_req not in ("rd", "tree"):
            return None
        reason = latency_algo_unsupported_reason(
            self.world_size, algo_req, self.two_level, primitive=primitive
        )
        if reason is not None:
            raise ValueError(
                f"{primitive} algo={algo_req!r} cannot run here: {reason}"
            )
        return algo_req

    def all_gather(
        self,
        stacked: jnp.ndarray,
        active_gpus: Optional[Sequence[int]] = None,
        *,
        epoch: Optional[int] = None,
        algo: Optional[str] = None,
    ) -> jnp.ndarray:
        """All-gather with subset semantics (reference stub: trans.h ALLGATHER).

        Input ``[world, *payload]`` (row r = rank r's shard) → output
        ``[world, world, *payload]`` (row r = the full gathered stack as seen
        by rank r).  With ``active_gpus``, inactive ranks contribute zeros
        (the gather identity) but still receive the gathered stack — the
        relay contract of :meth:`all_reduce`.  Two-level worlds gather
        hierarchically (DCN first, so each payload crosses DCN once).

        ``algo="rd"`` (or an ``ADAPCC_COLL_ALGO`` pin) runs the
        recursive-doubling all-gather instead — ``log2(p)`` rounds for
        latency-bound payloads (docs/LATENCY.md §5) — behind the shared
        support funnel (power-of-two flat worlds only, loud reject
        otherwise); the executed algorithm rides the trace like
        ``wire_dtype``.
        """
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "all_gather")
        mask = self._active_to_mask(active_gpus)
        masked = active_gpus is not None
        if self._latency_variant("all_gather", algo) == "rd":
            from adapcc_tpu.comm import latency as lat

            world = self.world_size
            axis = self.axis_name

            def per_shard(x, m):  # x: [1, *payload]
                v = x[0]
                if masked:
                    v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
                return lat.rd_all_gather_shard(v, world, axis)[None]

            key = ("allgather_rd", stacked.shape, stacked.dtype.name, masked)
            self._record(
                "all_gather", "rd", stacked,
                cache_hit=key in self._cache, algo="rd",
            )
            return self._shard_mapped(key, per_shard, 2)(stacked, mask)

        if self.two_level:
            from adapcc_tpu.comm.two_level import all_gather_two_level_shard

            def per_shard(x, m):  # x: [1, *payload]
                v = x[0]
                if masked:
                    v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
                return all_gather_two_level_shard(
                    v, self.num_slices, self.ici_size
                )[None]

            key = ("allgather2l", stacked.shape, stacked.dtype.name, masked)
            self._record(
                "all_gather", "two_level", stacked,
                cache_hit=key in self._cache, algo="ring",
            )
            return self._shard_mapped(key, per_shard, 2)(stacked, mask)

        def per_shard(x, m):  # x: [1, *payload]
            v = x[0]
            if masked:
                v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
            return lax.all_gather(v, self.axis_name, axis=0)[None]

        key = ("allgather", stacked.shape, stacked.dtype.name, masked)
        self._record(
            "all_gather", "xla", stacked,
            cache_hit=key in self._cache, algo="ring",
        )
        return self._shard_mapped(key, per_shard, 2)(stacked, mask)

    def all_to_all(
        self,
        stacked: jnp.ndarray,
        active_gpus: Optional[Sequence[int]] = None,
        *,
        epoch: Optional[int] = None,
    ) -> jnp.ndarray:
        """All-to-all over ICI with subset semantics.

        ``stacked[src, dst]`` blocks are exchanged so each rank ``r`` ends up
        with ``stacked[:, r]`` — the expert-parallel shuffle the reference
        delegates to fastmoe/NCCL (models/moe/train_moe.py, AdapCC.alltoall
        stub adapcc.py:59-61).  Expects ``stacked.shape[1] == world``.  With
        ``active_gpus``, blocks *originating* from inactive ranks are zeroed
        (they contribute identity); every rank, active or not, still receives
        its incoming blocks — inactive ranks stay on the fabric as relays.
        """
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "all_to_all")
        if stacked.shape[1] != self.world_size:
            raise ValueError(
                f"all_to_all needs a [world, world, ...] stacked array, got {stacked.shape}"
            )
        from adapcc_tpu.tuner.policy import A2A_XLA_PATH, NO_CHUNK

        mask = self._active_to_mask(active_gpus)
        masked = active_gpus is not None

        if self.two_level:
            from adapcc_tpu.comm.two_level import all_to_all_two_level_shard

            def per_shard(x, m):  # x: [1, world, *payload]
                v = x[0]
                if masked:
                    v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
                return all_to_all_two_level_shard(
                    v, self.num_slices, self.ici_size
                )[None]

            key = ("alltoall2l", stacked.shape, stacked.dtype.name, masked)
            impl = path = "two_level"
        else:
            def per_shard(x, m):  # x: [1, world, *payload]
                v = x[0]
                if masked:
                    v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
                return lax.all_to_all(v, self.axis_name, split_axis=0, concat_axis=0)[None]

            key = ("alltoall", stacked.shape, stacked.dtype.name, masked)
            impl, path = "xla", A2A_XLA_PATH
        # all_to_all is tuned like every other collective (the primitive
        # the reference left a stub and PR 4 left untimed): with a tuner
        # attached, record|choose time every dispatch into the database
        # under the `all_to_all` primitive — the MoE dispatch/combine
        # traffic (parallel/expert.py via workloads/train_moe.py) lands
        # here at its real payload geometry
        cache_hit = key in self._cache
        tuner = self.tuner
        timing = tuner is not None and tuner.recording
        t0 = time.perf_counter()
        out = self._shard_mapped(key, per_shard, 2)(stacked, mask)
        extras: Dict[str, Any] = {}
        if timing:
            jax.block_until_ready(out)
            duration = time.perf_counter() - t0
            extras["duration_s"] = duration
            # one rank's send volume: its full [world, *payload] row
            per_rank_bytes = (
                int(np.prod(stacked.shape[1:])) * stacked.dtype.itemsize
            )
            tuner.observe_dispatch(
                tuner.key_for(
                    "all_to_all", per_rank_bytes, path, NO_CHUNK, "off"
                ),
                key,
                duration,
            )
        self._record("all_to_all", impl, stacked, cache_hit=cache_hit, **extras)
        return out

    def expert_a2a(self, axis_name: Optional[str] = None) -> Callable:
        """Shard-level MoE token-exchange function for
        :func:`adapcc_tpu.parallel.expert.expert_parallel_moe` — the
        engine-routed spelling of its ``a2a`` override, so expert traffic
        rides the engine's configuration (two-level hierarchy included)
        and is *traced* like every other collective.

        Returns ``a2a(v)`` to be called inside the caller's own shard_map:
        on a flat mesh it is the XLA ``lax.all_to_all`` over ``axis_name``
        (default: the engine's axis), on a two-level ``(dcn, ici)`` mesh
        the hierarchical two-hop exchange.  Each traced application
        records one ``all_to_all`` event (impl suffixed ``[moe]``) into
        the engine's dispatch trace — once per compiled program, the
        traceable boundary when the exchange lives inside a jitted step.
        The tuner database is fed by :meth:`all_to_all` probe dispatches
        at the same payload geometry (workloads/train_moe.py), since an
        in-jit exchange cannot be walltimed individually.
        """
        if self.two_level:
            from adapcc_tpu.comm.two_level import all_to_all_two_level_shard

            inner = functools.partial(
                all_to_all_two_level_shard,
                num_slices=self.num_slices,
                ici_size=self.ici_size,
            )
            impl = "two_level[moe]"
        else:
            name = axis_name if axis_name is not None else self.axis_name
            if name not in self.mesh.axis_names:
                raise ValueError(
                    f"expert_a2a axis {name!r} is not a mesh axis "
                    f"{tuple(self.mesh.axis_names)}"
                )
            inner = functools.partial(
                lax.all_to_all, axis_name=name,
                split_axis=0, concat_axis=0, tiled=False,
            )
            impl = "xla[moe]"

        def a2a(v):
            if self.trace is not None:
                nbytes = int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
                self.trace.record("all_to_all", impl, nbytes, moe=True)
            return inner(v)

        return a2a

    def kv_transfer(
        self,
        pages: Any,
        *,
        src_pod: int,
        dst_pod: int,
        wire_dtype: str = "off",
        block_size: Optional[int] = None,
        chunk_bytes: int = KV_TRANSFER_CHUNK_BYTES,
        dst_sharding: Optional[Any] = None,
        epoch: Optional[int] = None,
    ) -> Any:
        """Point-to-point KV-cache handoff between serving pods — a chunked
        DCN stream as a first-class engine primitive (docs/SERVING.md §7).

        ``pages`` is a pytree of stacked ``[world, ...]`` arrays (one slot's
        per-layer K/V pages in the :class:`~adapcc_tpu.serve.kv_cache
        .SlotKVCache` layout); the return value is the same pytree as it
        arrives on the destination pod.  ``wire_dtype="off"`` (the default)
        is the bit-exact fp32 path — the values are untouched, which is what
        the disaggregated-vs-colocated parity drill pins.  A non-"off" codec
        from the :mod:`adapcc_tpu.quant` registry puts the block-wise
        quantized wire under the stream: the returned pages carry the
        decode(encode(x)) wire values, and admission under a lossy wire is
        gated by the token-level-KL acceptance bound upstream
        (:mod:`adapcc_tpu.serve.disagg` — the engine moves bytes, the router
        owns the acceptance bar).

        Every transfer records ONE dispatch-trace event (``primitive=
        "kv_transfer"``, impl ``dcn_stream[+codec]``) with the executed
        payload bytes, wire dtype, wire bytes, chunk count at
        ``chunk_bytes`` granularity, wall duration, and the (src_pod,
        dst_pod) route — the same honesty contract as every collective.
        ``dst_sharding`` re-places the arrived pages (the destination
        pool's cache sharding); chunking is transport accounting — the
        codec is applied whole-payload so block geometry never depends on
        the stream granularity.
        """
        self._check_epoch(epoch)
        if chunk_bytes < 1:
            raise ValueError(f"chunk_bytes must be >= 1, got {chunk_bytes}")
        from adapcc_tpu.quant import get_codec
        from adapcc_tpu.sim.cost_model import wire_bytes_per_element

        codec = get_codec(wire_dtype)  # loud on an unknown codec name
        from adapcc_tpu.quant.codec import DEFAULT_BLOCK_SIZE

        block = int(block_size) if block_size is not None else DEFAULT_BLOCK_SIZE
        leaves, treedef = jax.tree_util.tree_flatten(pages)
        if not leaves:
            raise ValueError("kv_transfer needs at least one page array")
        for leaf in leaves:
            self._check_world_dim(leaf, "kv_transfer")
        t0 = time.perf_counter()
        nbytes = 0
        wire_bytes = 0.0
        moved = []
        for leaf in leaves:
            nbytes += int(leaf.nbytes)
            if codec.name == "off":
                out = leaf  # identity: the bit-exact default path
                wire_bytes += float(leaf.nbytes)
            else:
                out = codec.apply(leaf, block).astype(leaf.dtype)
                wire_bytes += float(leaf.size) * wire_bytes_per_element(
                    codec.name, block
                )
            if dst_sharding is not None:
                out = jax.device_put(out, dst_sharding)
            moved.append(out)
        jax.block_until_ready(moved)
        duration = time.perf_counter() - t0
        chunks = max(1, -(-int(wire_bytes) // int(chunk_bytes)))
        if self.trace is not None:
            suffix = "" if codec.name == "off" else f"+{codec.name}"
            extras: Dict[str, Any] = {
                "epoch": self.epoch,
                "wire_dtype": codec.name,
                "wire_bytes": int(wire_bytes),
                "chunks": chunks,
                "chunk_bytes": int(chunk_bytes),
                "duration_s": duration,
                "src_pod": int(src_pod),
                "dst_pod": int(dst_pod),
            }
            if codec.name != "off":
                extras["block_size"] = block
            self.trace.record("kv_transfer", f"dcn_stream{suffix}", nbytes, **extras)
        return jax.tree_util.tree_unflatten(treedef, moved)

    def pipe_send(
        self,
        stacked: jnp.ndarray,
        *,
        src: int,
        dst: int,
        kind: str = "activation",
        mb: Optional[int] = None,
        tick: Optional[int] = None,
        epoch: Optional[int] = None,
    ) -> jnp.ndarray:
        """Point-to-point pipeline stage hop over the ICI fabric: move rank
        ``src``'s row of a stacked ``[world, ...]`` buffer to rank ``dst``,
        leaving every other row untouched (docs/PIPELINE.md).

        The single-controller analog of a send/recv pair — one compiled
        ``shard_map`` ppermute per (route, shape, dtype), cached like every
        other engine program.  Each hop records ONE dispatch-trace event
        (``primitive="pipe_send"``, impl ``ici_hop``) with the executed
        payload bytes (one row, not the stacked buffer) and the
        (src, dst) route, plus the schedule coordinates (``kind``
        ``activation``/``grad``/``tied_embed``, microbatch, tick) when the
        executor provides them — the stage-hop analog of the
        :meth:`kv_transfer` honesty contract.
        """
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "pipe_send")
        w = self.world_size
        for label, r in (("src", src), ("dst", dst)):
            if not 0 <= r < w:
                raise ValueError(
                    f"pipe_send {label}={r} outside world [0, {w})"
                )
        if src == dst:
            raise ValueError(f"pipe_send src == dst == {src}: nothing to move")
        if kind not in ("activation", "grad", "tied_embed"):
            raise ValueError(
                f"pipe_send kind={kind!r}: expected 'activation', 'grad' or "
                "'tied_embed'"
            )
        axis = self.axis_name

        def per_shard(x: jnp.ndarray) -> jnp.ndarray:
            me = lax.axis_index(axis)
            moved = lax.ppermute(x, axis, perm=[(src, dst)])
            return jnp.where(me == dst, moved, x)

        fn = self._shard_mapped(
            ("pipe_send", src, dst, stacked.shape, stacked.dtype.name),
            per_shard,
            1,
        )
        out = fn(stacked)
        if self.trace is not None:
            extras: Dict[str, Any] = {
                "epoch": self.epoch,
                "src": int(src),
                "dst": int(dst),
                "kind": kind,
            }
            if mb is not None:
                extras["mb"] = int(mb)
            if tick is not None:
                extras["tick"] = int(tick)
            self.trace.record(
                "pipe_send", "ici_hop", int(stacked.nbytes) // w, **extras
            )
        return out

    def pipe_recv(
        self,
        stacked: jnp.ndarray,
        *,
        src: int,
        dst: int,
        **kwargs: Any,
    ) -> jnp.ndarray:
        """Destination-side spelling of the stage hop.  In the
        single-controller engine one dispatch is both halves of a
        send/recv pair, so this forwards to :meth:`pipe_send` — calling
        either records exactly one trace event for the hop."""
        return self.pipe_send(stacked, src=src, dst=dst, **kwargs)

    def _ring_plan(
        self,
        stacked: jnp.ndarray,
        chunk_bytes: Optional[int],
        rs: bool,
        ag: bool,
        wire_dtype: str = "off",
        block_size: Optional[int] = None,
    ):
        """The executed ring schedule for a stacked call: the synthesized
        ``Strategy.chunk_bytes`` is the default granularity, an explicit
        argument overrides it, and the ``ADAPCC_RING_CHUNK_BYTES`` sweep env
        (resolved inside the planner) overrides both.  The plan decides the
        VMEM vs HBM-streaming path (and the fused wire geometry when a
        codec is on) and is recorded into the dispatch trace — the chunk
        size and wire dtype a ring collective ran at are an artifact, not
        a guess."""
        from adapcc_tpu.comm.pallas_ring import plan_ring_schedule

        per_rank = int(np.prod(stacked.shape[1:]))
        # allreduce / reduce-scatter shards carry the full payload per rank;
        # a pure all-gather's shard is one chunk of a world × chunk payload
        nelems = per_rank if rs else per_rank * self.world_size
        return plan_ring_schedule(
            nelems,
            stacked.dtype,
            self.world_size,
            chunk_bytes if chunk_bytes is not None else self.strategy.chunk_bytes,
            rs=rs,
            ag=ag,
            wire_dtype=wire_dtype,
            block_size=block_size,
        )

    @staticmethod
    def _ring_extras(plan, interpret) -> Dict[str, Any]:
        """Trace payload for a Pallas-ring dispatch — ONE definition shared
        by allreduce/RS/AG so the three primitives' artifacts cannot
        drift.  ``wire_dtype`` is the EXECUTED codec (from the plan), never
        a hard-coded constant; ``wire_bytes`` is what the per-rank payload
        actually costs on the fabric under it."""
        extras = {
            "chunk_bytes": plan.chunk_bytes,
            "stage_bytes": plan.stage_bytes,
            "n_tiles": plan.n_tiles,
            "wire_dtype": plan.wire_dtype,
            # Mosaic kernel (False) or the Pallas interpreter (True): what
            # ops/kernel_mode.resolve_interpret decided for this dispatch
            "interpret": bool(interpret),
        }
        if plan.wire_dtype == "off":
            extras["wire_bytes"] = plan.payload_bytes
        else:
            from adapcc_tpu.sim.cost_model import wire_bytes_per_element

            extras["wire_bytes"] = int(
                (plan.payload_bytes / 4.0)
                * wire_bytes_per_element(plan.wire_dtype, plan.block_size or 1)
            )
            extras["block_size"] = plan.block_size
            extras["scale_slot_bytes"] = plan.scale_slot_bytes
            extras["fused"] = True
        return extras

    @staticmethod
    def _finish_interpreted(out, interpret):
        """An interpreted Pallas kernel runs as host callbacks that execute
        JAX ops of their own; left in flight beside the caller's next
        dispatches they can deadlock jax's CPU client (seen under the
        six-worker test run: eight callback threads parked in the
        interpreter's ``store`` while the main thread's next eager op never
        returns).  The interpreter is never a fast path, so an interpreted
        dispatch completes before it returns; a Mosaic dispatch stays async."""
        return jax.block_until_ready(out) if interpret else out

    def _record_ring(self, primitive: str, plan, stacked: jnp.ndarray, interpret) -> None:
        if self.trace is not None:
            suffix = "" if plan.wire_dtype == "off" else f"+{plan.wire_dtype}"
            self.trace.record(
                primitive,
                f"pallas_ring[{plan.path}{suffix}]",
                int(stacked.nbytes),
                **self._ring_extras(plan, interpret),
            )

    def _resolved_wire_dtype(self, wire_dtype: Optional[str]) -> str:
        """The wire codec a ring dispatch runs: ADAPCC_WIRE_DTYPE override >
        explicit argument > the strategy's synthesized ``wire_dtype`` — the
        same precedence ladder as the ring chunk size."""
        from adapcc_tpu.quant import resolve_wire_dtype

        return resolve_wire_dtype(
            wire_dtype if wire_dtype is not None else self.strategy.wire_dtype
        )

    def _wire_ring_allreduce(
        self, stacked: jnp.ndarray, wire_dtype: str, block_size: int
    ) -> Tuple[jnp.ndarray, Tuple, Dict[str, Any]]:
        """Ring allreduce over codec-compressed chunks (the EQuARX shape):
        reduce-scatter dequant-accumulate-requants at every hop, all-gather
        ships each reduced chunk's encoded blocks once.  ppermute-based —
        any backend, no Pallas requirement.  Returns ``(result, cache_key,
        trace_extras)`` so :meth:`ring_allreduce` can fold tuner timing and
        provenance into one trace record."""
        from adapcc_tpu.quant import get_codec, wire_ring_allreduce_shard
        from adapcc_tpu.sim.cost_model import wire_bytes_per_element

        codec = get_codec(wire_dtype)  # fail before tracing, not inside
        world = self.world_size

        def per_shard(x):  # x: [1, *payload]
            return wire_ring_allreduce_shard(
                x[0], world, self.axis_name,
                wire_dtype=codec.name, block_size=block_size,
            )[None]

        key = (
            "quant_ring_allreduce", stacked.shape, stacked.dtype.name,
            codec.name, block_size,
        )
        per_rank = int(np.prod(stacked.shape[1:]))
        extras = {
            "wire_dtype": codec.name,
            "block_size": block_size,
            "wire_bytes": int(
                per_rank * wire_bytes_per_element(codec.name, block_size)
            ),
        }
        return self._shard_mapped(key, per_shard, 1)(stacked), key, extras

    def ring_allreduce(
        self,
        stacked: jnp.ndarray,
        interpret: Optional[bool] = None,
        chunk_bytes: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        quant_block_size: Optional[int] = None,
        algo: Optional[str] = None,
    ) -> jnp.ndarray:
        """Pallas ICI ring allreduce (hand-tuned data plane; see
        :mod:`adapcc_tpu.comm.pallas_ring`).  ``interpret=None`` auto-selects
        the interpreter off-TPU so the same call works on the virtual pod.
        ``chunk_bytes=None`` uses the strategy's synthesized granularity.

        ``wire_dtype=None`` adopts the strategy's synthesized codec
        (``ADAPCC_WIRE_DTYPE`` overrides both): a non-"off" codec reroutes
        to the quantized ppermute ring (:meth:`_wire_ring_allreduce`) —
        compressed chunks on the wire, fp32 accumulation at every hop.

        With a tuner attached (:mod:`adapcc_tpu.tuner`), ``ADAPCC_TUNER=
        choose`` lets the measured policy fill the knobs the caller left
        open — precedence **env > explicit arg > tuner > strategy** — and
        ``record``/``choose`` time every dispatch (``block_until_ready``
        walltime, compile warmup discarded) into the tuning database.  The
        dispatch trace carries the decision (``tuner={chosen, source,
        applied}``) next to the executed values, so precedence is visible
        in the artifact."""
        from adapcc_tpu.comm.pallas_ring import ring_allreduce_shard

        if self.two_level:
            raise ValueError(
                "ring_allreduce needs a flat ranks mesh (a single ICI ring); "
                "two-level worlds use the strategy allreduce"
            )
        self._check_world_dim(stacked, "ring_allreduce")
        # the single source of the key vocabulary: candidates(), live
        # recording, and trace replay must all spell one cell identically
        from adapcc_tpu.comm.latency import resolve_coll_algo
        from adapcc_tpu.tuner.policy import ALGO_OF_PATH, ALGO_PATHS, NO_CHUNK, QUANT_PATH

        # algorithm selector (docs/LATENCY.md): env > arg > tuner cell >
        # sim-crossover (under "auto"); unset everywhere keeps the ring —
        # the legacy contract of this entry point
        algo_req = resolve_coll_algo(algo)
        wire_arg = wire_dtype  # the caller's pin, before tuner adoption
        if algo_req == "ir":
            # the IR pin owns every allreduce entry point: rerouting here
            # (not silently running the ring under the pinned label) is
            # the same honesty rule as the rd/tree pins.  Ring-plane
            # knobs have no IR meaning — the program carries its own
            # chunking and codec — so explicit ones conflict loudly.
            if wire_arg is not None or chunk_bytes is not None:
                raise ValueError(
                    "algo='ir' executes a ScheduleProgram whose chunking "
                    "and wire codec are program properties; drop the "
                    "chunk_bytes/wire_dtype arguments or the ir pin"
                )
            per_rank_bytes = (
                int(np.prod(stacked.shape[1:])) * stacked.dtype.itemsize
            )
            return self._ir_allreduce(stacked, ReduceOp.SUM, per_rank_bytes, None)
        if algo_req in ("rd", "tree"):
            # double-pin conflict BEFORE the tuner consult: under both
            # pins the candidate grid is legitimately empty (neither the
            # ring planes nor the algo cells may be offered), and choose()
            # would die with a misleading "no candidate cells" — the
            # purpose-built diagnostic must fire first
            self._check_algo_wire_conflict(algo_req, wire_arg)
        per_rank_bytes = int(np.prod(stacked.shape[1:])) * stacked.dtype.itemsize
        tuner = self.tuner
        tplan = None
        tuner_chose_quant = False
        tuner_chose_algo: Optional[str] = None
        algos_narrow = self._ALGO_NARROW.get(algo_req)
        if algos_narrow is None and self._wire_pinned_non_off(wire_arg):
            # a caller-pinned codec rides the ring planes only: narrow the
            # algorithm axis so the explorer never offers a cell the
            # conflict guard would refuse on execution (the wire-pin
            # collapse, engine side; the env pin is collapsed inside
            # candidates() already — this covers the explicit argument)
            algos_narrow = ("ring",)
        if tuner is not None and tuner.choosing:
            tplan = tuner.choose(
                "allreduce", per_rank_bytes, stacked.dtype.name,
                algos=algos_narrow,
            )
            if algo_req in (None, "auto") and tplan.key.path in ALGO_PATHS:
                tuner_chose_algo = ALGO_OF_PATH[tplan.key.path]
            # the tuner only fills knobs the caller left open; the env
            # overrides (resolved inside resolve_chunk_bytes /
            # resolve_wire_dtype) still win over everything
            if wire_dtype is None and tplan.key.path not in ALGO_PATHS:
                wire_dtype = tplan.wire_dtype
                # a codec cell names its PATH too: the unfused quant-ring
                # cell must actually run unfused, or the fused-vs-unfused
                # A/B can never measure its second arm
                tuner_chose_quant = (
                    tplan.wire_dtype != "off" and tplan.key.path == QUANT_PATH
                )
            if chunk_bytes is None and tplan.chunk_bytes is not None:
                chunk_bytes = tplan.chunk_bytes
        executed_algo: Optional[str] = None
        if algo_req in ("rd", "tree"):
            executed_algo = algo_req  # pinned: loud reject if unsupported
        elif tuner_chose_algo is not None:
            executed_algo = tuner_chose_algo
        elif algo_req == "auto" and tplan is None:
            # the sim crossover is the LAST rung of the ladder: a choosing
            # tuner's committed cell — ring-plane included — outranks it
            # (tplan carries the decision above; overriding a committed
            # ring cell here would discard its adopted chunk/codec knobs
            # and starve the cells the tuner is trying to measure)
            executed_algo = self._auto_algo(per_rank_bytes, wire_arg)
        timing = tuner is not None and tuner.recording
        t0 = time.perf_counter()
        if executed_algo is not None:
            self._check_algo_wire_conflict(executed_algo, wire_arg)
            out, cache_key, _ = self._latency_allreduce(stacked, executed_algo)
            impl = executed_algo
            executed_path, executed_chunk = executed_algo, NO_CHUNK
            extras = {"algo": executed_algo}
            wd = "off"
        elif (wd := self._resolved_wire_dtype(wire_dtype)) != "off":
            from adapcc_tpu.comm.pallas_ring import (
                fused_ring_dispatch_reason,
                note_quant_reroute,
                resolve_fused_wire,
            )
            from adapcc_tpu.quant import DEFAULT_BLOCK_SIZE

            block = quant_block_size or DEFAULT_BLOCK_SIZE
            reroute = fused_ring_dispatch_reason(stacked.dtype, wd, block)
            # ADAPCC_FUSED_WIRE=on outranks the tuner's path cell: "on"
            # means NOTHING runs unfused here, tuner exploration included
            chosen_reroute = (
                reroute is None
                and tuner_chose_quant
                and resolve_fused_wire() != "on"
            )
            if chosen_reroute:
                reroute = "tuner chose the unfused quant-ring cell"
            if reroute is None:
                # the fused path: codec inside the staged Pallas kernels —
                # compressed tiles on the wire, fp32 accumulation in VMEM
                interpret = resolve_interpret(interpret, "ring_allreduce")
                world = self.world_size
                plan = self._ring_plan(
                    stacked, chunk_bytes, rs=True, ag=True,
                    wire_dtype=wd, block_size=block,
                )

                def per_shard(x):  # x: [1, *payload]
                    return ring_allreduce_shard(
                        x[0], world, self.axis_name, interpret=interpret,
                        chunk_bytes=plan.chunk_bytes,
                        wire_dtype=wd, block_size=block,
                    )[None]

                cache_key = (
                    "ring_allreduce", stacked.shape, stacked.dtype.name,
                    bool(interpret), plan.path, plan.stage_bytes, wd, block,
                )
                out = self._finish_interpreted(
                    self._shard_mapped(cache_key, per_shard, 1)(stacked), interpret
                )
                impl = f"pallas_ring[{plan.path}+{wd}]"
                executed_path, executed_chunk = plan.path, plan.chunk_bytes
                extras = self._ring_extras(plan, interpret)
            else:
                # the staged kernel was abandoned for this dispatch — say so
                # once, loudly, and record the executed impl honestly (a
                # tuner-chosen unfused cell is a deliberate A/B arm, not an
                # abandonment — no note for it)
                if not chosen_reroute:
                    note_quant_reroute(wd, reroute)
                out, cache_key, extras = self._wire_ring_allreduce(
                    stacked, wd, block
                )
                extras["reroute_reason"] = reroute
                impl = f"quant_ring[{wd}]"
                executed_path, executed_chunk = QUANT_PATH, NO_CHUNK
        else:
            interpret = resolve_interpret(interpret, "ring_allreduce")
            world = self.world_size
            plan = self._ring_plan(stacked, chunk_bytes, rs=True, ag=True)

            def per_shard(x):  # x: [1, *payload]
                return ring_allreduce_shard(
                    x[0], world, self.axis_name, interpret=interpret,
                    chunk_bytes=plan.chunk_bytes,
                )[None]

            cache_key = (
                "ring_allreduce", stacked.shape, stacked.dtype.name,
                bool(interpret), plan.path, plan.stage_bytes,
            )
            out = self._finish_interpreted(
                self._shard_mapped(cache_key, per_shard, 1)(stacked), interpret
            )
            impl = f"pallas_ring[{plan.path}]"
            executed_path, executed_chunk = plan.path, plan.chunk_bytes
            extras = self._ring_extras(plan, interpret)
        # the executed ALGORITHM rides the trace like wire_dtype: every
        # ring-family branch above is "ring", the latency branch stamped
        # its own name
        extras.setdefault("algo", "ring")
        if timing:
            # measurement semantics: the sample is the full dispatch-to-
            # completion walltime.  The block serializes the host loop by
            # design — that is what "record" mode buys its database with
            jax.block_until_ready(out)
            duration = time.perf_counter() - t0
            extras["duration_s"] = duration
            tuner.observe_dispatch(
                tuner.key_for(
                    "allreduce", per_rank_bytes, executed_path,
                    # a vmem dispatch is ONE cell regardless of budget (the
                    # knob is inert there); keying by the resolved budget
                    # would split its samples away from the candidate grid
                    NO_CHUNK if executed_path == "vmem" else executed_chunk,
                    wd,
                ),
                cache_key,
                duration,
            )
        if tplan is not None:
            applied = (
                wd == tplan.wire_dtype
                and executed_path == tplan.key.path
                and (
                    tplan.chunk_bytes is None
                    or executed_chunk == tplan.chunk_bytes
                )
            )
            extras["tuner"] = tplan.trace_extra(applied=applied)
        if self.trace is not None:
            self.trace.record("allreduce", impl, int(stacked.nbytes), **extras)
        return out

    def _ring_wire_args(
        self, stacked: jnp.ndarray, wire_dtype: Optional[str],
        quant_block_size: Optional[int], primitive: str,
    ) -> Tuple[str, Optional[int]]:
        """Resolve the wire codec for a ring RS/AG dispatch and validate it
        against the fused kernels — the ONLY data plane those primitives
        have for a codec, so an unsupported combination rejects loudly
        instead of silently running fp32 under a codec label."""
        wd = self._resolved_wire_dtype(wire_dtype)
        if wd == "off":
            return wd, None
        from adapcc_tpu.comm.pallas_ring import fused_ring_dispatch_reason
        from adapcc_tpu.quant import DEFAULT_BLOCK_SIZE

        block = quant_block_size or DEFAULT_BLOCK_SIZE
        reason = fused_ring_dispatch_reason(stacked.dtype, wd, block)
        if reason is not None:
            raise ValueError(
                f"{primitive} has no unfused wire data plane "
                f"(quant/ring.py is allreduce-only): wire_dtype={wd!r} "
                f"cannot run here — {reason}.  Pin wire_dtype='off' (or "
                "ADAPCC_WIRE_DTYPE=off) to run the fp32 kernels."
            )
        return wd, block

    def ring_reduce_scatter(
        self,
        stacked: jnp.ndarray,
        interpret: Optional[bool] = None,
        chunk_bytes: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        quant_block_size: Optional[int] = None,
    ) -> jnp.ndarray:
        """Pallas ICI ring reduce-scatter (the RS half of the hand-tuned ring,
        :func:`adapcc_tpu.comm.pallas_ring.ring_reduce_scatter_shard`).

        Input ``[world, n]`` → output ``[world, chunk]`` with row ``r`` = the
        fully reduced chunk ``r`` of the flattened, tile-padded input
        (``chunk = tile_round(ceil(n / world))``).  The kernel leaves chunk
        ``(r+1) % world`` on rank ``r``; one static roll restores chunk order
        in the stacked single-controller view so this matches
        :meth:`reduce_scatter`'s row semantics on tile-aligned payloads.

        ``wire_dtype`` (default: the strategy's synthesized codec, under
        the usual env > arg > strategy precedence) runs the fused codec
        kernels: hops ship encoded tiles, accumulation stays fp32.  There
        is no unfused RS codec plane — where the fused path can't run, the
        dispatch rejects loudly rather than silently running fp32.
        """
        from adapcc_tpu.comm.pallas_ring import ring_reduce_scatter_shard

        if self.two_level:
            raise ValueError(
                "ring_reduce_scatter needs a flat ranks mesh (a single ICI "
                "ring); two-level worlds use the strategy primitives"
            )
        self._check_world_dim(stacked, "ring_reduce_scatter")
        wd, block = self._ring_wire_args(
            stacked, wire_dtype, quant_block_size, "ring_reduce_scatter"
        )
        interpret = resolve_interpret(interpret, "ring_reduce_scatter")
        world = self.world_size
        plan = self._ring_plan(
            stacked, chunk_bytes, rs=True, ag=False,
            wire_dtype=wd, block_size=block,
        )

        def per_shard(x):  # x: [1, *payload]
            out = ring_reduce_scatter_shard(
                x[0], world, self.axis_name, interpret=interpret,
                chunk_bytes=plan.chunk_bytes,
                wire_dtype=wd, block_size=block,
            )
            # relabel to chunk order INSIDE the compiled program: the kernel
            # leaves rank r holding chunk (r+1) % world; one [chunk]-sized
            # ppermute hop lands chunk r on rank r (an eager host-side roll
            # would dispatch a second, uncached cross-device permute per call)
            out = lax.ppermute(
                out, self.axis_name, [(i, (i + 1) % world) for i in range(world)]
            )
            return out[None]

        key = (
            "ring_rs", stacked.shape, stacked.dtype.name, bool(interpret),
            plan.path, plan.stage_bytes, wd, block,
        )
        self._record_ring("reduce_scatter", plan, stacked, interpret)
        return self._finish_interpreted(
            self._shard_mapped(key, per_shard, 1)(stacked), interpret
        )

    def ring_all_gather(
        self,
        stacked: jnp.ndarray,
        interpret: Optional[bool] = None,
        chunk_bytes: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        quant_block_size: Optional[int] = None,
    ) -> jnp.ndarray:
        """Pallas ICI ring all-gather (the AG half of the hand-tuned ring).

        Input ``[world, chunk]`` (row ``r`` = rank ``r``'s tile-aligned
        payload) → output ``[world, world, chunk]`` — row ``r`` is the full
        gathered stack as seen by rank ``r``, matching :meth:`all_gather`.

        ``wire_dtype`` runs the fused codec kernels: each rank's chunk is
        encoded ONCE and the encoded bits are forwarded verbatim, so every
        rank holds identical post-codec values.  No unfused AG codec plane
        exists — unsupported combinations reject loudly.
        """
        from adapcc_tpu.comm.pallas_ring import ring_all_gather_shard

        if self.two_level:
            raise ValueError(
                "ring_all_gather needs a flat ranks mesh (a single ICI "
                "ring); two-level worlds use the strategy primitives"
            )
        self._check_world_dim(stacked, "ring_all_gather")
        wd, block = self._ring_wire_args(
            stacked, wire_dtype, quant_block_size, "ring_all_gather"
        )
        interpret = resolve_interpret(interpret, "ring_all_gather")
        world = self.world_size
        plan = self._ring_plan(
            stacked, chunk_bytes, rs=False, ag=True,
            wire_dtype=wd, block_size=block,
        )

        def per_shard(x):  # x: [1, chunk]
            return ring_all_gather_shard(
                x[0], world, self.axis_name, interpret=interpret,
                chunk_bytes=plan.chunk_bytes,
                wire_dtype=wd, block_size=block,
            )[None]

        key = (
            "ring_ag", stacked.shape, stacked.dtype.name, bool(interpret),
            plan.path, plan.stage_bytes, wd, block,
        )
        self._record_ring("all_gather", plan, stacked, interpret)
        return self._finish_interpreted(
            self._shard_mapped(key, per_shard, 1)(stacked), interpret
        )

    def reduce_scatter(
        self,
        stacked: jnp.ndarray,
        *,
        active_gpus: Optional[Sequence[int]] = None,
        op: ReduceOp = ReduceOp.SUM,
        epoch: Optional[int] = None,
        algo: Optional[str] = None,
    ) -> jnp.ndarray:
        """Reduce-scatter with subset semantics (reference stub: REDUCESCATTER).

        ``active_gpus``/``op`` are keyword-only: a positional
        ``reduce_scatter(t, ReduceOp.AVG)`` predates the active_gpus
        parameter and must fail loudly rather than bind the enum to the
        mask (ADVICE r5).

        Row ``r`` of the result is the reduction of everyone's ``r``-th
        world-slice: input ``[world, n]`` → output ``[world, n // world]``.
        With ``active_gpus``, inactive ranks contribute the reduction
        identity but still receive their chunk (the relay contract);
        ``ReduceOp.AVG`` averages over the *active* count.  Two-level worlds
        scatter hierarchically (ICI first, so DCN carries only ``1/ici`` of
        the buffer).

        ``algo="rd"`` (or an ``ADAPCC_COLL_ALGO`` pin) runs the
        recursive-halving reduce-scatter instead — ``log2(p)`` rounds for
        latency-bound payloads (docs/LATENCY.md §5) — behind the shared
        support funnel (power-of-two flat worlds only, loud reject
        otherwise); the executed algorithm rides the trace like
        ``wire_dtype``.
        """
        self._check_epoch(epoch)
        self._check_world_dim(stacked, "reduce_scatter")
        if op is ReduceOp.MAX:
            raise ValueError(
                "reduce_scatter supports SUM/AVG (psum_scatter has no max "
                "variant); use reduce + a local slice for MAX"
            )
        n = int(np.prod(stacked.shape[1:]))
        if n % self.world_size:
            raise ValueError(
                f"reduce_scatter payload ({n} elems) must divide the world "
                f"({self.world_size})"
            )
        mask = self._active_to_mask(active_gpus)
        masked = active_gpus is not None

        if self._latency_variant("reduce_scatter", algo) == "rd":
            from adapcc_tpu.comm import latency as lat

            world = self.world_size
            axis = self.axis_name

            def per_shard(x, m):  # x: [1, n]
                out = lat.rd_reduce_scatter_shard(
                    x.reshape(-1), m if masked else None, world, axis, op=op
                )
                return out[None, :]

            key = (
                "reducescatter_rd", stacked.shape, stacked.dtype.name, op,
                masked,
            )
            self._record(
                "reduce_scatter", "rd", stacked,
                cache_hit=key in self._cache, algo="rd",
            )
            return self._shard_mapped(key, per_shard, 2)(stacked, mask)

        def _contrib(v, m):
            if masked:
                v = jnp.where(m[self._my_flat_rank()], v, jnp.zeros_like(v))
            return v

        def _norm(out, m):
            if op is ReduceOp.AVG:
                denom = (
                    jnp.maximum(jnp.sum(m.astype(out.dtype)), 1)
                    if masked else self.world_size
                )
                out = out / denom
            return out

        if self.two_level:
            from adapcc_tpu.comm.two_level import reduce_scatter_two_level_shard

            def per_shard(x, m):  # x: [1, n]
                v = _contrib(x.reshape(-1), m)
                out = reduce_scatter_two_level_shard(
                    v, self.num_slices, self.ici_size
                )
                return _norm(out, m)[None, :]

            key = ("reducescatter2l", stacked.shape, stacked.dtype.name, op, masked)
            self._record(
                "reduce_scatter", "two_level", stacked,
                cache_hit=key in self._cache, algo="ring",
            )
            return self._shard_mapped(key, per_shard, 2)(stacked, mask)

        def per_shard(x, m):  # x: [1, n]
            v = _contrib(x.reshape(-1), m)
            out = lax.psum_scatter(v, self.axis_name, scatter_dimension=0, tiled=True)
            return _norm(out, m)[None, :]

        key = ("reducescatter", stacked.shape, stacked.dtype.name, op, masked)
        self._record(
            "reduce_scatter", "xla", stacked,
            cache_hit=key in self._cache, algo="ring",
        )
        return self._shard_mapped(key, per_shard, 2)(stacked, mask)
