"""Gradient-sync hook: the TPU analog of ``cuda_allreduce_hook``.

The reference registers a torch-DDP comm hook that, per gradient bucket,
negotiates the step's active set with the coordinator, sizes chunks, and
either runs the adaptive allreduce (active rank), skips it (BSP straggler),
or hands the bucket to an async relay replay (commu.py:385-435, SURVEY §3.3).

Under XLA the data plane must be one compiled program, so the hook splits
into the two halves the reference interleaves:

- **host half** (:meth:`GradSyncHook.negotiate`): once per step, before the
  jitted train step — talk to the coordinator (hook_fetch + update_relay)
  and produce the ``[world]`` active mask.  Runs in microseconds, off the
  device critical path (the reference pays the same ~1 ms gRPC cost,
  proto/latency_0.0.txt).

- **device half** (:meth:`GradSyncHook.sync`): inside the jitted step —
  bucket the gradient pytree, run the strategy allreduce per bucket with the
  active mask, scatter back.  AVG semantics over the active count, matching
  DDP gradient averaging.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax.numpy as jnp
import numpy as np

from adapcc_tpu.comm.engine import allreduce_shard, masked_psum_shard
from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.ddp.bucketing import (
    BucketPlan,
    build_bucket_plan,
    flatten_to_buckets,
    unflatten_from_buckets,
)
from adapcc_tpu.primitives import ReduceOp
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.observability import default_registry


class GradSyncHook:
    def __init__(
        self,
        strategy: Strategy,
        axis_name: str = RANKS_AXIS,
        op: ReduceOp = ReduceOp.AVG,
        bucket_cap_mb: float = 100.0,
        use_xla_fastpath: bool = True,
        communicator: Optional[Any] = None,
        mode: str = "auto",
        compress: str = "off",
        error_feedback: bool = False,
        quant_block_size: int = 256,
        overlap: str = "off",
        trace: Optional[Any] = None,
        metrics: Optional[Any] = None,
    ) -> None:
        """``mode``: ``"psum"`` = per-leaf masked psum (one XLA collective per
        leaf — no bucketing copies, optimal on a flat ICI mesh and still
        honoring subset semantics); ``"schedule"`` = bucketed strategy-tree
        allreduce (the adaptive path for hierarchical topologies);
        ``"auto"`` = psum when fastpath is allowed and the strategy spans a
        single host group, schedule otherwise.

        ``compress`` names a wire codec from the quant registry
        (:mod:`adapcc_tpu.quant` — ``"off" | "bf16" | "int8"`` plus anything
        registered later), or ``"strategy"`` to adopt the synthesized
        ``Strategy.wire_dtype``; ``ADAPCC_WIRE_DTYPE`` overrides either.
        ``"bf16"`` casts gradients to bfloat16 for the wire (halving ICI/DCN
        bytes, the torch-DDP ``bf16_compress_hook`` analog) and back
        afterwards — accumulation then happens in bf16, adding ~bf16-eps
        relative error to the synced mean.  ``"int8"`` gives every
        contribution its block-wise quantized wire *value* (per-block fp32
        scales over ``quant_block_size`` elements, deterministic rounding)
        before the fp32 collective — the XLA-plane realization of the
        quantized allreduce (the ring engine moves actual int8 bytes; see
        docs/QUANT.md).  ``"off"`` keeps the gradient dtype end to end.

        ``error_feedback``: carry each rank's quantization error in a
        residual buffer folded into the next step's gradient (the
        :func:`adapcc_tpu.quant.error_feedback_step` loop) — drive it via
        :meth:`sync_error_feedback`; the trainer threads the buffer.

        ``overlap`` selects the sync schedule (docs/OVERLAP.md; resolved at
        construction, ``ADAPCC_OVERLAP`` overriding): ``"bucket"`` forces
        the bucketed path on either data plane and dispatches every bucket
        as independent chunked collectives honoring the plan's per-bucket
        ``chunk_bytes``; ``"microbatch"`` is a trainer-level schedule and
        leaves the hook's per-sync program unchanged.

        ``trace``/``metrics`` are optional observability sinks (a
        :class:`~adapcc_tpu.utils.observability.CollectiveTrace` /
        :class:`~adapcc_tpu.utils.observability.MetricsRegistry`): the
        first traced sync records the bucket plan — count, byte histogram,
        oversized leaves, resolved chunk sizes, and the model-predicted
        ``exposed_comm_s`` floor — into both, and every traced sync, on
        every path, sets the ``grad_sync.bytes`` / ``grad_sync.calls``
        gauges.  When absent, an attached communicator's engine trace /
        metrics registry are used, and failing that the process-wide
        default registry (:attr:`metrics`).
        """
        from adapcc_tpu.ddp.overlap import resolve_overlap_mode
        from adapcc_tpu.quant import get_codec

        if compress != "strategy":
            get_codec(compress)  # loud, lists the registered codecs
        if quant_block_size < 1:
            raise ValueError(
                f"quant_block_size must be >= 1, got {quant_block_size}"
            )
        self.error_feedback = error_feedback
        self.quant_block_size = quant_block_size
        self.strategy = strategy
        self.axis_name = axis_name
        self.op = op
        self.bucket_cap_mb = bucket_cap_mb
        self.use_xla_fastpath = use_xla_fastpath
        self.communicator = communicator
        self.mode = mode
        self.compress = compress
        self.overlap = resolve_overlap_mode(overlap)
        self._trace = trace
        self._metrics = metrics
        self._plan: Optional[BucketPlan] = None
        self.recorded_buckets: List[tuple] = []  # (size, chunk_bytes) per bucket

    @property
    def metrics(self) -> Any:
        """The registry this hook (and the trainer that owns it) records
        into: the constructor's > the communicator's > the process-wide
        default."""
        if self._metrics is not None:
            return self._metrics
        attached = getattr(self.communicator, "metrics", None)
        return attached if attached is not None else default_registry()

    def _resolved_mode(self) -> str:
        if self.mode != "auto":
            return self.mode
        if not self.use_xla_fastpath:
            return "schedule"
        ips = set()
        for t in self.strategy.trees:
            ips |= set(t.ips.values())
        single_host = len(ips) <= 1
        return "psum" if single_host else "schedule"

    # -- host half -------------------------------------------------------------

    def negotiate(self, step: int) -> jnp.ndarray:
        """Coordinator round-trip → active mask for this step.

        Mirrors the reference's per-step sequence: ``update_relay(step)``
        (controller heartbeat) + first-bucket ``hook_fetch`` (rent-or-buy
        freeze).  Without a communicator/coordinator, everyone is active.
        """
        world = self.strategy.world_size
        if self.communicator is None or self.communicator._hooker is None:
            return jnp.ones((world,), dtype=jnp.bool_)
        self.communicator.update_relay(step)
        active_processes = self.communicator.hook_ready(step)
        # the coordinator speaks process ranks; the mask indexes chip ranks
        active_chips = self.communicator.chips_of_processes(active_processes)
        mask = np.zeros((world,), dtype=bool)
        mask[[r for r in active_chips if 0 <= r < world]] = True
        return jnp.asarray(mask)

    # -- device half -----------------------------------------------------------

    def effective_compress(self) -> str:
        """The wire codec this hook runs: ``ADAPCC_WIRE_DTYPE`` override >
        (``compress="strategy"`` → the strategy's synthesized wire_dtype) >
        the constructor's ``compress`` — the engine ring's precedence
        ladder, so hook and engine can never disagree about the codec a
        strategy asked for."""
        from adapcc_tpu.quant import resolve_wire_dtype

        value = (
            self.strategy.wire_dtype
            if self.compress == "strategy"
            else self.compress
        )
        return resolve_wire_dtype(value)

    def _codec_apply(self, g: jnp.ndarray) -> jnp.ndarray:
        from adapcc_tpu.quant import get_codec

        return get_codec(self.effective_compress()).apply(
            g, self.quant_block_size
        )

    def sync(self, grads: Any, active_mask: Optional[jnp.ndarray]) -> Any:
        """Allreduce a gradient pytree; call inside shard_map.

        ``active_mask=None`` means *statically* full-world (no coordinator
        attached): masking and the active-count divide fold away at trace
        time, leaving exactly the plain-DDP program.
        """
        import jax as _jax

        codec = self.effective_compress()
        if codec == "bf16":
            orig_dtypes = _jax.tree_util.tree_map(lambda g: g.dtype, grads)
            wire = _jax.tree_util.tree_map(
                lambda g: g.astype(jnp.bfloat16), grads
            )
            synced = self._sync_impl(wire, active_mask)
            return _jax.tree_util.tree_map(
                lambda s, dt: s.astype(dt), synced, orig_dtypes
            )
        if codec != "off":
            # quantized wire values, fp32 accumulation: each contribution is
            # replaced by its decode(encode(·)) before the collective — the
            # value contract the quantized ring engine also honors
            grads = _jax.tree_util.tree_map(self._codec_apply, grads)
        return self._sync_impl(grads, active_mask)

    def sync_error_feedback(
        self, grads: Any, residual: Any, active_mask: Optional[jnp.ndarray]
    ) -> tuple:
        """Error-feedback sync; call inside shard_map.  Returns ``(synced,
        new_residual)``: the wire carries ``codec(grads + residual)`` and
        the per-rank quantization error is banked for the next step, so no
        gradient mass is ever dropped (codec ``"off"`` keeps the residual
        identically zero and reduces to :meth:`sync`).

        Dtype contract: the residual accumulates in fp32 (a narrow bank
        would lose the very mass it defers), but the wire and the synced
        result keep each gradient leaf's own dtype — the fp32 compensation
        must not silently widen a bf16 program's collective operands, and
        the residual absorbs the cast-back error along with the codec's.
        """
        import jax as _jax

        tm = _jax.tree_util.tree_map
        orig_dtypes = tm(lambda g: g.dtype, grads)
        compensated = tm(
            lambda g, r: g.astype(jnp.float32) + r, grads, residual
        )
        wire = tm(
            lambda c, dt: self._codec_apply(c).astype(dt),
            compensated, orig_dtypes,
        )
        new_residual = tm(
            lambda c, w: c - w.astype(jnp.float32), compensated, wire
        )
        synced = self._sync_impl(wire, active_mask)
        return tm(lambda s, dt: s.astype(dt), synced, orig_dtypes), new_residual

    def resolved_chunk_bytes(self) -> List[int]:
        """The per-bucket chunk sizes the dispatch actually honors:
        ``ADAPCC_RING_CHUNK_BYTES`` override > the plan's per-bucket
        heuristic — the chunk-knob precedence every other chunk consumer
        follows.  Requires a recorded plan (first traced sync)."""
        from adapcc_tpu.comm.pallas_ring import resolve_chunk_bytes

        if self._plan is None:
            raise ValueError(
                "no recorded bucket plan yet: resolved_chunk_bytes() reads "
                "the table the first traced sync records"
            )
        return [resolve_chunk_bytes(c) for c in self._plan.chunk_bytes]

    def _record_plan(self, plan: BucketPlan, data_plane: str) -> None:
        """Bucket-plan observability (host side, once per trace): counts and
        the byte histogram into the metrics registry, the full table — with
        the resolved chunk sizes and the cost model's predicted
        ``exposed_comm_s`` floor for the active overlap schedule — into the
        dispatch trace."""
        metrics = self.metrics
        trace = self._trace
        if trace is None and self.communicator is not None:
            trace = getattr(
                getattr(self.communicator, "engine", None), "trace", None
            )
        metrics.gauge("bucket_plan.num_buckets", plan.num_buckets)
        metrics.gauge("bucket_plan.total_bytes", plan.total_bytes)
        if plan.oversized_leaves:
            metrics.incr(
                "bucket_plan.oversized_leaves", plan.oversized_leaves
            )
        for b in plan.bucket_bytes:
            metrics.sample("bucket_plan.bucket_bytes", b)
        if trace is not None:
            from adapcc_tpu.sim.calibrate import load_or_default
            from adapcc_tpu.sim.cost_model import (
                bottleneck_ring_coeffs,
                exposed_comm_floor_s,
            )

            world = self.strategy.world_size
            coeffs = bottleneck_ring_coeffs(load_or_default(world=world), world)
            wd = self.effective_compress()
            trace.record(
                "grad_sync",
                f"{data_plane}[{self.overlap}]",
                plan.total_bytes,
                buckets=plan.num_buckets,
                bucket_bytes=list(plan.bucket_bytes),
                plan_chunk_bytes=list(plan.chunk_bytes),
                chunk_bytes=self.resolved_chunk_bytes(),
                oversized_leaves=plan.oversized_leaves,
                overlap=self.overlap,
                wire_dtype=wd,
                exposed_comm_s=exposed_comm_floor_s(
                    world, plan.total_bytes, coeffs,
                    overlap=self.overlap,
                    bucket_bytes=plan.bucket_bytes,
                    wire_dtype=wd,
                ),
            )

    def _bucket_plan(self, grads: Any, data_plane: str) -> BucketPlan:
        if self._plan is None:
            # first trace records the bucket table (the analog of the
            # reference's step-0/1 record phase, commu.py:409-418)
            self._plan = build_bucket_plan(grads, self.bucket_cap_mb)
            self.recorded_buckets = [
                (s, c) for s, c in zip(self._plan.bucket_sizes, self._plan.chunk_bytes)
            ]
            self._record_plan(self._plan, data_plane)
        return self._plan

    def _record_sync(self, nbytes: int, calls: int) -> None:
        """What one sync hands to collectives (host side, at trace time):
        bytes at the wire dtype's width, and the collective calls the hook
        emits — one per leaf on the psum path, one per bucket on the
        bucketed paths (``overlap="bucket"`` then cuts each into chunks)."""
        metrics = self.metrics
        metrics.gauge("grad_sync.bytes", nbytes)
        metrics.gauge("grad_sync.calls", calls)

    def _sync_impl(self, grads: Any, active_mask: Optional[jnp.ndarray]) -> Any:
        import jax as _jax
        from jax import lax as _lax

        data_plane = self._resolved_mode()
        if self.overlap == "bucket":
            # per-bucket rolling sync: the bucket plan drives independent
            # chunked collectives on whichever data plane resolved —
            # bitwise-identical values, finer dispatch granularity so
            # XLA's async collectives interleave buckets with remaining
            # compute (docs/OVERLAP.md §2)
            from adapcc_tpu.ddp.overlap import rolling_bucket_sync

            mask = active_mask
            if data_plane != "psum" and mask is None:
                mask = jnp.ones((self.strategy.world_size,), dtype=jnp.bool_)
            plan = self._bucket_plan(grads, data_plane)
            self._record_sync(plan.total_bytes, plan.num_buckets)
            buckets = flatten_to_buckets(plan, grads)
            synced = rolling_bucket_sync(
                buckets, plan.chunk_bytes, mask,
                mode=data_plane, strategy=self.strategy,
                axis_name=self.axis_name, op=self.op,
            )
            return unflatten_from_buckets(plan, synced)
        if data_plane == "psum":
            leaves = _jax.tree_util.tree_leaves(grads)
            self._record_sync(
                sum(g.size * g.dtype.itemsize for g in leaves), len(leaves)
            )
            if active_mask is None:
                world = self.strategy.world_size

                def full(g):
                    s = _lax.psum(g, self.axis_name)
                    return s / world if self.op is ReduceOp.AVG else s

                return _jax.tree_util.tree_map(full, grads)
            return _jax.tree_util.tree_map(
                lambda g: masked_psum_shard(g, active_mask, self.axis_name, self.op),
                grads,
            )
        if active_mask is None:
            active_mask = jnp.ones((self.strategy.world_size,), dtype=jnp.bool_)
        plan = self._bucket_plan(grads, data_plane)
        self._record_sync(plan.total_bytes, plan.num_buckets)
        buckets = flatten_to_buckets(plan, grads)
        synced = [
            allreduce_shard(
                b, active_mask, self.strategy, axis_name=self.axis_name, op=self.op
            )
            for b in buckets
        ]
        return unflatten_from_buckets(plan, synced)

    def sync_deferred(
        self, grads: Any, deferred: Any, active_mask: jnp.ndarray
    ) -> tuple:
        """Async (non-BSP) relay sync; call inside shard_map.

        The reference's non-BSP mode replays a straggler's recorded buckets
        through relay ranks so its gradients still land
        (commu.py:160-170,427-431 + run.cu updateActive).  Under one SPMD
        program the replay becomes a carried per-rank buffer: a rank masked
        out of this step banks ``grads + deferred`` locally and contributes
        the accumulated sum at its next active step, when the masked
        allreduce folds it into the average.  Returns
        ``(synced, new_deferred)``; active ranks leave with a cleared buffer.
        """
        import jax as _jax
        from jax import lax as _lax

        contrib = _jax.tree_util.tree_map(lambda g, d: g + d, grads, deferred)
        synced = self.sync(contrib, active_mask)
        my_active = active_mask[_lax.axis_index(self.axis_name)]
        new_deferred = _jax.tree_util.tree_map(
            lambda c: jnp.where(my_active, jnp.zeros_like(c), c), contrib
        )
        return synced, new_deferred

    def reset_plan(self) -> None:
        """Drop the recorded bucket table (model structure changed)."""
        self._plan = None
        self.recorded_buckets = []
