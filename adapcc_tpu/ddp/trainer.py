"""DDP trainer: jitted data-parallel train step with adaptive gradient sync.

The TPU-shaped equivalent of the reference's training template
(train_ddp.py:30-58): model replicated, batch sharded over the world mesh
axis, gradients synced by the :class:`GradSyncHook` (strategy allreduce with
relay masking), optimizer step applied identically everywhere.  The whole
step — forward, backward, sync, update — is one ``shard_map`` program under
``jit``; the per-step coordinator negotiation stays on the host and feeds in
only a ``[world]`` active mask, so relay decisions never recompile.

``reconstruct_topology`` parity: calling :meth:`rebuild` with a new strategy
recompiles the step against the re-synthesized schedule (the analog of
tearing down and re-creating transmission contexts, adapcc.py:63-67).
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.ddp.hook import GradSyncHook
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.compile_cache import compile_watch
from adapcc_tpu.utils.observability import SPAN_PREFIX


@struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    # non-gradient model collections (e.g. BatchNorm ``batch_stats``),
    # updated by the loss when the trainer runs in ``stateful_loss`` mode;
    # the default empty tuple adds no pytree leaves, so stateless trainers
    # and old checkpoints are unaffected
    model_state: Any = ()

    @classmethod
    def create(
        cls,
        params: Any,
        tx: optax.GradientTransformation,
        model_state: Any = (),
    ) -> "TrainState":
        return cls(
            params=params,
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
            model_state=model_state,
        )


class DDPTrainer:
    """Builds and caches the compiled data-parallel train step.

    ``loss_fn(params, batch) -> scalar`` is evaluated per rank on that rank's
    batch shard; everything else is the trainer's business.  With
    ``stateful_loss=True`` the contract becomes ``loss_fn(params,
    model_state, batch) -> (scalar, new_model_state)`` — non-gradient model
    collections (BatchNorm running stats) ride in ``TrainState.model_state``.
    """

    def __init__(
        self,
        loss_fn: Callable[[Any, Any], jnp.ndarray],
        tx: optax.GradientTransformation,
        mesh: Mesh,
        strategy: Strategy,
        axis_name: str = RANKS_AXIS,
        bucket_cap_mb: float = 100.0,
        use_xla_fastpath: bool = True,
        communicator: Optional[Any] = None,
        # off by default: donation deletes the caller's input state buffers,
        # which surprises library users; training loops that own their state
        # should turn it on for in-place updates
        donate_state: bool = False,
        sync_mode: str = "auto",
        measure_gns: bool = False,
        # BSP mode (reference is_bsp, commu.py:107): a straggler's gradients
        # are dropped from its missed step.  bsp=False is the async relay
        # mode — stragglers bank their gradients in a per-rank deferred
        # buffer that folds into their next active step's allreduce
        # (commu.py:160-170, 427-431).
        bsp: bool = True,
        # force the compiled step to take a runtime active mask even without
        # a communicator (workloads injecting their own skew signal; tests)
        dynamic_mask: Optional[bool] = None,
        # gradient accumulation: split each rank's batch shard into this many
        # microbatches, scanned inside the compiled step with fp32 gradient
        # accumulation — same math as the full batch (for mean losses), peak
        # activation memory divided by accum_steps
        accum_steps: int = 1,
        # ZeRO-1 optimizer sharding (parallel/fsdp.py) composed with the
        # adaptive sync: the hook's strategy/relay allreduce produces the
        # synced gradient, then each rank updates only its flat [N/world]
        # optimizer shard and all-gathers the new params — relay tolerance
        # and 1/world optimizer memory in ONE compiled program.  States come
        # from :meth:`init_state` (not TrainState.create).
        zero1: bool = False,
        # zero1's param all-gather rides the Pallas ICI ring kernel instead
        # of XLA's (the hand-tuned data plane); shards become VMEM-tile
        # aligned in the ring's chunk ownership — see Zero1Optimizer(ring=)
        zero1_ring: bool = False,
        # ring staging granularity (strategy plane's synthesized
        # chunk_bytes; None = default).  Payloads above it stream through
        # fixed HBM→VMEM staging instead of living VMEM-resident
        zero1_ring_chunk_bytes: Optional[int] = None,
        # redundant ZeRO-1 shard placement (elastic/redundancy.py,
        # docs/RECOVERY.md): replicate each rank's optimizer shard to this
        # many ring-neighbor holders after every step, piggybacked on the
        # post-step all-gather window, so a dead rank's shard is repaired
        # from its in-fabric replica instead of a checkpoint reload.
        # None = the ADAPCC_SHARD_REPLICAS env funnel (default 0 = off);
        # requires zero1=True (there is no single-owner state otherwise)
        shard_replicas: Optional[int] = None,
        # gradient-sync wire codec (quant registry: "off" | "bf16" | "int8",
        # or "strategy" to adopt the synthesized Strategy.wire_dtype).
        # "bf16" halves wire bytes (torch bf16_compress_hook analog, ~bf16-
        # eps error on the synced mean); "int8" quantizes block-wise with
        # per-block fp32 scales (docs/QUANT.md)
        grad_compress: str = "off",
        # carry each rank's quantization error into the next step's gradient
        # (adapcc_tpu.quant error-feedback loop): closes the deterministic-
        # rounding accuracy gap of int8.  The residual rides the compiled
        # step as a per-rank [world, ...] buffer, exactly like the async
        # relay bank; requires BSP mode (the deferred bank and the residual
        # would otherwise double-carry the same missed-gradient mass)
        error_feedback: bool = False,
        # stateful losses carry non-gradient model collections (BatchNorm
        # running stats): ``loss_fn(params, model_state, batch) -> (loss,
        # new_model_state)``, with the state riding in
        # ``TrainState.model_state``.  The state is compiled replicated, so
        # on a multi-rank mesh the loss must produce cross-rank identical
        # state — BatchNorm with ``axis_name`` set (SyncBN) does; unsynced
        # per-rank statistics would silently diverge from the spec.
        # Relay/masked steps: the active mask gates GRADIENT sync only; the
        # SyncBN pmean still averages every rank's batch, by design —
        # a straggler's forward ran on real data, so its activation
        # statistics are sound even when its late gradients are dropped,
        # and full-axis stats stay bit-identical across ranks (a masked
        # pmean would fork per-rank state and violate the replication spec).
        stateful_loss: bool = False,
        # measurement-driven tuning (adapcc_tpu/tuner): record each step's
        # dispatch walltime into the tuning database under the executed
        # (wire codec, ring chunk) cell, and every ``tune_every`` steps let
        # the policy re-choose the gradient-sync codec — the trainer adopts
        # a winning challenger by recompiling with the new codec (hysteresis
        # in the policy keeps that rare).  ADAPCC_TUNER=off still disables
        # everything globally; an attached communicator's tuner is reused so
        # engine dispatches and step timings share one database.
        tune: bool = False,
        tuner: Optional[Any] = None,
        tune_every: int = 16,
        # overlapped gradient sync (adapcc_tpu/ddp/overlap, docs/OVERLAP.md;
        # ADAPCC_OVERLAP overrides, resolved at construction):
        #   "off"        — compute the full gradient, then sync (baseline);
        #   "bucket"     — per-bucket rolling sync: every bucket dispatches
        #                  as independent chunked collectives honoring the
        #                  plan's per-bucket chunk_bytes, so XLA's async
        #                  collectives interleave them with remaining
        #                  compute.  Bitwise-identical gradients;
        #   "microbatch" — pipeline each microbatch delta's allreduce
        #                  behind the next microbatch's forward/backward in
        #                  the accumulation scan (requires accum_steps >= 2,
        #                  BSP, no error_feedback/measure_gns); parity to
        #                  accumulation-order tolerance, accum x wire bytes.
        overlap: str = "off",
    ) -> None:
        self.loss_fn = loss_fn
        self.stateful_loss = stateful_loss
        # one internal signature for both modes: (params, ms, batch) -> (loss, ms)
        if stateful_loss:
            self._loss3 = loss_fn
        else:
            self._loss3 = lambda p, ms, b: (loss_fn(p, b), ms)
        self.tx = tx
        self.mesh = mesh
        self.axis_name = axis_name
        self.donate_state = donate_state
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = accum_steps
        self.zero1 = zero1
        if zero1_ring and not zero1:
            raise ValueError("zero1_ring=True requires zero1=True")
        self.zero1_ring = zero1_ring
        self.zero1_ring_chunk_bytes = zero1_ring_chunk_bytes
        from adapcc_tpu.elastic.redundancy import shard_replicas as _replicas

        # env > explicit arg > off (the chunk-bytes precedence ladder);
        # resolved eagerly so a malformed env var dies at construction
        self.shard_replicas = _replicas(
            default=0 if shard_replicas is None else int(shard_replicas)
        )
        if self.shard_replicas and not zero1:
            raise ValueError(
                "shard_replicas > 0 requires zero1=True: replicated DDP "
                "state has no single-owner optimizer shard to replicate "
                "(every rank already holds everything)"
            )
        #: the in-fabric replica set (built at init_state when armed)
        self.replica_store: Optional[Any] = None
        if error_feedback and not bsp:
            raise ValueError(
                "error_feedback=True requires BSP mode: the async relay "
                "bank already defers gradient mass, and layering the "
                "quantization residual on top would double-carry it"
            )
        self.error_feedback = error_feedback
        from adapcc_tpu.ddp.overlap import resolve_overlap_mode

        self.overlap = resolve_overlap_mode(overlap)
        if self.overlap == "microbatch":
            # guard rails for the pipelined scan — each incompatibility
            # would silently change semantics, so all reject at
            # construction (the bsp/error-feedback precedent above):
            if accum_steps < 2:
                raise ValueError(
                    "overlap='microbatch' needs accum_steps >= 2: with one "
                    "microbatch there is no later compute to hide the sync "
                    "behind (use overlap='bucket')"
                )
            if not bsp:
                raise ValueError(
                    "overlap='microbatch' requires BSP mode: the async "
                    "relay's deferred bank folds into ONE sync per step, "
                    "which the per-microbatch pipeline would re-sync "
                    "accum times"
                )
            if error_feedback:
                raise ValueError(
                    "overlap='microbatch' with error_feedback=True would "
                    "apply the codec (and bank its residual) per "
                    "microbatch delta — a different quantization loop than "
                    "the one the residual compensates; use "
                    "overlap='bucket' (residual threads unchanged) or "
                    "drop error_feedback"
                )
            if measure_gns:
                raise ValueError(
                    "overlap='microbatch' never materializes the unsynced "
                    "accumulated gradient the GNS estimator contrasts; "
                    "use overlap='bucket' or drop measure_gns"
                )
        self.hook = GradSyncHook(
            strategy,
            axis_name=axis_name,
            bucket_cap_mb=bucket_cap_mb,
            use_xla_fastpath=use_xla_fastpath,
            communicator=communicator,
            mode=sync_mode,
            compress=grad_compress,
            error_feedback=error_feedback,
            overlap=self.overlap,
        )
        if error_feedback and self.hook.effective_compress() == "off":
            # the residual of a no-op codec is provably zero, but the bank
            # would still thread (and donate) a world-sized fp32 copy of
            # every param through each compiled step
            raise ValueError(
                "error_feedback=True with an 'off' wire codec banks an "
                "identically-zero residual at world x params x 4 bytes per "
                "step; pass grad_compress='int8' (or 'strategy' / set "
                "ADAPCC_WIRE_DTYPE) or drop error_feedback"
            )
        self.bsp = bsp
        self._dynamic_mask = (
            dynamic_mask
            if dynamic_mask is not None
            else (communicator is not None or not bsp)
        )
        if not bsp and not self._dynamic_mask:
            raise ValueError("async relay (bsp=False) needs a runtime active mask")
        if communicator is not None and not self._dynamic_mask:
            raise ValueError(
                "a coordinator-attached trainer must compile a dynamic-mask "
                "step: dynamic_mask=False would silently discard the "
                "negotiated active set"
            )
        self._deferred: Optional[Any] = None
        self._residual: Optional[Any] = None  # error-feedback bank
        self._bank_dirty = False  # some rank holds banked (deferred) grads
        self._coord_calibrated = False
        self._compiled: Optional[Callable] = None
        self._scan_cache: dict = {}  # ("scan", n_steps) → compiled program
        # elastic plan failover (adapcc_tpu.elastic, docs/ELASTIC.md):
        # compiled step programs keyed by the strategy fingerprint they were
        # traced under.  prewarm() AOT-compiles a standby strategy's step;
        # adopt_strategy() then swaps to it as a dispatch-time cache-key
        # switch — the training-loop twin of the engine's standby plan cache
        self._program_cache: dict = {}  # fingerprint → compiled step
        self._host_step = 0
        # supervised mode (docs/SUPERVISOR.md): when an out-of-band
        # Supervisor is attached, step() pulls its last ACTUATED
        # contribution mask instead of negotiating — membership authority
        # leaves the training loop entirely
        self._supervisor = None
        # optional gradient-noise-scale measurement (units-test/get_gns.py):
        # the per-rank vs allreduced gradient norms fall out of the sync step
        # for free; the estimator is created at the first step, when the
        # per-rank batch size is known
        if measure_gns and mesh.devices.size < 2:
            raise ValueError(
                "measure_gns needs a multi-device mesh: the estimator contrasts "
                "per-rank (small-batch) vs allreduced (big-batch) gradients"
            )
        self.measure_gns = measure_gns
        self._gns: Optional[Any] = None
        self._gns_pending: list = []
        self._zero1_opt: Optional[Any] = None
        # -- autotuning state --------------------------------------------------
        if tune_every < 1:
            raise ValueError(f"tune_every must be >= 1, got {tune_every}")
        self.tune_every = tune_every
        if tune and tuner is None:
            tuner = getattr(communicator, "tuner", None)
        if tune and tuner is None:
            from adapcc_tpu.tuner import CollectiveTuner

            tuner = CollectiveTuner.for_mesh(mesh)
        if tune and tuner.explicit_mode is None:
            # tune=True is an explicit opt-in: with ADAPCC_TUNER unset the
            # tuner must actually choose — for the per-step codec AND the
            # Zero1Optimizer chunk gate (which reads tuner.choosing).  A
            # caller-pinned mode (e.g. an explicit record-only tuner) is
            # respected; the env still overrides either way.
            tuner = tuner.with_mode("choose")
        self.tune = tune
        self.tuner = tuner if tune else None
        # the overlap schedules THIS trainer can legally compile — the
        # tuner's ddp_step grid is narrowed to these so the explorer never
        # pins on a cell the trainer cannot run (the error-feedback/'off'
        # codec precedent)
        modes = ["off", "bucket"]
        if (
            accum_steps >= 2
            and bsp
            and not error_feedback
            and not measure_gns
        ):
            modes.append("microbatch")
        self._overlap_modes = tuple(modes)
        self._grad_bytes: Optional[float] = None
        # warmup-discard token: bumped on every recompile so the first step
        # of each compiled program (which pays tracing + XLA compile) never
        # lands in the database as a steady-state sample
        self._build_gen = 0
        # the process's watch of JAX's compile events: it splits what each
        # step program's first call costs, and finds a step that compiles
        # when it should not (docs/OBSERVABILITY.md)
        self._watch = compile_watch()

    def _tuning(self) -> bool:
        """Is per-step tuning live right now?  ``tune=True`` opts the
        trainer in (its tuner view defaults to choose, see ``__init__``);
        ``ADAPCC_TUNER=off`` still kills it globally (same contract as the
        engine)."""
        return self.tune and self.tuner is not None and self.tuner.recording

    # -- step program ----------------------------------------------------------

    def _zero1_overlap(self) -> str:
        """The Zero1Optimizer schedule the trainer's overlap mode implies:
        any overlapped trainer schedule also chunks the zero1 RS/AG pair
        (the Pallas ring streams its own chunks, so the ring path keeps
        one chunking plane).  One definition for construction AND tuner
        adoption — the two must never disagree."""
        return (
            "bucket"
            if self.overlap != "off" and not self.zero1_ring
            else "off"
        )

    def init_state(self, params: Any, model_state: Any = ()) -> TrainState:
        """Build the trainer's state: replicated optax state normally, the
        ZeRO-1 flat master + sharded optimizer state when ``zero1=True``."""
        if not self.zero1:
            return self._on_mesh(
                TrainState.create(params, self.tx, model_state=model_state)
            )
        from adapcc_tpu.parallel.fsdp import Zero1Optimizer

        opt = self._zero1_opt = Zero1Optimizer(
            self.tx, self.mesh, self.axis_name, ring=self.zero1_ring,
            ring_chunk_bytes=self.zero1_ring_chunk_bytes,
            tuner=self.tuner,
            overlap=self._zero1_overlap(),
        )
        master, opt_state = opt.init(params)
        if self.shard_replicas:
            from adapcc_tpu.elastic.redundancy import ShardReplicaStore

            self.replica_store = ShardReplicaStore(
                self.mesh.shape[self.axis_name],
                ips=self.hook.strategy.trees[0].ips,
                replicas=self.shard_replicas,
            )
        if self.zero1_ring_chunk_bytes is None:
            # adopt the optimizer's (possibly tuner-chosen) staging
            # granularity so the step program and the optimizer execute the
            # same ring plan
            self.zero1_ring_chunk_bytes = opt.ring_chunk_bytes
        return self._on_mesh(TrainState(
            params=params,
            opt_state=(master, opt_state),
            step=jnp.zeros((), jnp.int32),
            model_state=model_state,
        ))

    def _on_mesh(self, state: TrainState) -> TrainState:
        """Commit what was made from nothing (the step counter, the
        optimizer's count, a caller's fresh ``model_state``, plain params) to
        the mesh, replicated, as the step's own outputs are.  Left
        uncommitted they type the first call's arguments differently from
        every later call's, and the step is traced and compiled a second
        time on its second call.  The ZeRO-1 pair is sharded by its maker."""
        replicated = NamedSharding(self.mesh, P())

        def place(x):
            return x if getattr(x, "committed", False) else jax.device_put(x, replicated)

        if self.zero1:
            rest = jax.tree_util.tree_map(place, state.replace(opt_state=()))
            return rest.replace(opt_state=state.opt_state)
        return jax.tree_util.tree_map(place, state)

    def checkpoint_extra(self, extra: Optional[dict] = None) -> dict:
        """``TrainCheckpointState.extra`` payload for this trainer's state.

        In ZeRO-1 mode it stamps the optimizer's layout tag (ring/world/
        align), which ``checkpoint.py``'s layout guard enforces on every
        load — a resume with ``--zero1-ring`` flipped fails loudly instead
        of silently loading a chunk-permuted master."""
        if not self.zero1:
            return dict(extra or {})
        if self._zero1_opt is None:
            raise ValueError(
                "call init_state(params) before checkpoint_extra(): the "
                "layout tag records the constructed optimizer's geometry"
            )
        return self._zero1_opt.checkpoint_extra(extra)

    def _check_state(self, state: TrainState) -> None:
        """Catch the common zero1 misuse (TrainState.create's replicated
        optax state) before it dies as a cryptic shard_map spec error."""
        if not self.zero1:
            return
        world = self.mesh.shape[self.axis_name]
        opt = state.opt_state
        ok = (
            isinstance(opt, tuple)
            and len(opt) == 2
            and getattr(opt[0], "ndim", 0) == 2
            and opt[0].shape[0] == world
        )
        if not ok:
            raise ValueError(
                "zero1=True needs the sharded (master [world, N/world], opt "
                "shard) state from trainer.init_state(params) — got a "
                "replicated optax state (TrainState.create?)"
            )

    def _state_spec(self):
        """shard_map pytree-prefix spec for TrainState: everything
        replicated, except the ZeRO-1 ``(master, opt shard)`` pair whose
        leading ``[world]`` dim shards over the axis."""
        opt_spec = P(self.axis_name) if self.zero1 else P()
        return TrainState(
            params=P(), opt_state=opt_spec, step=P(), model_state=P()
        )

    @jax.named_scope("optimizer")  # names the update in the device trace
    def _apply_synced(
        self, state: TrainState, synced: Any, model_state: Any = None
    ) -> TrainState:
        """Optimizer tail shared by every step variant: one change to the
        update rule applies to step() and scan_steps() alike.

        Runs inside the shard_map body.  ZeRO-1: the synced gradient is
        replicated (the hook allreduced it), so this rank's flat slice is a
        free local read; the optax update touches only the [N/world] shard
        and one all-gather rebuilds the replicated params.
        """
        if model_state is None:
            model_state = state.model_state
        if not self.zero1:
            updates, opt_state = self.tx.update(synced, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return TrainState(
                params=params,
                opt_state=opt_state,
                step=state.step + 1,
                model_state=model_state,
            )

        from adapcc_tpu.parallel.fsdp import (
            _flatten,
            _flatten_meta,
            local_grad_shard,
            zero1_apply_shard,
        )

        world = self.mesh.shape[self.axis_name]
        if self.zero1_ring:
            from adapcc_tpu.comm.pallas_ring import _tile_elems

            align = _tile_elems(jnp.float32)
            from adapcc_tpu.ops.kernel_mode import resolve_interpret

            ring_interpret = resolve_interpret(None, "zero1_ring")
        else:
            align, ring_interpret = 1, False
        meta = _flatten_meta(state.params, world, align)
        master, opt_state = state.opt_state  # [1, L] / [1, ...] per shard
        master = master[0]
        opt_state = jax.tree_util.tree_map(lambda x: x[0], opt_state)
        # the hook already allreduced: every rank holds the same synced
        # grads, so its slice is a free local read (ring ownership = offset 1)
        g_shard = local_grad_shard(
            _flatten(synced, meta), meta, world, self.axis_name,
            offset=1 if self.zero1_ring else 0,
        )
        overlap_chunks = (
            self._zero1_opt.overlap_chunks(meta.padded // world)
            if self._zero1_opt is not None
            else 1
        )
        master, opt_state, params = zero1_apply_shard(
            self.tx, master, opt_state, g_shard, meta, self.axis_name,
            ring=self.zero1_ring, ring_interpret=ring_interpret,
            ring_chunk_bytes=self.zero1_ring_chunk_bytes,
            overlap_chunks=overlap_chunks,
        )
        return TrainState(
            params=params,
            opt_state=(
                master[None],
                jax.tree_util.tree_map(lambda x: x[None], opt_state),
            ),
            step=state.step + 1,
            model_state=model_state,
        )

    def _value_and_grad(self, params: Any, model_state: Any, batch: Any):
        """Per-rank (loss, grads, new_model_state), microbatch-accumulated
        when accum_steps>1.

        Accumulation runs as a ``lax.scan`` over ``[accum, B/accum, ...]``
        microbatches with fp32 gradient carry; the mean over equal-size
        microbatches equals the full-batch value for mean losses, so every
        sync/update path downstream is unchanged.  Model state threads
        through the microbatches sequentially (torch grad-accum semantics:
        BatchNorm statistics see every microbatch).
        """
        accum = self.accum_steps
        vg = jax.value_and_grad(self._loss3, has_aux=True)
        if accum == 1:
            (loss, new_ms), grads = vg(params, model_state, batch)
            return loss, grads, new_ms

        micro = self._to_microbatches(batch)
        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def body(carry, mb):
            acc_l, acc_g, ms = carry
            (loss, ms), g = vg(params, ms, mb)
            acc_g = jax.tree_util.tree_map(
                lambda a, x: a + x.astype(jnp.float32), acc_g, g
            )
            return (acc_l + loss.astype(jnp.float32), acc_g, ms), None

        (loss_sum, g_sum, new_ms), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), g0, model_state), micro
        )
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / accum).astype(p.dtype), g_sum, params
        )
        return loss_sum / accum, grads, new_ms

    def _to_microbatches(self, batch: Any) -> Any:
        """``[B, ...]`` leaves → ``[accum, B/accum, ...]`` microbatch stacks
        (shared by the sequential and pipelined accumulation paths)."""
        accum = self.accum_steps

        def to_micro(x):
            b = x.shape[0]
            if b % accum:
                raise ValueError(
                    f"per-rank batch {b} not divisible by accum_steps {accum}"
                )
            return x.reshape((accum, b // accum) + x.shape[1:])

        return jax.tree_util.tree_map(to_micro, batch)

    def _loss_and_synced(
        self, params: Any, model_state: Any, batch: Any, mask
    ):
        """Per-rank ``(loss, synced_grads, new_model_state)`` for the plain
        (non-banked) sync paths: sequential accumulate-then-sync by
        default, the microbatch-pipelined scan under
        ``overlap='microbatch'`` (docs/OVERLAP.md §1)."""
        if self.overlap != "microbatch":
            loss, grads, new_ms = self._value_and_grad(
                params, model_state, batch
            )
            return loss, self._sync(grads, mask), new_ms
        from adapcc_tpu.ddp.overlap import microbatch_pipelined_sync

        vg = jax.value_and_grad(self._loss3, has_aux=True)
        return microbatch_pipelined_sync(
            vg, params, model_state, self._to_microbatches(batch),
            lambda g: self._sync(g, mask), self.accum_steps,
        )

    def _sync(self, grads: Any, mask) -> Any:
        """The hook's sync under the ``grad_sync`` scope, so the device
        trace names the operations it emits."""
        with jax.named_scope("grad_sync"):
            return self.hook.sync(grads, mask)

    def _static_full_step(self, state: TrainState, batch: Any):
        """The static full-world step (no mask, no relay banking): the body
        scan_steps scans and _build's static path reduces to."""
        loss, synced, new_ms = self._loss_and_synced(
            state.params, state.model_state, batch, None
        )
        return self._apply_synced(state, synced, new_ms), loss

    def _build(self) -> Callable:
        # without a coordinator (or an explicit dynamic_mask request) the
        # active set is statically full-world, so the compiled program takes
        # no mask input and the masking folds away
        dynamic_mask = self._dynamic_mask
        deferred_relay = not self.bsp
        error_feedback = self.error_feedback

        pipelined = self.overlap == "microbatch"

        def ddp_step(state: TrainState, batch: Any, *extra: Any):
            mask = extra[0] if dynamic_mask else None
            outs = []
            if not pipelined:
                loss, grads, new_ms = self._value_and_grad(
                    state.params, state.model_state, batch
                )
            if pipelined:
                # microbatch-pipelined sync (docs/OVERLAP.md §1): each
                # delta's allreduce dispatches behind the next microbatch's
                # compute inside the accumulation scan.  The banked paths
                # (deferred relay, error feedback) and measure_gns are
                # construction-rejected with this schedule.
                loss, synced, new_ms = self._loss_and_synced(
                    state.params, state.model_state, batch, mask
                )
            elif deferred_relay:
                # deferred rides in/out with a sharded [world] leading dim;
                # strip the per-shard [1] so it matches the grads tree
                deferred = jax.tree_util.tree_map(lambda d: d[0], extra[-1])
                with jax.named_scope("grad_sync"):
                    synced, new_deferred = self.hook.sync_deferred(
                        grads, deferred, mask
                    )
                outs.append(jax.tree_util.tree_map(lambda d: d[None], new_deferred))
            elif error_feedback:
                # the residual bank rides like the deferred bank: per-rank,
                # sharded [world] leading dim, replaced wholesale every step
                residual = jax.tree_util.tree_map(lambda r: r[0], extra[-1])
                with jax.named_scope("grad_sync"):
                    synced, new_residual = self.hook.sync_error_feedback(
                        grads, residual, mask
                    )
                outs.append(
                    jax.tree_util.tree_map(lambda r: r[None], new_residual)
                )
            else:
                synced = self._sync(grads, mask)
            new_state = self._apply_synced(state, synced, new_ms)
            if self.measure_gns:
                from adapcc_tpu.measure.gns import ddp_grad_sq_norms

                small, big = ddp_grad_sq_norms(grads, synced, self.axis_name)
                outs.insert(0, jnp.stack([small, big]))
            # [1] per rank → stacked [world] losses
            return (new_state, loss[None], *outs)

        banked = deferred_relay or error_feedback
        in_specs = (
            (self._state_spec(), P(self.axis_name))
            + ((P(),) if dynamic_mask else ())
            + ((P(self.axis_name),) if banked else ())
        )
        out_specs = (
            (self._state_spec(), P(self.axis_name))
            + ((P(),) if self.measure_gns else ())
            + ((P(self.axis_name),) if banked else ())
        )
        fn = jax.shard_map(
            ddp_step,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            # gradients pass through ppermute chains; jax cannot prove the
            # result replicated, but the allreduce guarantees it
            check_vma=False,
        )
        donate = (0,) if self.donate_state else ()
        if banked:
            # the deferred/residual bank is replaced wholesale every step;
            # donating it avoids holding two world-sized copies per dispatch
            donate = donate + (len(in_specs) - 1,)
        return jax.jit(fn, donate_argnums=donate)

    def step(
        self,
        state: TrainState,
        batch: Any,
        step_idx: Optional[int] = None,
        active_mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[TrainState, jnp.ndarray]:
        """One training step.  ``batch`` leading dim is the global batch,
        sharded over the mesh axis.  Returns (new_state, per-rank losses).

        ``active_mask`` overrides the coordinator's negotiation (workloads
        injecting their own skew signal; requires a dynamic-mask trainer).
        """
        # the host step index: what the three spans of one step share.
        # Host-side counter: reading state.step would force a device sync
        # on every dispatch, serializing the loop
        idx = self._host_step if step_idx is None else step_idx
        span = self.hook.metrics.span
        # what JAX compiles on this thread from here to the return is this
        # step's: a recompile on the hot path, unless the call turns out to
        # be a program's first (_prepare_step clears the mark)
        here = self._watch.here
        here.step = idx
        try:
            # three consecutive spans tile the call; no parent span (their
            # sum is the parent): live only while a profile is being taken
            # (docs/OBSERVABILITY.md)
            with span("step.prepare", step=idx):
                fn, args, active_mask = self._prepare_step(
                    state, batch, idx, active_mask
                )
            tuning = self._tuning()
            with span("step.enqueue", step=idx):
                # pjit dispatch, and the runtime's wait for output buffers
                if tuning:
                    t0 = time.perf_counter()
                    out = fn(*args)
                    jax.block_until_ready(out)
                    seconds = time.perf_counter() - t0
                else:
                    out = fn(*args)
            with span("step.finish", step=idx):
                if tuning:
                    self._tune_observe(state, seconds)
                return self._finish_step(out, batch, active_mask)
        finally:
            stall, here.step = here.step, None
            if stall is not idx and stall is not None:
                self._watch.left_step(stall)  # an event arrived: say so, once

    def _prepare_step(
        self, state: TrainState, batch: Any, idx: int, active_mask
    ) -> Tuple[Callable, list, Optional[jnp.ndarray]]:
        """Everything ``step`` does before the compiled call: the program,
        its arguments, and the step's active mask."""
        self._check_state(state)
        # local binding: an out-of-band supervisor's adopt_strategy may
        # null self._compiled between this resolution and the dispatch
        # in step(); the step then finishes on the outgoing program (exactly
        # like a collective already in flight when an epoch bumps) and the
        # NEXT step picks up the swapped one
        fn = self._compiled
        if fn is None:
            key = self._program_key()
            fn = self._compiled = self._program_cache.get(key)
            if fn is None:
                fn = self._compiled = self._program_cache[key] = self._build()
                self._build_gen += 1  # an actual (re)trace, not a cache hit
                # the call about to be made is this program's first: what
                # compiles in this step is the build, and no recompile
                self._watch.here.step = None
                fn = functools.partial(self._first_call, fn, key, "step", idx)
        if not self._coord_calibrated:
            # rent-or-buy calibration: this trainer's actual gradient volume
            # + the bootstrap's profiled link bandwidth replace the
            # coordinator's hardcoded cost constants.  Latches on SUCCESS —
            # a False (worker process, coordinator not yet enabled, no
            # profile) retries next step; the no-server case is a cheap
            # attribute check inside calibrate_coordinator
            comm = self.hook.communicator
            if comm is None or not hasattr(comm, "calibrate_coordinator"):
                self._coord_calibrated = True
            else:
                grad_bytes = sum(
                    leaf.nbytes for leaf in jax.tree_util.tree_leaves(state.params)
                )
                self._coord_calibrated = comm.calibrate_coordinator(
                    float(grad_bytes)
                )
        self._host_step = idx + 1
        if active_mask is not None and not self._dynamic_mask:
            raise ValueError(
                "this trainer compiled a static full-world step; pass "
                "dynamic_mask=True to drive explicit active masks"
            )
        if active_mask is None and self._supervisor is not None:
            # supervised mode (docs/SUPERVISOR.md): the out-of-band daemon
            # owns detect → decide → swap; the step only OBSERVES its last
            # actuated view — the trainer never makes a membership call
            active_mask = jnp.asarray(self._supervisor.current_mask())
        if active_mask is None and self.hook.communicator is not None:
            active_mask = self.hook.negotiate(idx)
        args = [state, batch]
        if self._dynamic_mask:
            if active_mask is None:
                active_mask = jnp.ones((self.mesh.devices.size,), dtype=jnp.bool_)
            args.append(active_mask)
        if not self.bsp:
            if self._deferred is None:
                world = self.mesh.devices.size
                self._deferred = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((world,) + p.shape, p.dtype), state.params
                )
            args.append(self._deferred)
        elif self.error_feedback:
            if self._residual is None:
                world = self.mesh.devices.size
                # fp32 regardless of param dtype: a residual accumulated in
                # a narrow dtype would itself lose the mass it exists to keep
                self._residual = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((world,) + p.shape, jnp.float32),
                    state.params,
                )
            args.append(self._residual)
        return fn, args, active_mask

    def _first_call(
        self, fn: Callable, key: tuple, cause: str, idx: int, *args: Any
    ):
        """A step program's first call, which traces, lowers and loads it,
        as the span ``step.build``: kept by ``observe`` with its start, end
        and cause (so it is there with no profile live, and after the next
        one), and an ``adapcc.step.build`` annotation where a profile is
        live.  It ends when the dispatch returns.  ``step.build.load`` is
        the backend seconds JAX reported inside it (XLA's compile, or the
        persistent cache's read) and ``step.build.trace_lower`` the rest:
        JAX's trace, the lowering with its Mosaic kernels, the cache key's
        hash.  The inner jitted functions' trace events nest in the outer
        one's, so the rest is taken by subtraction and not by a sum."""
        fingerprint, codec, overlap = key
        meta = dict(
            gen=self._build_gen, fingerprint=fingerprint, codec=codec,
            overlap=overlap, cause=cause, step=idx,
        )
        annotation = (
            TraceAnnotation(SPAN_PREFIX + "step.build", **meta)
            if TraceAnnotation.is_enabled()
            else contextlib.nullcontext()
        )
        with self._watch.building() as build:
            t0 = time.perf_counter()
            with annotation:
                out = fn(*args)
            end = time.perf_counter()
        metrics = self._watch.registry
        metrics.observe("step.build", end - t0, end=end, **meta)
        metrics.observe("step.build.load", build.load_s)
        metrics.observe("step.build.trace_lower", end - t0 - build.load_s)
        # no hit in the load: XLA compiled it (with no persistent cache in
        # force, every build)
        metrics.incr("step.build.cache_misses", 0 if build.cache_hits else 1)
        return out

    def _finish_step(
        self, out, batch: Any, active_mask
    ) -> Tuple[TrainState, jnp.ndarray]:
        """Everything ``step`` does after the compiled call returns."""
        if not self.bsp:
            *out, self._deferred = out
        elif self.error_feedback:
            *out, self._residual = out
        if self.replica_store is not None:
            # the piggyback window (docs/RECOVERY.md §1): the shard rows
            # this step's optimizer update just wrote are exactly what the
            # post-step all-gather broadcast alongside — capture them,
            # stamped with the STATE's own step counter (not the
            # process-local _host_step, which restarts at 0 on a resumed
            # trainer and would make the freshness guard refuse every
            # repair after a restore) so a later repair's guard compares
            # like with like against state.step
            self.replica_store.capture(
                out[0].opt_state,
                int(np.asarray(jax.device_get(out[0].step))),
            )
        if not self.measure_gns:
            return tuple(out) if isinstance(out, list) else out
        new_state, loss, norms = out
        self._record_gns(batch, norms, active_mask)
        return new_state, loss

    def scan_steps(
        self, state: TrainState, batch: Any, n_steps: int
    ) -> Tuple[TrainState, jnp.ndarray]:
        """``n_steps`` full-world steps on one batch as ONE compiled dispatch
        (``lax.scan`` inside the shard_map).

        Every ``step()`` call pays a host→device dispatch; a scanned
        multi-step program pays it once, so this is the way to take the
        host out of a device-side throughput reading and the fast way to
        run tight loops whose active set cannot change mid-scan.  Static
        full world only — no per-step negotiation, relay banking, or GNS
        capture.  Returns
        ``(final_state, losses [world, n_steps])``.
        """
        if self._dynamic_mask or not self.bsp or self.measure_gns:
            raise ValueError(
                "scan_steps runs a static full-world program: incompatible "
                "with dynamic_mask, async relay (bsp=False), and measure_gns"
            )
        if self.error_feedback:
            raise ValueError(
                "scan_steps does not thread the error-feedback residual "
                "across scanned steps; use step() with error_feedback=True"
            )
        self._check_state(state)
        key = ("scan", int(n_steps))
        fn = self._scan_cache.get(key)
        if fn is None:
            from jax import lax

            def per_shard(state: TrainState, batch: Any):
                def body(st, _):
                    return self._static_full_step(st, batch)

                st, losses = lax.scan(body, state, None, length=n_steps)
                return st, losses[None]  # [1, n] per rank → stacked [world, n]

            fn = jax.jit(
                jax.shard_map(
                    per_shard,
                    mesh=self.mesh,
                    in_specs=(self._state_spec(), P(self.axis_name)),
                    out_specs=(self._state_spec(), P(self.axis_name)),
                    check_vma=False,
                ),
                donate_argnums=(0,) if self.donate_state else (),
            )
            self._scan_cache[key] = fn
        new_state, losses = fn(state, batch)
        self._host_step += n_steps
        return new_state, losses

    # -- autotuning ------------------------------------------------------------

    def _step_cell(self, grad_bytes: int):
        """The database cell the *current* configuration's step walltimes
        pool under: the hook's effective wire codec crossed with the
        executed overlap schedule (encoded in the key's path slot via
        ``hook_path``).  The cell must stay inside
        ``TuningPolicy.candidates("ddp_step")`` — the (codec × overlap)
        grid narrowed to this trainer's legal modes — or the posterior
        never forms and exploration never ends; the ZeRO-1 ring chunk is a
        separate knob, tuned once at ``Zero1Optimizer.init`` under its own
        "zero1_ring" cells."""
        from adapcc_tpu.tuner.policy import NO_CHUNK, hook_path

        return self.tuner.key_for(
            "ddp_step", grad_bytes, hook_path(self.overlap), NO_CHUNK,
            self.hook.effective_compress(),
        )

    def _tune_observe(self, state: TrainState, seconds: float) -> None:
        """Record one step walltime; periodically let the policy re-choose
        the gradient-sync codec and adopt a winning challenger (recompile).
        Step times of different codecs share the same compute, so their
        medians are mutually comparable — exactly the posterior the policy
        ranks on."""
        if self._grad_bytes is None:
            self._grad_bytes = float(
                sum(
                    leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(state.params)
                )
            )
        grad_bytes = int(self._grad_bytes)
        self.tuner.observe_dispatch(
            self._step_cell(grad_bytes), ("ddp_step", self._build_gen), seconds
        )
        if not self.tuner.choosing:
            return  # record-only mode: measure, never steer
        if self._host_step % self.tune_every:
            return
        import os as _os

        from adapcc_tpu.ddp.overlap import OVERLAP_ENV
        from adapcc_tpu.quant import WIRE_DTYPE_ENV
        from adapcc_tpu.tuner.policy import hook_overlap_of

        if _os.environ.get(WIRE_DTYPE_ENV, "").strip():
            # ADAPCC_WIRE_DTYPE pins the executed codec (effective_compress
            # resolves it); "adopting" would recompile the step for zero
            # behavioral change, every tune_every boundary, forever — keep
            # measuring the pinned cell and never steer
            return
        # error feedback cannot legally run the 'off' codec (the residual
        # would bank zero at world x params); excluding it from the grid —
        # not just from adoption — keeps the explorer from pinning on a
        # cell that can never accrue samples
        wire_dtypes = (
            tuple(w for w in self.tuner.policy.wire_dtypes if w != "off")
            if self.error_feedback
            else None
        )
        # ADAPCC_OVERLAP pins the executed schedule the same way the wire
        # env pins the codec: collapse the overlap axis to the pinned mode
        # (the codec axis stays free) instead of "adopting" a schedule the
        # env would override at the next construction anyway
        overlap_modes = (
            (self.overlap,)
            if _os.environ.get(OVERLAP_ENV, "").strip()
            else self._overlap_modes
        )
        plan = self.tuner.choose(
            "ddp_step", grad_bytes,
            wire_dtypes=wire_dtypes, overlap_modes=overlap_modes,
        )
        wd = plan.wire_dtype
        ov = hook_overlap_of(plan.key.path)
        if wd == self.hook.effective_compress() and ov == self.overlap:
            return
        self.hook.compress = wd
        if ov != self.overlap:
            # adopting an overlap schedule re-steers EVERY half that
            # executes it: the hook (bucket-rolling dispatch), the trainer
            # (pipelined scan), and an already-constructed Zero1Optimizer
            # (chunked RS/AG) — a stale optimizer would leave the adopted
            # cell's measurements half-applied, corrupting the very A/B
            # the adoption logic ranks on
            self.overlap = ov
            self.hook.overlap = ov
            if self._zero1_opt is not None:
                self._zero1_opt.overlap = self._zero1_overlap()
                self._zero1_opt._compiled = None
        self.hook.reset_plan()
        self._compiled = None  # recompile with the adopted codec/schedule
        self._scan_cache.clear()

    def _record_gns(self, batch: Any, norms: jnp.ndarray, active_mask) -> None:
        if self._gns is None:
            from adapcc_tpu.measure.gns import GNSEstimator

            world = self.mesh.devices.size
            b_big = jax.tree_util.tree_leaves(batch)[0].shape[0]
            self._gns = GNSEstimator(b_small=max(1, b_big // world), b_big=b_big)
        # partial-world steps break the estimator's batch-size accounting
        # (synced averages only the active ranks), so only full-world steps
        # contribute; in async relay mode the first step after a miss is
        # contaminated too (synced folds in the stragglers' banked previous-
        # batch gradients), so it is skipped and the bank marked drained.
        # Norms stay on device until someone reads `gns`, keeping async
        # dispatch intact (see the host-step comment above).
        full = active_mask is None or bool(np.asarray(active_mask).all())
        contaminated = (not self.bsp) and self._bank_dirty
        if not self.bsp:
            self._bank_dirty = not full
        if full and not contaminated:
            self._gns_pending.append(norms)
            # bound retained device buffers on runs that never read `gns`
            if len(self._gns_pending) > 256:
                self._flush_gns()

    def _flush_gns(self) -> None:
        if self._gns is not None and self._gns_pending:
            pending, self._gns_pending = self._gns_pending, []
            for small, big in np.asarray(jax.device_get(pending)):
                self._gns.update(small, big)

    @property
    def gns(self) -> Optional[Any]:
        """The GNS estimator (flushes buffered per-step norms on access)."""
        self._flush_gns()
        return self._gns

    def reset(self) -> None:
        """Zero the host step counter and drop any banked (deferred)
        gradients, keeping compiled programs.  For harnesses that warm up
        the compile cache on throwaway state before a measured run."""
        self._host_step = 0
        self._deferred = None
        self._residual = None
        self._bank_dirty = False

    # -- re-adaptation ---------------------------------------------------------

    def _program_key(self, strategy: Optional[Strategy] = None) -> tuple:
        """Compiled-step cache key: everything the traced program bakes in
        that can change at runtime — the strategy shape, the wire codec
        (tuner adoption rewrites ``hook.compress``), and the overlap
        schedule.  Two configurations sharing a key replay one program;
        anything else retraces."""
        s = strategy if strategy is not None else self.hook.strategy
        return (s.fingerprint(), self.hook.effective_compress(), self.overlap)

    def rebuild(self, strategy: Strategy) -> None:
        """Swap in a freshly synthesized strategy and recompile the step
        (the reconstruct_topology analog for the training loop).  A
        strategy whose program was already compiled under the current
        codec/overlap (a prewarmed standby, or a swap back after
        recovery) is a cache hit — the swap costs one dict lookup."""
        self.hook.strategy = strategy
        self.hook.reset_plan()
        self._compiled = None
        self._scan_cache.clear()  # scanned programs trace the old schedule too

    # -- elastic plan failover (docs/ELASTIC.md) -------------------------------

    @property
    def recompiles(self) -> int:
        """How many step programs were actually traced+compiled — the
        counter the elastic acceptance test pins: a failover onto a
        prewarmed standby strategy must NOT increment it."""
        return self._build_gen

    def prewarm(
        self,
        strategy: Strategy,
        state: "TrainState",
        batch: Any,
        active_mask: Optional[jnp.ndarray] = None,
    ) -> bool:
        """AOT-compile the step program for a standby ``strategy`` on the
        real state/batch shapes, so a later :meth:`adopt_strategy` is a
        dispatch-time switch with no recompile stall on the failover step.

        One throwaway dispatch traces + compiles the program; its outputs
        are discarded, and the prewarmed program is built WITHOUT donation
        (the caller's live state must survive the warmup dispatch — the
        cost is one extra state copy per step on that program, which a
        degraded epoch tolerates).  Returns False when
        the program was already warm.  Banked modes (async relay, error
        feedback) thread per-step buffers the throwaway dispatch would
        corrupt, so they are rejected here — prewarm before training
        starts, or run those modes with the cold-swap path.
        """
        if not self.bsp or self.error_feedback:
            raise ValueError(
                "prewarm() supports the plain BSP step only: banked modes "
                "(async relay / error feedback) carry per-step buffers a "
                "throwaway warmup dispatch would corrupt"
            )
        self._check_state(state)
        saved_strategy = self.hook.strategy
        saved_donate = self.donate_state
        # the key must resolve under the SWAPPED strategy: with
        # compress="strategy" the effective codec is the standby
        # strategy's synthesized wire_dtype, not the incumbent's
        self.hook.strategy = strategy
        self.donate_state = False
        try:
            key = self._program_key()
            if key in self._program_cache:
                return False
            fn = self._build()
            self._build_gen += 1
            args = [state, batch]
            if self._dynamic_mask:
                if active_mask is None:
                    active_mask = jnp.ones(
                        (self.mesh.devices.size,), dtype=jnp.bool_
                    )
                args.append(active_mask)
            jax.block_until_ready(
                self._first_call(fn, key, "prewarm", self._host_step, *args)
            )
        finally:
            self.hook.strategy = saved_strategy
            self.donate_state = saved_donate
        self._program_cache[key] = fn
        return True

    def attach_supervisor(self, supervisor) -> "DDPTrainer":
        """Hand membership authority to an out-of-band
        :class:`~adapcc_tpu.supervisor.Supervisor` (docs/SUPERVISOR.md):
        every ``step()`` without an explicit ``active_mask`` consumes the
        daemon's last actuated view, and strategy swaps arrive through
        :meth:`adopt_strategy` driven by the daemon — the trainer only
        observes epoch bumps.  Requires a dynamic-mask step (the mask is
        runtime state, so supervision never recompiles)."""
        if supervisor is not None and not self._dynamic_mask:
            raise ValueError(
                "a supervised trainer needs dynamic_mask=True: the "
                "supervisor's world changes arrive as runtime masks, and "
                "a static full-world step could not shrink without a "
                "retrace"
            )
        self._supervisor = supervisor
        return self

    def adopt_strategy(self, strategy: Strategy) -> bool:
        """Hot-swap the training step onto ``strategy``.

        Returns True when the swap hit a prewarmed program (dispatch-time
        cache-key switch — the no-recompile failover the standby cache
        exists for) and False when it fell back to a cold rebuild (an
        unanticipated world shape; the next step pays the compile).
        """
        self.rebuild(strategy)
        # resolved AFTER the swap so a compress="strategy" hook keys on
        # the adopted strategy's codec (exactly what step() will look up)
        return self._program_key() in self._program_cache
