"""Fully-sharded data parallelism (FSDP / ZeRO), the TPU way.

The reference's only first-class strategy is replicated DP (SURVEY §2.3):
every rank holds full params + full optimizer state and allreduces full
gradients (train_ddp.py:35-41).  At modern model sizes that wastes
``(world-1)/world`` of HBM on redundant state.  This module adds the two
standard remedies as first-class strategies, both expressed as shardings on
a ``jax.sharding.Mesh`` axis so XLA schedules the ICI traffic:

1. **FSDP / ZeRO-3 via GSPMD** (:func:`fsdp_shardings`,
   :func:`fsdp_train_step`): every parameter leaf is sharded over the data
   axis along its largest divisible dimension; optimizer state inherits the
   same sharding.  XLA inserts the all-gather before each use and the
   reduce-scatter after each gradient — the scaling-book "weight sharding"
   recipe, zero hand-written collectives.

2. **ZeRO-1** (:class:`Zero1Optimizer`): params stay replicated (so the
   forward is untouched and composes with the adaptive gradient hook), but
   the *optimizer state* lives sharded: gradients are reduce-scattered onto
   a flat ``[N/world]`` shard, the optax update runs on that shard only,
   and the updated parameter slice is all-gathered back.  Optimizer memory
   drops by ``1/world`` and the gradient sync becomes the optimal
   reduce-scatter + all-gather pair (bandwidth-equal to one allreduce).

Both paths are pure functions over (params, opt_state, batch) and compose
with ``jax.jit`` donation for in-place updates.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS


# -- FSDP (ZeRO-3) via GSPMD shardings ----------------------------------------


def _leaf_spec(
    shape: Tuple[int, ...], world: int, min_elems: int, axis_name: str
) -> P:
    """PartitionSpec sharding the largest dim divisible by ``world``.

    Small leaves (biases, layernorm scales) stay replicated — sharding them
    buys nothing and forces XLA to all-gather scalars.
    """
    if not shape or int(np.prod(shape)) < min_elems:
        return P()
    # largest divisible dim wins; ties go to the later (usually output) dim
    best, best_size = None, 0
    for i, d in enumerate(shape):
        if d % world == 0 and d >= best_size:
            best, best_size = i, d
    if best is None:
        return P()
    spec: list = [None] * len(shape)
    spec[best] = axis_name
    return P(*spec)


def fsdp_shardings(
    params: Any,
    mesh: Mesh,
    axis_name: str = RANKS_AXIS,
    min_shard_elems: int = 2**14,
) -> Any:
    """Pytree of ``NamedSharding`` sharding each leaf over the data axis.

    The same tree annotates optimizer state: optax states mirror the param
    tree structure, so mapping the leaf rule over ``tx.init(params)`` gives
    each moment buffer the sharding of its parameter.
    """
    world = mesh.shape[axis_name]

    def one(leaf):
        return NamedSharding(
            mesh, _leaf_spec(jnp.shape(leaf), world, min_shard_elems, axis_name)
        )

    return jax.tree_util.tree_map(one, params)


def shard_fsdp(
    params: Any,
    mesh: Mesh,
    axis_name: str = RANKS_AXIS,
    min_shard_elems: int = 2**14,
) -> Any:
    """Device-put ``params`` into their FSDP shardings (1/world HBM each)."""
    return jax.device_put(
        params, fsdp_shardings(params, mesh, axis_name, min_shard_elems)
    )


def fsdp_train_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = RANKS_AXIS,
    donate: bool = True,
    min_shard_elems: int = 2**14,
) -> Callable:
    """Compile a full FSDP train step: params + optimizer state sharded over
    the data axis, batch sharded over the same axis, XLA-inserted
    all-gather/reduce-scatter over ICI.

    Returns ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    where the sharded layouts are preserved across calls (out_shardings =
    in_shardings, so the update is a stable fixed point under donation).
    """

    return _sharded_train_step(
        loss_fn, tx, mesh,
        lambda tree: fsdp_shardings(tree, mesh, axis_name, min_shard_elems),
        batch_spec=P(axis_name),
        donate=donate,
    )


def _sharded_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    shardings_of: Callable[[Any], Any],
    batch_spec: P,
    donate: bool,
) -> Callable:
    """Shared engine for every GSPMD sharded-state step variant: state lives
    in the layout ``shardings_of`` assigns, out_shardings = in_shardings so
    the update is a stable fixed point under donation."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def compile_for(params: Any, opt_state: Any) -> Callable:
        p_sh = shardings_of(params)
        # optax state mirrors the param tree per-transform, so the same rule
        # tree-maps over it: moment buffers inherit their parameter's layout,
        # scalars (count) fall to replicated
        o_sh = shardings_of(opt_state)
        b_sh = NamedSharding(mesh, batch_spec)
        return jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else (),
        )

    cache: dict = {}

    def stepper(params, opt_state, batch):
        # keyed by tree structure + leaf shapes: a new model layout gets a
        # new program instead of silently reusing stale shardings
        key = _tree_key(params)
        if key not in cache:
            cache[key] = compile_for(params, opt_state)
        return cache[key](params, opt_state, batch)

    return stepper


def _tree_key(tree: Any) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((jnp.shape(l), jnp.result_type(l)) for l in leaves))


# -- FSDP × TP: 2D sharding over a (data, model) mesh -------------------------


def fsdp_tp_shardings(
    params: Any,
    mesh: Mesh,
    tp_rules: Any,
    data_axis: str = "data",
    min_shard_elems: int = 2**14,
) -> Any:
    """2D layout: Megatron TP rules claim their dims over the model axis,
    then FSDP shards the largest *free* divisible dim over the data axis —
    the scaling-book "FSDP + tensor parallelism" composition.  A leaf whose
    only divisible dim is TP-claimed stays 1D-sharded; small leaves get no
    additional data-axis sharding (TP-ruled small leaves keep their TP
    spec, unruled ones stay replicated).
    """
    from adapcc_tpu.parallel.tensor import tree_shardings

    tp = tree_shardings(params, mesh, tp_rules)
    data_size = mesh.shape[data_axis]

    def combine(leaf, tp_sh):
        shape = jnp.shape(leaf)
        spec = list(tp_sh.spec) + [None] * (len(shape) - len(tp_sh.spec))
        if shape and int(np.prod(shape)) >= min_shard_elems:
            best, best_size = None, 0
            for i, d in enumerate(shape):
                if spec[i] is not None:
                    continue
                if d % data_size == 0 and d >= best_size:
                    best, best_size = i, d
            if best is not None:
                spec[best] = data_axis
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(combine, params, tp)


def fsdp_tp_train_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    tp_rules: Any,
    data_axis: str = "data",
    donate: bool = True,
    min_shard_elems: int = 2**14,
) -> Callable:
    """FSDP over ``data_axis`` × tensor parallel per ``tp_rules``: params and
    optimizer state live 2D-sharded, batch shards over the data axis, and XLA
    inserts the per-axis collectives (all-gather on use over data, psum of
    row-parallel partials over model) — one jitted program on one mesh.
    """
    return _sharded_train_step(
        loss_fn, tx, mesh,
        lambda tree: fsdp_tp_shardings(
            tree, mesh, tp_rules, data_axis, min_shard_elems
        ),
        batch_spec=P(data_axis),
        donate=donate,
    )


# -- ZeRO-1: sharded optimizer state over the flat gradient vector ------------


class _FlatMeta(NamedTuple):
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    dtypes: Tuple[Any, ...]
    total: int
    padded: int


def _flatten_meta(params: Any, world: int, align: int = 1) -> _FlatMeta:
    """``align`` rounds the per-rank shard length up to a multiple (the
    Pallas ring kernels move whole VMEM tiles, so the ring path needs
    tile-aligned shards; the XLA path keeps align=1)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    dtypes = tuple(l.dtype for l in leaves)
    total = int(sum(sizes))
    shard = -(-total // world)
    shard = -(-shard // align) * align
    return _FlatMeta(treedef, shapes, sizes, dtypes, total, world * shard)


def _flatten(tree: Any, meta: _FlatMeta, dtype=jnp.float32) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    flat = jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])
    return jnp.pad(flat, (0, meta.padded - meta.total))


def _unflatten(flat: jnp.ndarray, meta: _FlatMeta) -> Any:
    parts = []
    off = 0
    for shape, size, dt in zip(meta.shapes, meta.sizes, meta.dtypes):
        parts.append(flat[off : off + size].reshape(shape).astype(dt))
        off += size
    return jax.tree_util.tree_unflatten(meta.treedef, parts)


def zero1_apply_shard(
    tx: optax.GradientTransformation,
    master: jnp.ndarray,
    opt_state: Any,
    g_shard: jnp.ndarray,
    meta: _FlatMeta,
    axis_name: str,
    ring: bool = False,
    ring_interpret: bool = False,
    ring_chunk_bytes: Optional[int] = None,
    overlap_chunks: int = 1,
):
    """The in-shard ZeRO-1 update cycle, shared by every composition site
    (Zero1Optimizer.apply, zero1_train_step, DDPTrainer(zero1=True)):
    optax update on this rank's flat ``[N/world]`` slice, then one
    ``all_gather`` rebuilds the replicated params.  Runs inside shard_map;
    ``master``/``opt_state`` enter WITHOUT their leading shard dim.

    ``ring=True`` rides the Pallas ICI ring all-gather instead of XLA's
    (the hand-tuned data plane): rank ``r`` then owns chunk ``(r+1) % world``
    (the ring's natural ownership), and the gathered rank-ordered rows are
    rolled back into chunk order before unflattening.  ``ring_chunk_bytes``
    is the staging granularity handed down from the strategy plane (None =
    default; payloads above it stream through HBM staging).

    ``overlap_chunks > 1`` (XLA path only — the Pallas ring streams its own
    chunks) splits the param all-gather into that many independent
    collectives over contiguous shard slices, so XLA's async collectives
    overlap later slices' gathers with the unflatten/cast compute — and, in
    a scanned multi-step program, with the next step's forward — of earlier
    slices (docs/OVERLAP.md §3).  The gathered bytes and their layout are
    identical: chunk ``j`` of every rank lands in the same flat positions,
    so results are bitwise-equal to the single-collective gather.
    """
    updates, opt_state = tx.update(g_shard, opt_state, master)
    master = optax.apply_updates(master, updates)
    if ring:
        from adapcc_tpu.comm.pallas_ring import ring_all_gather_shard

        world = meta.padded // master.size
        gathered = ring_all_gather_shard(
            master, world, axis_name, interpret=ring_interpret,
            chunk_bytes=ring_chunk_bytes,
        )
        # gathered[i] = rank i's payload = chunk (i+1) % world
        flat_p = jnp.roll(gathered, 1, axis=0).reshape(-1)
    elif overlap_chunks > 1:
        from adapcc_tpu.ddp.overlap import even_chunk_bounds

        gathered = [
            lax.all_gather(master[off : off + n], axis_name)  # [world, n]
            for off, n in even_chunk_bounds(master.size, overlap_chunks)
        ]
        flat_p = jnp.concatenate(gathered, axis=1).reshape(-1)
    else:
        flat_p = lax.all_gather(master, axis_name).reshape(-1)
    return master, opt_state, _unflatten(flat_p, meta)


def local_grad_shard(
    flat_g: jnp.ndarray, meta: _FlatMeta, world: int, axis_name: str,
    offset: int = 0,
) -> jnp.ndarray:
    """This rank's slice of an already-replicated flat gradient — a free
    local read, no collective.  ``offset=1`` selects the ring path's chunk
    ownership (rank ``r`` owns chunk ``(r+1) % world``)."""
    shard_len = meta.padded // world
    idx = lax.axis_index(axis_name)
    if offset:
        idx = (idx + offset) % world
    return lax.dynamic_index_in_dim(
        flat_g.reshape(world, shard_len), idx, keepdims=False
    )


class Zero1Optimizer:
    """Optimizer-state-sharded DDP (ZeRO stage 1) over one mesh axis.

    Params stay replicated; the optimizer state is a flat ``[N/world]``
    fp32 shard per rank.  Each step, inside one ``shard_map`` program:

    1. ``psum_scatter`` the flat gradient → this rank's ``[N/world]`` slice
       (bandwidth-optimal: the reduce-scatter half of a ring allreduce);
    2. optax update on the slice against this rank's opt-state shard —
       1/world of the adam moment memory and FLOPs per rank;
    3. ``all_gather`` the updated parameter slice → replicated new params
       (the other half of the ring).

    The fp32 flat master copy also gives mixed-precision training a proper
    master-weight update for bf16 params for free.

    ``ring=True`` swaps both collectives onto the Pallas ICI ring kernels
    (:mod:`adapcc_tpu.comm.pallas_ring`) — the hand-tuned data plane, the
    TPU analog of the reference's CUDA chunk pipeline (trans.cu:58-100).
    The ring's natural chunk ownership (rank ``r`` finishes reduce-scatter
    holding chunk ``(r+1) % world``) is adopted as the shard layout, so no
    extra rotation hop is paid at step time; shards are VMEM-tile aligned.
    Checkpoints of ring and non-ring masters are NOT interchangeable (the
    row→chunk mapping differs).
    """

    def __init__(
        self,
        tx: optax.GradientTransformation,
        mesh: Mesh,
        axis_name: str = RANKS_AXIS,
        ring: bool = False,
        ring_interpret: Optional[bool] = None,
        ring_chunk_bytes: Optional[int] = None,
        wire_dtype: Optional[str] = None,
        tuner: Optional[Any] = None,
        overlap: str = "off",
        overlap_chunk_bytes: Optional[int] = None,
    ) -> None:
        self.tx = tx
        self.mesh = mesh
        self.axis_name = axis_name
        self.world = mesh.shape[axis_name]
        self.ring = ring
        # overlapped collectives (docs/OVERLAP.md §3): "bucket" splits the
        # gradient reduce-scatter and the param all-gather into independent
        # per-chunk collectives at ``overlap_chunk_bytes`` granularity
        # (default: the reference's 4 MB chunk, env-overridable through the
        # ring chunk resolver) so XLA interleaves them with surrounding
        # compute.  Identical bytes, identical layout — checkpoints are
        # unaffected.  The value arrives caller-resolved: DDPTrainer and
        # train_ddp apply the ADAPCC_OVERLAP precedence *before* passing it
        # down, because the env may legally pin "microbatch" for the
        # trainer's scan while this optimizer's collectives stay "off"
        if overlap == "microbatch":
            raise ValueError(
                "Zero1Optimizer has no microbatch axis to pipeline over — "
                "microbatch overlap lives in DDPTrainer's accumulation "
                "scan (overlap='microbatch' there composes with zero1=True)"
            )
        if overlap not in ("off", "bucket"):
            raise ValueError(
                f"overlap={overlap!r}: expected 'off' or 'bucket'"
            )
        self.overlap = overlap
        if self.overlap == "bucket" and ring:
            raise ValueError(
                "overlap='bucket' with ring=True would chunk the Pallas "
                "ring's payload twice: the ring kernel already streams "
                "chunk_bytes-sized tiles (ring_chunk_bytes steers it); "
                "use one chunking plane or the other"
            )
        self.overlap_chunk_bytes = overlap_chunk_bytes
        # measurement-driven chunk choice (adapcc_tpu/tuner): when the ring
        # staging granularity is left open and ADAPCC_TUNER=choose, init()
        # asks the tuner's policy for it (sized to the actual flat master)
        # instead of falling to the default.  Explicit ring_chunk_bytes and
        # the ADAPCC_RING_CHUNK_BYTES env keep their precedence — the tuner
        # only fills the knob nobody pinned.
        self.tuner = tuner
        #: the TunedPlan behind an adopted chunk (None = not tuner-chosen)
        self.tuned_plan = None
        from adapcc_tpu.ops.kernel_mode import resolve_interpret

        self.ring_interpret = resolve_interpret(ring_interpret, "zero1_ring")
        # gradient-sync wire codec (quant registry; None/"off" = payload
        # dtype, ADAPCC_WIRE_DTYPE overrides — the ring_chunk_bytes
        # precedence).  zero1_train_step applies the codec's wire value to
        # each rank's gradient contribution before the reduce-scatter;
        # resolved eagerly so a typo'd codec dies at construction
        from adapcc_tpu.quant import resolve_wire_dtype

        self.wire_dtype = resolve_wire_dtype(wire_dtype)
        #: staging granularity for the ring collectives (strategy plane's
        #: synthesized chunk_bytes; None = default, env-overridable for
        #: sweeps).  Payloads above it ride the HBM-streaming kernel, so
        #: gradient size is bounded by HBM, not VMEM — chunk *layout* is
        #: unaffected (the executed tile divides the shard), so this knob
        #: never invalidates a checkpoint.
        self.ring_chunk_bytes = ring_chunk_bytes
        self._meta: Optional[_FlatMeta] = None
        self._compiled: Optional[Callable] = None

    def _align(self) -> int:
        if not self.ring:
            return 1
        from adapcc_tpu.comm.pallas_ring import _tile_elems

        return _tile_elems(jnp.float32)

    def overlap_chunks(self, shard_len: Optional[int] = None) -> int:
        """How many independent collectives the overlapped RS/AG pair
        splits into: 1 when overlap is off, else the fp32 shard's byte
        count over ``overlap_chunk_bytes`` (env-overridable through the
        ring chunk resolver — one precedence ladder for every chunk knob).
        ``shard_len`` defaults to the initialized flat master's."""
        if self.overlap != "bucket":
            return 1
        if shard_len is None:
            if self._meta is None:
                raise RuntimeError("call init(params) first")
            shard_len = self._meta.padded // self.world
        from adapcc_tpu.ddp.overlap import overlap_chunk_count

        return overlap_chunk_count(int(shard_len) * 4, self.overlap_chunk_bytes)

    def tuning_key(self):
        """The tuning-database cell this optimizer's ring collectives
        execute, or None off the ring path / before ``init``.  Callers
        timing zero1 steps record into THIS key — the tuner-chosen cell
        when the tuner picked the chunk, else the executed configuration
        via the kernel's own planner — so the measurements land where the
        next ``init()``'s ``choose("zero1_ring", ...)`` will look (the
        loop closes across runs through the persisted database)."""
        if self.tuner is None or self._meta is None or not self.ring:
            return None
        if self.tuned_plan is not None:
            return self.tuned_plan.key
        from adapcc_tpu.comm.pallas_ring import plan_ring_schedule
        from adapcc_tpu.tuner.policy import NO_CHUNK

        plan = plan_ring_schedule(
            self._meta.padded, jnp.float32, self.world, self.ring_chunk_bytes
        )
        return self.tuner.key_for(
            "zero1_ring", self._meta.padded * 4, plan.path,
            # same key vocabulary as the candidate grid: vmem is one cell
            NO_CHUNK if plan.path == "vmem" else plan.chunk_bytes, "off",
        )

    def init(self, params: Any) -> Tuple[jnp.ndarray, Any]:
        """Returns ``(flat_master [world, N/world] fp32, opt_state shard)``.

        Both carry a leading ``[world]`` dim sharded over the mesh axis, so
        each device holds exactly its slice.  In ring mode row ``r`` holds
        chunk ``(r+1) % world`` (the ring's ownership); the XLA path keeps
        the identity layout.
        """
        meta = self._meta = _flatten_meta(params, self.world, self._align())
        self._compiled = None  # re-init with a new tree invalidates the program
        if (
            self.ring
            and self.ring_chunk_bytes is None
            and self.tuner is not None
            and self.tuner.choosing
        ):
            # the ring collectives move the whole padded flat master; size
            # the cell to that payload.  "zero1_ring" cells carry only the
            # chunk axis (no codec — the wire dtype is a separate knob)
            self.tuned_plan = self.tuner.choose("zero1_ring", meta.padded * 4)
            self.ring_chunk_bytes = self.tuned_plan.chunk_bytes
        flat = _flatten(params, meta)
        shard_len = meta.padded // self.world
        master = flat.reshape(self.world, shard_len)
        if self.ring:
            # row r ← chunk (r+1) % world
            master = jnp.roll(master, -1, axis=0)
        opt_state = jax.vmap(self.tx.init)(master)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return (
            jax.device_put(master, sharding),
            jax.device_put(opt_state, sharding),
        )

    def _build(self) -> Callable:
        meta = self._meta
        world, axis, tx = self.world, self.axis_name, self.tx
        shard_len = meta.padded // world

        ring, ring_interpret = self.ring, self.ring_interpret
        ring_chunk_bytes = self.ring_chunk_bytes
        overlap_chunks = self.overlap_chunks(shard_len)

        def per_shard(master, opt_state, grads_tree):
            # strip the [1] shard dim shard_map leaves on the leading axis
            master = master[0]
            opt_state = jax.tree_util.tree_map(lambda x: x[0], opt_state)
            # grads enter replicated (in_spec P()): every rank already holds
            # the full synced gradient, so its shard is a free local slice —
            # no collective needed on this path (ring ownership = offset 1)
            g_shard = local_grad_shard(
                _flatten(grads_tree, meta), meta, world, axis,
                offset=1 if ring else 0,
            )
            master, opt_state, new_params = zero1_apply_shard(
                tx, master, opt_state, g_shard, meta, axis,
                ring=ring, ring_interpret=ring_interpret,
                ring_chunk_bytes=ring_chunk_bytes,
                overlap_chunks=overlap_chunks,
            )
            return (
                master[None],
                jax.tree_util.tree_map(lambda x: x[None], opt_state),
                new_params,
            )

        fn = jax.shard_map(
            per_shard,
            mesh=self.mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis), P()),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0, 1))

    # -- checkpoint layout tagging ---------------------------------------------

    #: key under which the layout tag rides in ``TrainCheckpointState.extra``
    LAYOUT_KEY = "zero1_layout"

    def layout_metadata(self) -> Dict[str, Any]:
        """The master/opt-state layout this optimizer produces: ring mode
        permutes chunk ownership (row ``r`` holds chunk ``(r+1) % world``)
        and tile-aligns shards, so ring and non-ring checkpoints are NOT
        interchangeable — the tag makes a flipped ``--zero1-ring`` resume
        fail loudly instead of silently loading permuted master weights."""
        return {"ring": self.ring, "align": self._align(), "world": self.world}

    def checkpoint_extra(self, extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """``TrainCheckpointState.extra`` payload with the layout recorded."""
        out = dict(extra or {})
        out[self.LAYOUT_KEY] = self.layout_metadata()
        return out

    def validate_checkpoint_extra(self, extra: Optional[Dict[str, Any]]) -> None:
        """Raise unless the checkpoint's recorded layout matches this
        optimizer's.  A checkpoint with no tag is also rejected: an untagged
        ZeRO-1 master is exactly the silent-corruption hazard the tag
        exists to close."""
        recorded = (extra or {}).get(self.LAYOUT_KEY)
        if recorded is None:
            raise ValueError(
                "checkpoint has no zero1 layout tag (extra["
                f"{self.LAYOUT_KEY!r}]); refusing to restore a ZeRO-1 master "
                "of unknown chunk layout — re-save with "
                "Zero1Optimizer.checkpoint_extra()"
            )
        expected = self.layout_metadata()
        mismatches = {
            k: (recorded.get(k), v)
            for k, v in expected.items()
            if recorded.get(k) != v
        }
        if mismatches:
            detail = ", ".join(
                f"{k}: checkpoint={a!r} vs optimizer={b!r}"
                for k, (a, b) in sorted(mismatches.items())
            )
            raise ValueError(
                f"ZeRO-1 checkpoint layout mismatch ({detail}); restoring "
                "would load chunk-permuted master weights — resume with the "
                "matching ring/world configuration or re-shard offline"
            )

    def restore(self, ckpt: Any) -> Tuple[jnp.ndarray, Any]:
        """Validated restore from a :class:`TrainCheckpointState`-shaped
        object whose ``opt_state`` is the ``(master, opt shard)`` pair and
        whose ``extra`` carries the layout tag; returns the pair placed on
        this optimizer's sharding."""
        self.validate_checkpoint_extra(getattr(ckpt, "extra", None))
        master, opt_state = ckpt.opt_state
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return (
            jax.device_put(jnp.asarray(master), sharding),
            jax.device_put(opt_state, sharding),
        )

    def apply(
        self, master: jnp.ndarray, opt_state: Any, grads: Any
    ) -> Tuple[jnp.ndarray, Any, Any]:
        """One sharded update from a *replicated* (already-synced) gradient
        pytree — the layout the DDP hook hands back.  Returns ``(master,
        opt_state, new_params)`` with ``new_params`` replicated in the
        original dtypes.  For per-rank unsynced gradients use
        :func:`zero1_train_step`, whose program computes them in-shard."""
        if self._meta is None:
            raise RuntimeError("call init(params) first")
        if self._compiled is None:
            self._compiled = self._build()
        return self._compiled(master, opt_state, grads)


def zero1_train_step(
    loss_fn: Callable[[Any, Any], jnp.ndarray],
    opt: Zero1Optimizer,
    mesh: Mesh,
) -> Callable:
    """Full ZeRO-1 DDP step: per-rank grads from the sharded batch, then the
    reduce-scatter / sharded-update / all-gather cycle — one jitted program.

    ``step(params, master, opt_state, batch) -> (params, master, opt_state,
    losses)``; ``batch`` leading dim is global and sharded over ``opt``'s
    mesh axis.  ``losses`` is the gathered ``[world]`` per-rank loss vector
    (``losses.mean()`` is the global batch loss when ``loss_fn`` is a mean);
    gradient semantics are the mean over ranks, matching DDP averaging.
    """
    meta_holder: dict = {}
    axis_name = opt.axis_name

    def build(params):
        meta = _flatten_meta(params, opt.world, opt._align())
        world = opt.world
        shard_len = meta.padded // world
        tx = opt.tx
        ring, ring_interpret = opt.ring, opt.ring_interpret
        ring_chunk_bytes = opt.ring_chunk_bytes
        overlap_chunks = opt.overlap_chunks(shard_len)

        if opt.wire_dtype != "off":
            from adapcc_tpu.quant import get_codec

            codec_apply = get_codec(opt.wire_dtype).apply
        else:
            codec_apply = None

        def per_shard(params, master, opt_state, batch):
            master = master[0]
            opt_state = jax.tree_util.tree_map(lambda x: x[0], opt_state)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            # unsynced per-rank grads: the reduce-scatter both averages and
            # slices (the bandwidth-optimal half of a ring allreduce)
            flat_g = _flatten(grads, meta) / world
            if codec_apply is not None:
                # wire codec on the contribution (value semantics): the
                # scattered sum is the sum of quantized per-rank gradients,
                # matching the quantized ring's accumulation contract
                flat_g = codec_apply(flat_g)
            if ring:
                from adapcc_tpu.comm.pallas_ring import ring_reduce_scatter_shard

                # the Pallas ring leaves rank r with reduced chunk
                # (r+1) % world — exactly this mode's master/opt layout
                g_shard = ring_reduce_scatter_shard(
                    flat_g, world, axis_name, interpret=ring_interpret,
                    chunk_bytes=ring_chunk_bytes,
                )
            elif overlap_chunks > 1:
                # per-bucket rolling reduce-scatter (docs/OVERLAP.md §3):
                # each contiguous shard slice scatters as an independent
                # collective XLA can interleave with the flatten/codec
                # compute and with the other slices.  Block r of chunk
                # [:, off:off+n].reshape(-1) is row r's slice, so the
                # concatenated shards keep the identity layout — bitwise
                # equal to the single psum_scatter
                from adapcc_tpu.ddp.overlap import even_chunk_bounds

                g2d = flat_g.reshape(world, shard_len)
                g_shard = jnp.concatenate([
                    lax.psum_scatter(
                        g2d[:, off : off + n].reshape(-1), axis_name,
                        scatter_dimension=0, tiled=True,
                    )
                    for off, n in even_chunk_bounds(shard_len, overlap_chunks)
                ])
            else:
                g_shard = lax.psum_scatter(
                    flat_g.reshape(world, shard_len), axis_name,
                    scatter_dimension=0, tiled=False,
                )
            master, opt_state, new_params = zero1_apply_shard(
                tx, master, opt_state, g_shard, meta, axis_name,
                ring=ring, ring_interpret=ring_interpret,
                ring_chunk_bytes=ring_chunk_bytes,
                overlap_chunks=overlap_chunks,
            )
            return (
                new_params,
                master[None],
                jax.tree_util.tree_map(lambda x: x[None], opt_state),
                loss[None],
            )

        fn = jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name), P(axis_name)),
            out_specs=(P(), P(axis_name), P(axis_name), P(axis_name)),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(1, 2))

    def stepper(params, master, opt_state, batch):
        for leaf in jax.tree_util.tree_leaves(batch):
            shape = getattr(leaf, "shape", None)
            dim = shape[0] if shape else None
            if dim is not None and dim % opt.world:
                raise ValueError(
                    f"zero1_train_step: batch leading dim {dim} does not "
                    f"divide world={opt.world}; pad or resize the global "
                    "batch (an indivisible batch would otherwise fail with "
                    "an opaque shard_map/GSPMD error)"
                )
        key = _tree_key(params)
        if key not in meta_holder:
            meta_holder[key] = build(params)
        return meta_holder[key](params, master, opt_state, batch)

    return stepper
