"""Pallas ring collectives over ICI: the hand-tuned data plane.

The reference's performance path is hand-written CUDA: persistent per-tree
threads pushing 4 MB chunks through pre-shared IPC staging buffers with
event/flag handshakes (csrc/allreduce.cu:568-654, trans.cu:58-100).  The TPU
analog is a Pallas kernel that drives the ICI fabric directly with
``make_async_remote_copy`` RDMA — this module provides ring
reduce-scatter / all-gather / allreduce kernels with:

- **chunked pipelining**: the buffer is split into ``world`` chunks walking
  the ring, the Pallas version of the reference's chunk pipeline;
- **double-buffered staging** (2 comm slots), the analog of the reference's
  per-sibling staging slots;
- **credit-based flow control**: a receiver returns a capacity credit to its
  upstream neighbor after consuming a slot, so a fast sender can never
  clobber an unconsumed slot even on long rings — replacing the reference's
  shm bool + IPC-event handshake (trans.cu:73-98) with semaphores;
- **neighbor barrier** on entry so no device writes into a peer that has not
  allocated its buffers yet.

Two execution paths share those mechanics, selected per payload by
:func:`plan_ring_schedule`:

- **vmem** — the whole payload is VMEM-resident (input + work + comm slots),
  the right program when everything fits in one ``chunk_bytes`` staging
  budget;
- **hbm-stream** — the payload lives in HBM (``pl.ANY``) and a grid over
  (ring step × tile) streams ``chunk_bytes``-sized tiles through fixed VMEM
  staging: local DMA in → remote RDMA → accumulate → local DMA out, with the
  credit protocol carried across grid steps.  This is the TPU analog of the
  reference's fixed ``MAX_BUF_SIZE`` staging design (include/init.h:14-25):
  collective payload size is bounded by HBM, not by on-device scratch.

The tile granularity is the strategy plane's synthesized ``chunk_bytes``
(``Strategy.chunk_bytes`` → ``engine.ring_*`` → here), overridable for
sweeps with ``ADAPCC_RING_CHUNK_BYTES``.  The executed tile is a
near-budget whole-VMEM-tile size covering the per-rank chunk with minimal
zero padding (< one tile per chunk, sliced back out by the wrappers), so
the external chunk layout (and with it the ZeRO-1 shard layout) is
byte-identical across every chunk size — which also makes results
bit-identical: each element sees the same adds in the same ring order
regardless of tiling.

Everything is testable off-hardware: ``interpret=True`` runs the kernels
under the Pallas TPU interpreter on a virtual CPU mesh **with race detection
enabled** — a sanitizer the reference never had (SURVEY §5.2).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.primitives import DEFAULT_CHUNK_BYTES

#: VMEM tiles are (sublanes, 128) with sublanes scaling inversely with item
#: width: fp32 → (8, 128), bf16 → (16, 128), int8/fp8 → (32, 128).  Chunks
#: are padded to whole tiles of the payload dtype (``_tile_elems``).
_LANES = 128

#: env override for the ring staging granularity (chunk-size sweeps); wins
#: over both the caller's value and the strategy's synthesized chunk_bytes
RING_CHUNK_ENV = "ADAPCC_RING_CHUNK_BYTES"

#: env gate for the fused wire-codec kernels (A/B vs the unfused quantized
#: ppermute ring): ``auto`` (default) fuses whenever the plan supports it,
#: ``off`` forces the quant-ring reroute, ``on`` demands the fused path and
#: fails loudly where it cannot run.  Malformed → loud error (the
#: ADAPCC_MERGE_ROUNDS policy: a typo must not silently invalidate an A/B).
FUSED_WIRE_ENV = "ADAPCC_FUSED_WIRE"

FUSED_WIRE_MODES = ("auto", "on", "off")

#: wire dtypes the fused kernels speak, with their wire-array itemsize.
#: "off" is not fused (the plain kernels ship the payload dtype); other
#: registry codecs reroute to the unfused quantized ppermute ring.
_FUSED_WIRE_ITEMSIZE = {"bf16": 2, "int8": 1}


#: VMEM of one TPU v5e TensorCore (128 MiB; Google Cloud "TPU v5e"
#: system architecture), the compiler's default scoped limit there, and
#: the room a ring plan leaves for Mosaic's own temporaries (the fp32
#: upcast of a narrow accumulate, codec block math) on top of its buffers.
_VMEM_CAPACITY_BYTES = 128 * 1024 * 1024
_DEFAULT_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
_COMPILER_STACK_BYTES = 4 * 1024 * 1024


def _tile_elems(dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    return _LANES * sublanes


def resolve_fused_wire() -> str:
    """The fused-wire gate in force (``auto`` | ``on`` | ``off``)."""
    env = os.environ.get(FUSED_WIRE_ENV)
    if env is None or not env.strip():
        return "auto"
    mode = env.strip().lower()
    if mode not in FUSED_WIRE_MODES:
        raise ValueError(
            f"{FUSED_WIRE_ENV}={env!r}: expected one of "
            f"{'|'.join(FUSED_WIRE_MODES)}"
        )
    return mode


def fused_wire_unsupported_reason(
    dtype, wire_dtype: str, block_size: Optional[int] = None
) -> Optional[str]:
    """Why the fused codec kernels cannot run this configuration, or None
    when they can.  The one support funnel the engine, the wrappers, and
    the tuner's candidate grid all consult — so a candidate cell can never
    claim a fused path the data plane would not run.

    The codec math is defined on fp32 payloads (quant/codec.py), and the
    in-kernel block view needs whole 128-lane rows per block nested inside
    every staging tile: ``block_size`` must be a multiple of 128 whose row
    count divides the fp32 sublane tile (8 rows) — {128, 256, 512, 1024}.
    """
    if wire_dtype == "off":
        return "wire_dtype=off has no codec to fuse (the plain kernels ship fp32)"
    if wire_dtype not in _FUSED_WIRE_ITEMSIZE:
        return (
            f"wire_dtype={wire_dtype!r} has no fused kernel "
            f"(fused codecs: {'|'.join(sorted(_FUSED_WIRE_ITEMSIZE))})"
        )
    if jnp.dtype(dtype) != jnp.float32:
        return (
            f"fused wire codecs are defined on float32 payloads, got "
            f"{jnp.dtype(dtype).name} (quant/codec.py block semantics)"
        )
    if wire_dtype == "int8":
        if block_size is None:
            block_size = _default_block_size()
        rows = block_size // _LANES
        if block_size % _LANES or rows < 1 or 8 % rows:
            return (
                f"int8 block_size={block_size} cannot tile VMEM staging: "
                f"need a multiple of {_LANES} whose {_LANES}-lane row count "
                "divides the fp32 sublane tile (8) — one of 128|256|512|1024"
            )
    return None


def _default_block_size() -> int:
    from adapcc_tpu.quant.codec import DEFAULT_BLOCK_SIZE

    return DEFAULT_BLOCK_SIZE


def fused_ring_dispatch_reason(
    dtype, wire_dtype: str, block_size: Optional[int] = None
) -> Optional[str]:
    """Why a dispatch cannot take the fused wire path HERE (env gate,
    kernel support, codec geometry) — None when it can.  Under
    ``ADAPCC_FUSED_WIRE=on`` any reason becomes a loud error instead of a
    reroute: the operator demanded the fused kernel, a silent fallback
    would invalidate the A/B."""
    mode = resolve_fused_wire()
    if mode == "off":
        reason: Optional[str] = f"{FUSED_WIRE_ENV}=off pins the unfused path"
    else:
        reason = fused_wire_unsupported_reason(dtype, wire_dtype, block_size)
    if reason is not None and mode == "on":
        raise ValueError(
            f"{FUSED_WIRE_ENV}=on but the fused wire path cannot run: {reason}"
        )
    return reason


_REROUTE_NOTED: set = set()


def note_quant_reroute(wire_dtype: str, reason: str) -> None:
    """One-time (per process, per reason) stderr note that a codec dispatch
    abandoned the staged Pallas kernel for the XLA ppermute quant ring —
    operators reading throughput must know which data plane produced it."""
    key = (wire_dtype, reason)
    if key in _REROUTE_NOTED:
        return
    _REROUTE_NOTED.add(key)
    import sys

    print(
        f"adapcc: wire_dtype={wire_dtype} ring collective rerouted off the "
        f"staged Pallas kernel onto the unfused ppermute quant ring "
        f"(impl=quant_ring): {reason}",
        file=sys.stderr,
    )


def _scale_rows(n_blocks: int) -> int:
    """Rows of the fp32 scale side-channel tile holding ``n_blocks`` per-
    block scales: whole 128-lane rows, padded to the fp32 sublane tile so
    the slot is itself a legal VMEM tile."""
    rows = -(-n_blocks // _LANES)
    return -(-rows // 8) * 8


def _interpret_params(interpret):
    if interpret is True:
        return pltpu.InterpretParams(detect_races=True)
    return interpret  # False or a caller-provided InterpretParams


def resolve_chunk_bytes(chunk_bytes: Optional[int] = None) -> int:
    """The staging granularity actually in force: the ``ADAPCC_RING_CHUNK_
    BYTES`` sweep override wins, then the caller's (synthesized) value, then
    the default.  A malformed override raises — a typo silently falling back
    to the default would invalidate a chunk-size sweep (same policy as
    ADAPCC_MERGE_ROUNDS)."""
    env = os.environ.get(RING_CHUNK_ENV)
    if env is not None and env.strip():
        try:
            value = int(env)
        except ValueError:
            raise ValueError(
                f"{RING_CHUNK_ENV}={env!r}: expected a positive byte count"
            ) from None
        if value <= 0:
            raise ValueError(
                f"{RING_CHUNK_ENV}={env!r}: expected a positive byte count"
            )
        return value
    if chunk_bytes is not None:
        if chunk_bytes <= 0:
            raise ValueError(f"chunk_bytes must be positive, got {chunk_bytes}")
        return int(chunk_bytes)
    return DEFAULT_CHUNK_BYTES


@dataclass(frozen=True)
class RingSchedule:
    """The executed ring schedule — the observable contract for traces,
    benchmarks, and tests: which path ran, at what staging granularity,
    under which wire codec."""

    path: str              #: "vmem" | "hbm-stream"
    world: int
    steps: int             #: ring steps (RS + AG walks)
    chunk_bytes: int       #: requested staging budget (resolved)
    stage_bytes: int       #: executed tile bytes (near-budget, minimal padding)
    n_tiles: int           #: tiles per ring step on the hbm-stream path
    payload_bytes: int     #: caller bytes before padding
    padded_bytes: int      #: world × tile-padded chunk bytes
    dtype: str = "float32"
    #: wire codec fused into the kernels ("off" = the plain fp32 kernels)
    wire_dtype: str = "off"
    #: int8 quantization block (elements per fp32 scale); 0 when no blocks
    block_size: int = 0
    #: bytes of one staged *wire* tile (what each RDMA actually ships);
    #: equals ``stage_bytes`` on the unfused path
    wire_stage_bytes: int = 0
    #: bytes of one fp32 scale side-channel tile (int8 plans only)
    scale_slot_bytes: int = 0

    @property
    def scale_bytes(self) -> int:
        """Total scale side-channel VMEM the kernel allocates: one send
        slot + two comm slots, plus (vmem path) the per-chunk scale store
        the all-gather forwards bits from.  Zero off the int8 path — this
        is exactly what ``vmem_bound_bytes`` grows by on int8 plans."""
        if self.scale_slot_bytes == 0:
            return 0
        slots = 3 + (self.world if self.path == "vmem" else 0)
        return slots * self.scale_slot_bytes

    @property
    def vmem_bound_bytes(self) -> int:
        """Peak VMEM the data buffers need.  Unfused: the whole payload
        three times over (pallas input + output + work scratch) plus 2 comm
        slots on the vmem path, 4 staging tiles (1 send + 1 accumulate +
        2 comm) on the stream path.  Fused plans stage the *wire* arrays
        (1 send + 2 comm slots at wire density) next to the fp32 staging,
        plus the scale side channel (:attr:`scale_bytes`)."""
        chunk = self.padded_bytes // self.world
        if self.wire_dtype == "off":
            if self.path == "vmem":
                return 3 * self.padded_bytes + 2 * chunk
            return 4 * self.stage_bytes
        if self.path == "vmem":
            return 3 * self.padded_bytes + 3 * self.wire_stage_bytes + self.scale_bytes
        return 2 * self.stage_bytes + 3 * self.wire_stage_bytes + self.scale_bytes

    @property
    def vmem_limit_bytes(self) -> int:
        """The scoped-VMEM limit the kernel asks Mosaic for: the data
        buffers (:attr:`vmem_bound_bytes`) plus the compiler's own stack.
        The compiler's default (16 MiB on v5e) leaves a 4 × 4 MiB fp32
        stream plan zero margin and refuses the same plan in bf16, whose
        accumulate upcasts to fp32 (no bf16 VPU on v5e) through compiler
        temporaries the data-buffer bound cannot see.  Stating the limit
        from the plan makes the accounting a contract: a plan the chip
        cannot hold fails here, in Python, not inside Mosaic."""
        limit = self.vmem_bound_bytes + _COMPILER_STACK_BYTES
        if limit > _VMEM_CAPACITY_BYTES:
            raise ValueError(
                f"ring plan needs {limit} bytes of VMEM "
                f"({self.path}, stage {self.stage_bytes} B, wire "
                f"{self.wire_dtype}); the chip has {_VMEM_CAPACITY_BYTES} — "
                "lower chunk_bytes"
            )
        return max(limit, _DEFAULT_SCOPED_VMEM_BYTES)

    def to_row(self) -> dict:
        return {
            "ring_path": self.path,
            "chunk_bytes": self.chunk_bytes,
            "stage_bytes": self.stage_bytes,
            "n_tiles": self.n_tiles,
            "steps": self.steps,
            "world": self.world,
            "payload_bytes": self.payload_bytes,
            "padded_bytes": self.padded_bytes,
            "wire_dtype": self.wire_dtype,
            "wire_stage_bytes": self.wire_stage_bytes,
            "scale_slot_bytes": self.scale_slot_bytes,
        }


def _stage_rows_for(chunk_rows: int, sublanes: int, budget_bytes: int, row_bytes: int) -> int:
    """Near-budget whole-tile staging size with minimal padding: the chunk
    is covered by ``n = ceil(k / target)`` tiles of ``s = ceil(k / n)``
    native tiles each — the smallest tile achieving the minimal tile count,
    so zero-padding waste is bounded by ``n − 1`` native tiles per chunk
    (< one staging tile) instead of collapsing to single-tile staging when
    the chunk's tile count has no divisor near the budget (e.g. a prime
    count).  When the budget divides the chunk exactly, this is the budget
    itself and padding is zero.  The wrappers slice the padding back out,
    so the external chunk layout (and the ZeRO-1 shard layout built on it)
    is identical on both paths, for every chunk size."""
    k = chunk_rows // sublanes  # chunk is tile-aligned by construction
    target = max(1, budget_bytes // (row_bytes * sublanes))
    n = -(-k // target)
    return -(-k // n) * sublanes


def _wire_geometry(stage_rows: int, wire_dtype: str, block_size: int):
    """(wire_stage_bytes, scale_slot_bytes) for one ``[stage_rows, 128]``
    fp32 staging tile under a fused codec."""
    wire_stage = stage_rows * _LANES * _FUSED_WIRE_ITEMSIZE[wire_dtype]
    if wire_dtype != "int8":
        return wire_stage, 0
    n_blocks = stage_rows * _LANES // block_size
    return wire_stage, _scale_rows(n_blocks) * _LANES * 4


def plan_ring_schedule(
    nelems: int,
    dtype,
    world: int,
    chunk_bytes: Optional[int] = None,
    rs: bool = True,
    ag: bool = True,
    wire_dtype: str = "off",
    block_size: Optional[int] = None,
) -> RingSchedule:
    """Pure planning: path selection + executed tile size for a ring
    collective over ``nelems`` elements of ``dtype`` (total payload across
    the ``world`` ring chunks).

    Selection rule: the **vmem** path runs when the whole padded payload
    fits inside one ``chunk_bytes`` staging budget ("payloads under one
    chunk" — its VMEM need is then bounded by ~3× the budget); anything
    larger takes the **hbm-stream** path, whose VMEM need is a fixed set of
    staging tiles regardless of payload size.

    ``wire_dtype`` ≠ "off" plans the fused codec kernels: the staging
    budget then also covers the fp32 scale vectors an int8 tile carries
    (the scale side channel), and the plan records the wire/scale slot
    geometry (:attr:`RingSchedule.wire_stage_bytes` /
    :attr:`RingSchedule.scale_slot_bytes`) so ``vmem_bound_bytes`` accounts
    every buffer the fused kernel actually allocates.  The external chunk
    layout is the payload dtype's on every path and codec — wire density
    never changes element→chunk assignment, so ZeRO-1 shard layouts are
    codec-independent.
    """
    dtype = jnp.dtype(dtype)
    if wire_dtype != "off":
        if block_size is None:
            block_size = _default_block_size()
        reason = fused_wire_unsupported_reason(dtype, wire_dtype, block_size)
        if reason is not None:
            raise ValueError(f"cannot plan a fused wire ring: {reason}")
    itemsize = dtype.itemsize
    tile = _tile_elems(dtype)
    sublanes = tile // _LANES
    chunk = -(-max(1, int(nelems)) // max(1, world))  # ceil elems per rank
    chunk = -(-chunk // tile) * tile                  # whole dtype tiles
    padded_bytes = world * chunk * itemsize
    budget = resolve_chunk_bytes(chunk_bytes)
    steps = (world - 1 if rs else 0) + (world - 1 if ag else 0)
    fused = wire_dtype != "off"
    blk = int(block_size) if fused and wire_dtype == "int8" else 0
    if blk:
        # int8 kernels see one codec block per row: staging tiles are whole
        # fp32 tiles of *block rows* (8 blocks), so a chunk that is not is
        # zero-padded inside the dispatch (sliced back out, like the stream
        # path's tile padding — the external chunk layout never moves)
        sublanes *= blk // _LANES
    chunk_rows = -(-(chunk // _LANES) // sublanes) * sublanes
    if world == 1 or padded_bytes <= budget:
        wire_stage, scale_slot = (
            _wire_geometry(chunk_rows, wire_dtype, blk) if fused else (0, 0)
        )
        return RingSchedule(
            path="vmem", world=world, steps=steps, chunk_bytes=budget,
            stage_bytes=chunk_rows * _LANES * itemsize, n_tiles=1,
            payload_bytes=int(nelems) * itemsize,
            padded_bytes=world * chunk_rows * _LANES * itemsize,
            dtype=dtype.name, wire_dtype=wire_dtype, block_size=blk,
            wire_stage_bytes=wire_stage, scale_slot_bytes=scale_slot,
        )
    # the staging budget covers what one tile actually keeps in VMEM: the
    # payload row plus, on int8 plans, its amortized fp32 scale bytes (one
    # scale per block_size elements; ceil so block 1024's fraction of a
    # byte per row still counts) — the wire_dtype-aware tile budget
    row_bytes = _LANES * itemsize
    if blk:
        row_bytes += -(-(_LANES * 4) // blk)
    stage_rows = _stage_rows_for(chunk_rows, sublanes, budget, row_bytes)
    n_tiles = -(-chunk_rows // stage_rows)
    wire_stage, scale_slot = (
        _wire_geometry(stage_rows, wire_dtype, blk) if fused else (0, 0)
    )
    return RingSchedule(
        path="hbm-stream", world=world, steps=steps, chunk_bytes=budget,
        stage_bytes=stage_rows * _LANES * itemsize,
        n_tiles=n_tiles,
        payload_bytes=int(nelems) * itemsize,
        # the kernel's working footprint: each chunk zero-padded to whole
        # staging tiles (the wrappers slice the padding back out)
        padded_bytes=world * n_tiles * stage_rows * _LANES * itemsize,
        dtype=dtype.name, wire_dtype=wire_dtype, block_size=blk,
        wire_stage_bytes=wire_stage, scale_slot_bytes=scale_slot,
    )


# --------------------------------------------------------------------------- #
# kernel bodies
# --------------------------------------------------------------------------- #

def _ring_kernel(
    x_ref,
    out_ref,
    work,
    comm,
    send_sem,
    recv_sem,
    cap_sem,
    *,
    world: int,
    axis_name: str,
    do_reduce_scatter: bool,
    do_all_gather: bool,
):
    """VMEM-resident unidirectional ring walk: reduce-scatter phase then
    all-gather phase.

    ``x_ref``/``work`` are ``[world, S, 128]`` (chunk-major); ``comm`` is the
    ``[2, S, 128]`` double-buffered staging area written by the left
    neighbor's RDMA.
    """
    my_id = lax.axis_index(axis_name)
    right = (my_id + 1) % world
    left = (my_id + world - 1) % world

    # entry barrier with both neighbors (they write into our comm buffer)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)

    work[...] = x_ref[...]

    n_rs = world - 1 if do_reduce_scatter else 0
    n_ag = world - 1 if do_all_gather else 0
    total_steps = n_rs + n_ag

    for step in range(total_steps):
        slot = step % 2
        in_rs = step < n_rs
        if in_rs:
            send_idx = (my_id + world - step) % world
            recv_idx = (my_id + world - step - 1) % world
        else:
            ag = step - n_rs
            # after RS each rank owns the fully reduced chunk (my_id + 1);
            # without RS (pure all-gather) it owns chunk my_id
            own = 1 if do_reduce_scatter else 0
            send_idx = (my_id + world + own - ag) % world
            recv_idx = (my_id + world + own - ag - 1) % world

        # flow control: slot `slot` in the right neighbor was last written at
        # step-2; wait for the credit it returns after consuming that write
        if step >= 2:
            pltpu.semaphore_wait(cap_sem, 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=work.at[send_idx],
            dst_ref=comm.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()  # outbound sent AND left neighbor's chunk landed

        if in_rs:
            work[recv_idx] = work[recv_idx] + comm[slot]
        else:
            work[recv_idx] = comm[slot]

        # return a capacity credit upstream: slot is free for reuse
        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left)

    # drain outstanding credits so no signal outlives the kernel
    tail = min(2, total_steps)
    for _ in range(tail):
        pltpu.semaphore_wait(cap_sem, 1)
    out_ref[...] = work[...]


def _stream_ring_kernel(
    x_ref,
    out_ref,
    send_stage,
    acc,
    comm,
    local_sem,
    send_sem,
    recv_sem,
    cap_sem,
    *,
    world: int,
    axis_name: str,
    do_reduce_scatter: bool,
    do_all_gather: bool,
    n_tiles: int,
    stage_rows: int,
    total_iters: int,
):
    """HBM-streaming ring walk: grid = (ring step, tile within the chunk).

    ``x_ref``/``out_ref`` are HBM-resident ``[world, R, 128]``; ``out_ref``
    doubles as the work buffer (seeded from ``x_ref`` at the first grid
    iteration).  Each grid iteration moves one ``[stage_rows, 128]`` tile:
    local DMA stages the outbound tile into VMEM, one RDMA ships it to the
    right neighbor's double-buffered ``comm`` slot, and the landed inbound
    tile is folded back into HBM (accumulate during reduce-scatter, adopt
    during all-gather).  The credit protocol is the VMEM kernel's, carried
    across grid steps over the flattened (step × tile) counter: slot ``i %
    2`` is reused only after the downstream neighbor's credit from
    iteration ``i − 2`` arrives, so a fast sender can never clobber an
    unconsumed staging slot — the reference's fixed-staging flow control
    (trans.cu:73-98) at grid scope.
    """
    step = pl.program_id(0)
    tile = pl.program_id(1)
    it = step * n_tiles + tile
    my_id = lax.axis_index(axis_name)
    right = (my_id + 1) % world
    left = (my_id + world - 1) % world

    n_rs = world - 1 if do_reduce_scatter else 0

    @pl.when(it == 0)
    def _enter():
        # entry barrier with both neighbors, then seed the HBM work buffer
        # (out_ref) from the input — the one whole-payload DMA of the path
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right)
        pltpu.semaphore_wait(barrier, 2)
        seed = pltpu.make_async_copy(x_ref, out_ref, local_sem)
        seed.start()
        seed.wait()

    # chunk walk indices (the VMEM kernel's formulas on a traced step; the
    # +2·world keeps every branch of the where non-negative under floor-mod)
    in_rs = step < n_rs
    own = 1 if do_reduce_scatter else 0
    ag = step - n_rs
    send_idx = jnp.where(
        in_rs,
        (my_id + 2 * world - step) % world,
        (my_id + 2 * world + own - ag) % world,
    )
    recv_idx = jnp.where(
        in_rs,
        (my_id + 2 * world - step - 1) % world,
        (my_id + 2 * world + own - ag - 1) % world,
    )
    slot = it % 2
    row0 = tile * stage_rows
    rows = pl.ds(row0, stage_rows)

    # stage the outbound tile: HBM work → fixed VMEM staging.  One buffer
    # suffices: the RDMA below completes (send side included) inside this
    # iteration, so the staging is always free for the next tile — the
    # double buffering that matters for flow control is the *comm* slots,
    # which the left neighbor writes asynchronously
    stage_in = pltpu.make_async_copy(
        out_ref.at[send_idx, rows], send_stage, local_sem
    )
    stage_in.start()
    stage_in.wait()

    @pl.when(it >= 2)
    def _credit_wait():
        pltpu.semaphore_wait(cap_sem, 1)

    rdma = pltpu.make_async_remote_copy(
        src_ref=send_stage,
        dst_ref=comm.at[slot],
        send_sem=send_sem.at[slot],
        recv_sem=recv_sem.at[slot],
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    rdma.wait()  # outbound sent AND left neighbor's tile landed

    @pl.when(in_rs)
    def _reduce():
        # accumulate: HBM tile → VMEM, add the landed tile, DMA back
        acc_in = pltpu.make_async_copy(out_ref.at[recv_idx, rows], acc, local_sem)
        acc_in.start()
        acc_in.wait()
        acc[...] = acc[...] + comm[slot]
        acc_out = pltpu.make_async_copy(acc, out_ref.at[recv_idx, rows], local_sem)
        acc_out.start()
        acc_out.wait()

    @pl.when(jnp.logical_not(in_rs))
    def _adopt():
        adopt = pltpu.make_async_copy(comm.at[slot], out_ref.at[recv_idx, rows], local_sem)
        adopt.start()
        adopt.wait()

    # return a capacity credit upstream: slot is free for reuse
    pltpu.semaphore_signal(cap_sem, inc=1, device_id=left)

    @pl.when(it == total_iters - 1)
    def _drain():
        for _ in range(min(2, total_iters)):
            pltpu.semaphore_wait(cap_sem, 1)


# --------------------------------------------------------------------------- #
# fused wire-codec kernels: quantize/dequantize inside the VMEM staging
# --------------------------------------------------------------------------- #
#
# The EQuARX move (PAPERS.md) on the staged pipeline: each staging tile is
# encoded to the wire dtype *before* its RDMA and decoded+accumulated in
# fp32 on receive, so codec compute hides behind the RDMA of the
# neighboring tile and the fabric carries ~4x fewer bytes on the same
# credit-based flow control.  Bit-contract with the unfused quantized
# ppermute ring (quant/ring.py):
#
# - the block math is quant/codec.py's, verbatim: per-block absmax/127
#   fp32 scales, deterministic round, clip to [-127, 127].  Blocks nest in
#   staging tiles (fused_wire_unsupported_reason enforces the geometry),
#   so tile-wise encoding produces the same bits as chunk-wise encoding;
# - reduce-scatter dequant-accumulates-requants per hop in fp32 — only the
#   wire is narrow, the running sum never is;
# - all-gather encodes each reduced chunk ONCE (at its owner) and forwards
#   the encoded bits verbatim.  The int8 *codes* are exactly recoverable
#   by re-quantizing the decoded fp32 values against the original scale
#   (|q| <= 127 makes round(q*s/s) == q in fp32); the *scale* happens to
#   re-derive stably too (fl(fl(127*s)/127) == s for 127-quotient scales)
#   but only as a numerical accident of the quotient form — for raw values
#   the same expression drifts an ulp ~1% of the time.  So the scales ride
#   a side-channel store ([world, s_rows, 128] fp32; VMEM scratch on the
#   vmem path, an HBM side output on the stream path) and are forwarded
#   bit-verbatim: rank-to-rank bit identity rests on construction, not on
#   the accident holding for every backend.  Every rank, owner included,
#   adopts the decoded wire value, so results are bit-identical rank to
#   rank (and match the unfused ring up to the FP contraction of the
#   per-hop accumulate — XLA may fuse the dequantize multiply into an FMA
#   with the add differently across programs, a <= 2-ulp effect; the wire
#   bits and add order are op-identical).


#: block rows per lane-dense scale row: one 128×128 transpose moves 128
#: per-block scales from sublanes (where the lane reduction leaves them)
#: to lanes (where the side channel ships them densely)
_SCALE_GROUP = 128

# Kernel-side geometry of a fused tile: ``[n_rows, B]`` fp32 with ONE codec
# block per row — ``B = block_size`` on int8 plans (the wrapper reshapes the
# ``[.., rows, 128]`` chunk to ``[.., rows / rows_per_block, block_size]``,
# a relabeling of the same contiguous elements), ``B = 128`` for bf16.  Per-
# block scales are then a lane reduction with ``keepdims`` — an ``[n, 1]``
# column that broadcasts straight back over its row — and every value in
# the kernel stays 2-D: Mosaic has no layout for a rank-1 fp32 vector (a
# reshape through one aborts the compiler process, ``layout.h: arr.size()
# >= layout_rank``; the interpreter accepts it, so only
# tests/test_chip_compile.py guards this).  All codec math walks the tile
# in static 128-row groups, so compiler temporaries are one group, not one
# tile.


def _row_groups(n_rows: int):
    """Static ``(group, first row, rows)`` triples covering ``n_rows``."""
    return [
        (g, g * _SCALE_GROUP, min(_SCALE_GROUP, n_rows - g * _SCALE_GROUP))
        for g in range(-(-n_rows // _SCALE_GROUP))
    ]


def _at(lead, r0: int, n: int):
    """Index of rows ``[r0, r0 + n)`` of a tile ref, behind an optional
    leading (slot / chunk) index."""
    rows = pl.ds(r0, n)
    return (rows, slice(None)) if lead is None else (lead, rows, slice(None))


def _block_scale_col(vals: jnp.ndarray) -> jnp.ndarray:
    """``[n, B]`` fp32 → ``[n, 1]`` per-block scales — the exact absmax/127
    derivation of ``quant/codec.quantize_int8``."""
    absmax = jnp.max(jnp.abs(vals), axis=1, keepdims=True)
    return jnp.where(absmax > 0, absmax / 127.0, 1.0)


def _pack_scale_row(col: jnp.ndarray) -> jnp.ndarray:
    """``[n <= 128, 1]`` scale column → one lane-dense ``[1, 128]`` side-
    channel row (padding scales are 1.0, the all-zero-block convention)."""
    n = col.shape[0]
    blk = jnp.broadcast_to(col, (n, _LANES))
    if n < _SCALE_GROUP:
        blk = jnp.concatenate(
            [blk, jnp.ones((_SCALE_GROUP - n, _LANES), jnp.float32)], axis=0
        )
    return blk.T[0:1, :]


def _unpack_scale_col(row: jnp.ndarray, n: int) -> jnp.ndarray:
    """One ``[1, 128]`` side-channel row → the ``[n, 1]`` scale column."""
    return jnp.broadcast_to(row, (_SCALE_GROUP, _LANES)).T[:n, 0:1]


def _derive_scales(src, lead, scale_dst, n_rows: int) -> None:
    """Fresh per-block scales of the fp32 tile ``src[lead]`` → the lane-
    dense scale tile ``scale_dst``."""
    for g, r0, n in _row_groups(n_rows):
        scale_dst[g : g + 1, :] = _pack_scale_row(
            _block_scale_col(src[_at(lead, r0, n)])
        )


def _encode(src, lead, scale_src, wire_dst, n_rows: int, int8: bool) -> None:
    """Encode the fp32 tile ``src[lead]`` into ``wire_dst``: bf16 cast, or
    int8 codes against the scales already in ``scale_src`` — with fresh
    scales this IS ``quant/codec.quantize_int8`` (same divide / round /
    clip ops, so fused and unfused wire bits cannot drift); with forwarded
    scales it re-derives the codes of already-decoded values exactly
    (``round((q·s)/s) == q`` for ``|q| <= 127`` in fp32)."""
    for g, r0, n in _row_groups(n_rows):
        vals = src[_at(lead, r0, n)]
        if int8:
            col = _unpack_scale_col(scale_src[g : g + 1, :], n)
            wire = jnp.clip(jnp.round(vals / col), -127.0, 127.0).astype(jnp.int8)
        else:
            wire = vals.astype(jnp.bfloat16)
        wire_dst[_at(None, r0, n)] = wire


def _decode(
    wire_src, wire_lead, scale_src, scale_lead, dst, dst_lead,
    n_rows: int, int8: bool, accumulate: bool,
) -> None:
    """Decode the wire tile back to fp32 (``quant/codec.dequantize_int8``
    ops) and fold it into ``dst[dst_lead]``: add on reduce-scatter hops,
    adopt on all-gather hops."""
    for g, r0, n in _row_groups(n_rows):
        vals = wire_src[_at(wire_lead, r0, n)].astype(jnp.float32)
        if int8:
            vals = vals * _unpack_scale_col(scale_src[_at(scale_lead, g, 1)], n)
        idx = _at(dst_lead, r0, n)
        dst[idx] = dst[idx] + vals if accumulate else vals


def _fused_ring_kernel(
    x_ref,
    out_ref,
    work,
    wire_send,
    scale_send,
    comm_w,
    comm_s,
    scale_store,
    send_w_sem,
    recv_w_sem,
    send_s_sem,
    recv_s_sem,
    cap_sem,
    *,
    world: int,
    axis_name: str,
    do_reduce_scatter: bool,
    do_all_gather: bool,
    wire_dtype: str,
):
    """VMEM-resident fused ring walk: the ``_ring_kernel`` schedule with
    the wire codec applied per chunk.  ``wire_send``/``comm_w`` carry the
    encoded chunk (int8 codes or bf16), ``scale_send``/``comm_s`` the fp32
    block scales (int8 only), ``scale_store`` the per-chunk scales the
    all-gather forwards verbatim.  One capacity credit covers both slot
    arrays — the flow control is the unfused kernel's, unchanged."""
    my_id = lax.axis_index(axis_name)
    right = (my_id + 1) % world
    left = (my_id + world - 1) % world
    int8 = wire_dtype == "int8"
    n_rows = work.shape[1]

    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)

    work[...] = x_ref[...]

    n_rs = world - 1 if do_reduce_scatter else 0
    n_ag = world - 1 if do_all_gather else 0
    total_steps = n_rs + n_ag

    for step in range(total_steps):
        slot = step % 2
        in_rs = step < n_rs
        if in_rs:
            send_idx = (my_id + world - step) % world
            recv_idx = (my_id + world - step - 1) % world
        else:
            ag = step - n_rs
            own = 1 if do_reduce_scatter else 0
            send_idx = (my_id + world + own - ag) % world
            recv_idx = (my_id + world + own - ag - 1) % world

        if int8:
            if in_rs or step == n_rs:
                # RS hops re-encode the moving partial; the first AG hop is
                # the once-per-reduced-chunk encode that defines the bits
                _derive_scales(work, send_idx, scale_send, n_rows)
            else:
                # later AG hops forward verbatim: stored scales, exact codes
                scale_send[...] = scale_store[send_idx]
        _encode(work, send_idx, scale_send, wire_send, n_rows, int8)
        if not in_rs and step == n_rs:
            # the owner adopts its own DECODED chunk: every rank must see
            # the same post-codec value, owner included (quant/ring.py)
            _decode(
                wire_send, None, scale_send, None, work, send_idx,
                n_rows, int8, accumulate=False,
            )

        if step >= 2:
            pltpu.semaphore_wait(cap_sem, 1)

        rdma_w = pltpu.make_async_remote_copy(
            src_ref=wire_send,
            dst_ref=comm_w.at[slot],
            send_sem=send_w_sem.at[slot],
            recv_sem=recv_w_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma_w.start()
        if int8:
            rdma_s = pltpu.make_async_remote_copy(
                src_ref=scale_send,
                dst_ref=comm_s.at[slot],
                send_sem=send_s_sem.at[slot],
                recv_sem=recv_s_sem.at[slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma_s.start()
            rdma_s.wait()
        rdma_w.wait()  # outbound sent AND left neighbor's arrays landed

        _decode(
            comm_w, slot, comm_s, slot, work, recv_idx,
            n_rows, int8, accumulate=in_rs,
        )
        if not in_rs and int8:
            # bank the forwarded-bit scales for the next AG hop
            scale_store[recv_idx] = comm_s[slot]

        pltpu.semaphore_signal(cap_sem, inc=1, device_id=left)

    tail = min(2, total_steps)
    for _ in range(tail):
        pltpu.semaphore_wait(cap_sem, 1)
    out_ref[...] = work[...]


def _fused_stream_ring_kernel(
    x_ref,
    out_ref,
    scales_hbm,
    send_stage,
    acc,
    wire_send,
    scale_send,
    comm_w,
    comm_s,
    local_sem,
    send_w_sem,
    recv_w_sem,
    send_s_sem,
    recv_s_sem,
    cap_sem,
    *,
    world: int,
    axis_name: str,
    do_reduce_scatter: bool,
    do_all_gather: bool,
    n_tiles: int,
    total_iters: int,
    wire_dtype: str,
):
    """HBM-streaming fused ring walk: ``_stream_ring_kernel``'s grid and
    credit protocol with the codec in the staging tiles.  Each iteration
    stages one fp32 tile, encodes it in VMEM (fresh on RS hops and the
    first AG hop; re-derived against forwarded scales afterwards), ships
    the wire arrays (codes + scale side channel), and folds the landed
    tile back into HBM in fp32.  ``scales_hbm`` is the per-chunk scale
    store ([world, n_tiles·s_rows, 128] fp32, an ANY-space side output)
    the all-gather forwards bits from."""
    step = pl.program_id(0)
    tile = pl.program_id(1)
    it = step * n_tiles + tile
    my_id = lax.axis_index(axis_name)
    right = (my_id + 1) % world
    left = (my_id + world - 1) % world
    int8 = wire_dtype == "int8"
    n_rows = send_stage.shape[0]
    s_rows = scale_send.shape[0] if int8 else 0

    n_rs = world - 1 if do_reduce_scatter else 0

    @pl.when(it == 0)
    def _enter():
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=left)
        pltpu.semaphore_signal(barrier, inc=1, device_id=right)
        pltpu.semaphore_wait(barrier, 2)
        seed = pltpu.make_async_copy(x_ref, out_ref, local_sem)
        seed.start()
        seed.wait()

    in_rs = step < n_rs
    own = 1 if do_reduce_scatter else 0
    ag = step - n_rs
    send_idx = jnp.where(
        in_rs,
        (my_id + 2 * world - step) % world,
        (my_id + 2 * world + own - ag) % world,
    )
    recv_idx = jnp.where(
        in_rs,
        (my_id + 2 * world - step - 1) % world,
        (my_id + 2 * world + own - ag - 1) % world,
    )
    slot = it % 2
    rows = pl.ds(tile * n_rows, n_rows)
    srows = pl.ds(tile * s_rows, s_rows)
    # fresh encode on RS hops and the first AG hop (the once-per-reduced-
    # chunk encode); later AG hops re-derive codes against forwarded scales
    fresh = jnp.logical_or(in_rs, ag == 0)

    stage_in = pltpu.make_async_copy(
        out_ref.at[send_idx, rows], send_stage, local_sem
    )
    stage_in.start()
    stage_in.wait()

    if int8:

        @pl.when(jnp.logical_not(fresh))
        def _load_forwarded_scales():
            fwd = pltpu.make_async_copy(
                scales_hbm.at[send_idx, srows], scale_send, local_sem
            )
            fwd.start()
            fwd.wait()

        @pl.when(fresh)
        def _derive_fresh_scales():
            # only fresh hops pay the absmax pass; forwarded hops already
            # DMA'd the original scale bits into scale_send above
            _derive_scales(send_stage, None, scale_send, n_rows)

    # one encode serves both cases: with fresh scales it IS the quantize
    # (same round/clip ops), with forwarded scales it is exact
    _encode(send_stage, None, scale_send, wire_send, n_rows, int8)

    @pl.when(jnp.logical_and(jnp.logical_not(in_rs), ag == 0))
    def _adopt_own():
        # the owner adopts its own decoded tile: every rank must end with
        # the same post-codec bits, owner included
        _decode(
            wire_send, None, scale_send, None, acc, None,
            n_rows, int8, accumulate=False,
        )
        own_out = pltpu.make_async_copy(
            acc, out_ref.at[send_idx, rows], local_sem
        )
        own_out.start()
        own_out.wait()

    @pl.when(it >= 2)
    def _credit_wait():
        pltpu.semaphore_wait(cap_sem, 1)

    rdma_w = pltpu.make_async_remote_copy(
        src_ref=wire_send,
        dst_ref=comm_w.at[slot],
        send_sem=send_w_sem.at[slot],
        recv_sem=recv_w_sem.at[slot],
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma_w.start()
    if int8:
        rdma_s = pltpu.make_async_remote_copy(
            src_ref=scale_send,
            dst_ref=comm_s.at[slot],
            send_sem=send_s_sem.at[slot],
            recv_sem=recv_s_sem.at[slot],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma_s.start()
        rdma_s.wait()
    rdma_w.wait()  # outbound sent AND left neighbor's arrays landed

    @pl.when(in_rs)
    def _reduce():
        acc_in = pltpu.make_async_copy(
            out_ref.at[recv_idx, rows], acc, local_sem
        )
        acc_in.start()
        acc_in.wait()
        _decode(
            comm_w, slot, comm_s, slot, acc, None,
            n_rows, int8, accumulate=True,
        )
        acc_out = pltpu.make_async_copy(
            acc, out_ref.at[recv_idx, rows], local_sem
        )
        acc_out.start()
        acc_out.wait()

    @pl.when(jnp.logical_not(in_rs))
    def _adopt():
        _decode(
            comm_w, slot, comm_s, slot, acc, None,
            n_rows, int8, accumulate=False,
        )
        adopt = pltpu.make_async_copy(
            acc, out_ref.at[recv_idx, rows], local_sem
        )
        adopt.start()
        adopt.wait()
        if int8:
            # bank the forwarded-bit scales for the next AG hop
            bank = pltpu.make_async_copy(
                comm_s.at[slot], scales_hbm.at[recv_idx, srows], local_sem
            )
            bank.start()
            bank.wait()

    pltpu.semaphore_signal(cap_sem, inc=1, device_id=left)

    @pl.when(it == total_iters - 1)
    def _drain():
        for _ in range(min(2, total_iters)):
            pltpu.semaphore_wait(cap_sem, 1)


# --------------------------------------------------------------------------- #
# shard-level wrappers (call inside shard_map)
# --------------------------------------------------------------------------- #

def _pad_chunks(flat: jnp.ndarray, world: int):
    """Pad to world × (whole dtype-native tiles) and reshape chunk-major."""
    tile = _tile_elems(flat.dtype)
    chunk = -(-flat.size // world)          # ceil
    chunk = -(-chunk // tile) * tile        # round up to full tiles
    padded = jnp.zeros((world * chunk,), flat.dtype).at[: flat.size].set(flat)
    return padded.reshape(world, chunk // _LANES, _LANES), chunk


def _check_fused_wire(dtype, wire_dtype: str, block_size: Optional[int]) -> None:
    """Loud reject where fused codec semantics don't apply — running fp32
    silently under a requested codec would invalidate every wire A/B."""
    reason = fused_wire_unsupported_reason(dtype, wire_dtype, block_size)
    if reason is not None:
        raise ValueError(
            f"wire_dtype={wire_dtype!r} cannot run on the fused Pallas ring: "
            f"{reason}"
        )


def _run_fused_ring_chunks(
    chunks: jnp.ndarray,
    plan: RingSchedule,
    *,
    world,
    axis_name,
    rs,
    ag,
    interpret,
):
    """Dispatch a fused-codec plan on a pre-chunked ``[world, S, 128]``
    fp32 array (both paths).  The kernels see one codec block per row
    (``[world, S / rows_per_block, block_size]`` on int8 plans — the same
    contiguous elements, relabeled) with each chunk zero-padded to the
    plan's whole codec tiles; the padding is sliced back out, exactly like
    the unfused stream dispatch."""
    wire_dtype = plan.wire_dtype
    int8 = wire_dtype == "int8"
    wire_jnp = jnp.int8 if int8 else jnp.bfloat16
    width = plan.block_size if int8 else _LANES     # kernel tile width B
    rows_per_block = width // _LANES
    chunk_rows = chunks.shape[1]
    stage_rows = plan.stage_bytes // (_LANES * 4)    # fp32 128-lane rows
    padded_rows = plan.n_tiles * stage_rows
    if padded_rows != chunk_rows:
        chunks = jnp.pad(chunks, ((0, 0), (0, padded_rows - chunk_rows), (0, 0)))
    chunks = chunks.reshape(world, padded_rows // rows_per_block, width)
    tile_shape = (stage_rows // rows_per_block, width)
    scale_shape = (_scale_rows(tile_shape[0]), _LANES) if int8 else None
    kernel_kwargs = dict(
        world=world,
        axis_name=axis_name,
        do_reduce_scatter=rs,
        do_all_gather=ag,
        wire_dtype=wire_dtype,
    )
    wire_sems = [
        pltpu.SemaphoreType.DMA((2,)),                        # send codes
        pltpu.SemaphoreType.DMA((2,)),                        # recv codes
    ]
    if int8:
        wire_sems.extend([
            pltpu.SemaphoreType.DMA((2,)),                    # send scales
            pltpu.SemaphoreType.DMA((2,)),                    # recv scales
        ])
    wire_sems.append(pltpu.SemaphoreType.REGULAR)             # capacity
    payload_shape = jax.ShapeDtypeStruct(chunks.shape, chunks.dtype)

    if plan.path == "vmem":
        body = functools.partial(_fused_ring_kernel, **kernel_kwargs)
        scratch = [
            pltpu.VMEM(chunks.shape, chunks.dtype),              # work
            pltpu.VMEM(tile_shape, wire_jnp),                    # wire send
        ]
        if int8:
            scratch.append(pltpu.VMEM(scale_shape, jnp.float32))  # scale send
        scratch.append(pltpu.VMEM((2,) + tile_shape, wire_jnp))   # comm codes
        if int8:
            scratch.extend([
                pltpu.VMEM((2,) + scale_shape, jnp.float32),      # comm scales
                pltpu.VMEM((world,) + scale_shape, jnp.float32),  # scale store
            ])
        scratch.extend(wire_sems)

        if int8:
            kernel = body
        else:
            # bf16 needs no scale side channel: bind the unused refs to
            # None so the plan's VMEM accounting matches the allocations
            def kernel(x_ref, out_ref, work, wire_send, comm_w,
                       send_w, recv_w, cap_sem):
                return body(
                    x_ref, out_ref, work, wire_send, None, comm_w, None,
                    None, send_w, recv_w, None, None, cap_sem,
                )

        out = pl.pallas_call(
            kernel,
            out_shape=payload_shape,
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True, collective_id=0,
                vmem_limit_bytes=plan.vmem_limit_bytes,
            ),
            interpret=_interpret_params(interpret),
        )(chunks)
    else:
        total_iters = plan.steps * plan.n_tiles
        body = functools.partial(
            _fused_stream_ring_kernel,
            n_tiles=plan.n_tiles,
            total_iters=total_iters,
            **kernel_kwargs,
        )
        scratch = [
            pltpu.VMEM(tile_shape, chunks.dtype),              # fp32 send staging
            pltpu.VMEM(tile_shape, chunks.dtype),              # fp32 accumulate
            pltpu.VMEM(tile_shape, wire_jnp),                  # wire send
        ]
        if int8:
            scratch.append(pltpu.VMEM(scale_shape, jnp.float32))  # scale send
        scratch.append(pltpu.VMEM((2,) + tile_shape, wire_jnp))   # comm codes
        if int8:
            scratch.append(
                pltpu.VMEM((2,) + scale_shape, jnp.float32)       # comm scales
            )
        scratch.append(pltpu.SemaphoreType.DMA(()))               # local DMAs
        scratch.extend(wire_sems)
        if int8:
            kernel = body
            out_shape = (
                payload_shape,
                # per-chunk scale store: the AG's forwarded-bit side channel
                jax.ShapeDtypeStruct(
                    (world, plan.n_tiles * scale_shape[0], _LANES), jnp.float32
                ),
            )
            out_specs = (
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            )
        else:
            # bf16 needs no scale side channel or store: bind the unused
            # refs to None so the plan's VMEM accounting matches the
            # allocations
            def kernel(x_ref, out_ref, send_stage, acc, wire_send, comm_w,
                       local_sem, send_w, recv_w, cap_sem):
                return body(
                    x_ref, out_ref, None, send_stage, acc, wire_send, None,
                    comm_w, None, local_sem, send_w, recv_w, None, None,
                    cap_sem,
                )

            out_shape = payload_shape
            out_specs = pl.BlockSpec(memory_space=pl.ANY)
        result = pl.pallas_call(
            kernel,
            grid=(plan.steps, plan.n_tiles),
            out_shape=out_shape,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=0,
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=plan.vmem_limit_bytes,
            ),
            interpret=_interpret_params(interpret),
        )(chunks)
        out = result[0] if int8 else result
    out = out.reshape(world, padded_rows, _LANES)
    return out[:, :chunk_rows] if padded_rows != chunk_rows else out


def _run_ring_chunks(
    chunks: jnp.ndarray,
    *,
    world,
    axis_name,
    rs,
    ag,
    interpret,
    chunk_bytes: Optional[int] = None,
    wire_dtype: str = "off",
    block_size: Optional[int] = None,
):
    """Run the ring on a pre-chunked ``[world, S, 128]`` array, dispatching
    to the VMEM-resident or HBM-streaming kernel per the planned schedule
    (the fused codec variants when ``wire_dtype`` names one)."""
    if wire_dtype != "off":
        _check_fused_wire(chunks.dtype, wire_dtype, block_size)
        if block_size is None:
            block_size = _default_block_size()
    plan = plan_ring_schedule(
        chunks.size, chunks.dtype, world, chunk_bytes, rs=rs, ag=ag,
        wire_dtype=wire_dtype, block_size=block_size,
    )
    if wire_dtype != "off":
        return _run_fused_ring_chunks(
            chunks, plan, world=world, axis_name=axis_name, rs=rs, ag=ag,
            interpret=interpret,
        )
    if plan.path == "vmem":
        kernel = functools.partial(
            _ring_kernel,
            world=world,
            axis_name=axis_name,
            do_reduce_scatter=rs,
            do_all_gather=ag,
        )
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(chunks.shape, chunks.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM(chunks.shape, chunks.dtype),                # work
                pltpu.VMEM((2,) + chunks.shape[1:], chunks.dtype),     # comm slots
                pltpu.SemaphoreType.DMA((2,)),                         # send
                pltpu.SemaphoreType.DMA((2,)),                         # recv
                pltpu.SemaphoreType.REGULAR,                           # capacity
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True, collective_id=0,
                vmem_limit_bytes=plan.vmem_limit_bytes,
            ),
            interpret=_interpret_params(interpret),
        )(chunks)

    stage_rows = plan.stage_bytes // (_LANES * jnp.dtype(chunks.dtype).itemsize)
    total_iters = plan.steps * plan.n_tiles
    # zero-pad each chunk to whole staging tiles (bounded by < one tile per
    # chunk, see _stage_rows_for) and slice the padding back out below, so
    # callers see the legacy tile-aligned layout on both paths
    chunk_rows = chunks.shape[1]
    padded_rows = plan.n_tiles * stage_rows
    if padded_rows != chunk_rows:
        chunks = jnp.pad(chunks, ((0, 0), (0, padded_rows - chunk_rows), (0, 0)))
    kernel = functools.partial(
        _stream_ring_kernel,
        world=world,
        axis_name=axis_name,
        do_reduce_scatter=rs,
        do_all_gather=ag,
        n_tiles=plan.n_tiles,
        stage_rows=stage_rows,
        total_iters=total_iters,
    )
    tile_shape = (stage_rows, _LANES)
    out = pl.pallas_call(
        kernel,
        grid=(plan.steps, plan.n_tiles),
        out_shape=jax.ShapeDtypeStruct(chunks.shape, chunks.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM(tile_shape, chunks.dtype),          # send staging
            pltpu.VMEM(tile_shape, chunks.dtype),          # accumulate staging
            pltpu.VMEM((2,) + tile_shape, chunks.dtype),   # comm slots
            pltpu.SemaphoreType.DMA(()),                   # local DMAs
            pltpu.SemaphoreType.DMA((2,)),                 # send
            pltpu.SemaphoreType.DMA((2,)),                 # recv
            pltpu.SemaphoreType.REGULAR,                   # capacity
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            collective_id=0,
            # the ring walk is stateful: both grid dims must run in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes,
        ),
        interpret=_interpret_params(interpret),
    )(chunks)
    return out[:, :chunk_rows] if padded_rows != chunk_rows else out


def _run_ring(
    x: jnp.ndarray, *, world, axis_name, rs, ag, interpret, chunk_bytes=None,
    wire_dtype="off", block_size=None,
):
    chunks, chunk = _pad_chunks(x.reshape(-1), world)
    out = _run_ring_chunks(
        chunks, world=world, axis_name=axis_name, rs=rs, ag=ag,
        interpret=interpret, chunk_bytes=chunk_bytes,
        wire_dtype=wire_dtype, block_size=block_size,
    )
    return out, chunk


def ring_allreduce_shard(
    x: jnp.ndarray,
    world: int,
    axis_name: str = RANKS_AXIS,
    interpret: bool = False,
    chunk_bytes: Optional[int] = None,
    wire_dtype: str = "off",
    block_size: Optional[int] = None,
) -> jnp.ndarray:
    """Sum-allreduce via ring reduce-scatter + ring all-gather.

    Bandwidth-optimal (2·(world−1)/world of the buffer per link), the same
    schedule family the reference benchmarks against NCCL rings
    (nccl-perf/tree/all_reduce.cu).  ``chunk_bytes`` is the staging
    granularity (synthesized by the strategy plane; env-overridable): payloads
    above it stream through HBM, below it stay VMEM-resident.

    ``wire_dtype`` names a fused wire codec (``bf16`` | ``int8``): staging
    tiles are encoded before their RDMA and decoded+accumulated in fp32 on
    receive, the all-gather forwards each reduced chunk's encoded bits
    verbatim — results are bit-identical rank to rank and match the unfused
    ``quant/ring.py`` path wherever the chunk layouts coincide.  Rejects
    loudly where codec semantics don't apply (non-fp32 payloads, block
    sizes that can't tile VMEM) — never silently runs fp32.
    """
    if world == 1:
        return x
    out, _ = _run_ring(
        x, world=world, axis_name=axis_name, rs=True, ag=True,
        interpret=interpret, chunk_bytes=chunk_bytes,
        wire_dtype=wire_dtype, block_size=block_size,
    )
    return out.reshape(-1)[: x.size].reshape(x.shape)


def ring_reduce_scatter_shard(
    x: jnp.ndarray,
    world: int,
    axis_name: str = RANKS_AXIS,
    interpret: bool = False,
    chunk_bytes: Optional[int] = None,
    wire_dtype: str = "off",
    block_size: Optional[int] = None,
) -> jnp.ndarray:
    """Ring reduce-scatter: returns this rank's reduced chunk (padded shape
    ``[chunk]``); rank r owns chunk ``(r + 1) % world`` of the flattened,
    tile-padded input.

    Under a fused ``wire_dtype`` every hop ships encoded tiles and
    dequant-accumulates in fp32; the owned chunk comes back as the fp32
    running sum (no final encode — a standalone RS has no forwarding phase
    to pin bits for).  Loud reject where the codec can't apply."""
    if world == 1:
        return x.reshape(-1)
    out, chunk = _run_ring(
        x, world=world, axis_name=axis_name, rs=True, ag=False,
        interpret=interpret, chunk_bytes=chunk_bytes,
        wire_dtype=wire_dtype, block_size=block_size,
    )
    my_id = lax.axis_index(axis_name)
    own = (my_id + 1) % world
    return out.reshape(world, chunk)[own]


def ring_all_gather_shard(
    x: jnp.ndarray,
    world: int,
    axis_name: str = RANKS_AXIS,
    interpret: bool = False,
    chunk_bytes: Optional[int] = None,
    wire_dtype: str = "off",
    block_size: Optional[int] = None,
) -> jnp.ndarray:
    """Ring all-gather of per-rank chunks: input is this rank's ``[chunk]``
    payload (tile-aligned), output is ``[world, chunk]`` in rank order.

    Under a fused ``wire_dtype`` each rank encodes its chunk ONCE and the
    ring forwards the encoded bits verbatim (scales ride the side
    channel), so every rank — owner included — holds the identical
    post-codec values.  Loud reject where the codec can't apply."""
    if world == 1:
        return x.reshape(1, -1)
    tile = _tile_elems(x.dtype)
    if x.size % tile:
        raise ValueError(f"all-gather payload must be tile-aligned ({tile} elems), got {x.size}")
    if wire_dtype != "off":
        # validate before any traced axis op so the reject fires eagerly
        _check_fused_wire(x.dtype, wire_dtype, block_size)
    my_id = lax.axis_index(axis_name)
    chunks = jnp.zeros((world, x.size), x.dtype)
    # place the local payload in the row this rank owns; the ring walk
    # replaces every other row with the neighbors' payloads
    chunks = lax.dynamic_update_index_in_dim(chunks, x.reshape(-1), my_id, 0)
    chunks = chunks.reshape(world, x.size // _LANES, _LANES)
    out = _run_ring_chunks(
        chunks, world=world, axis_name=axis_name, rs=False, ag=True,
        interpret=interpret, chunk_bytes=chunk_bytes,
        wire_dtype=wire_dtype, block_size=block_size,
    )
    return out.reshape(world, -1)
