"""Public AdapCC façade — same surface as the reference's adapcc.py.

The reference exposes a classmethod façade over one ``CudaCommu``
(adapcc.py:6-77): ``init`` runs the detect/profile bootstrap chosen by
``entry_point``, ``setup`` creates a transmission context, the collective
methods forward to the communicator, and ``reconstruct_topology`` tears
everything down and re-adapts.  This is the same façade over the TPU
:class:`~adapcc_tpu.communicator.Communicator`.

Entry-point contract (adapcc.py:30-41): ``DETECT`` (6) runs detect → profile
→ synthesize; ``PROFILE`` (7) assumes a logical graph exists and runs profile
→ synthesize; ``-1`` skips the bootstrap (use a pre-written strategy file).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax.numpy as jnp
from jax.sharding import Mesh

from adapcc_tpu.communicator import Communicator
from adapcc_tpu.config import CommArgs
from adapcc_tpu.primitives import DETECT, PROFILE, SKIP_BOOTSTRAP, ReduceOp


class AdapCC:
    """Classmethod façade; state mirrors the reference's class attributes."""

    communicator: Optional[Communicator] = None
    local_rank: Optional[int] = None
    world_rank: Optional[int] = None
    world_size: Optional[int] = None
    profile_freq: Optional[int] = None

    @classmethod
    def init(
        cls,
        args: Any,
        local_rank: int = 0,
        world_rank: int = 0,
        world_size: Optional[int] = None,
        mesh: Optional[Mesh] = None,
    ) -> None:
        """Create the communicator and run the adaptive bootstrap.

        ``local_rank``/``world_rank`` are accepted for signature parity with
        the reference (adapcc.py:16); under single-controller JAX the mesh
        carries the whole world, so they only label this process.
        """
        comm_args = args if isinstance(args, CommArgs) else CommArgs.from_namespace(args)
        cls.communicator = Communicator(comm_args, mesh=mesh, world_size=world_size)
        cls.local_rank = local_rank
        cls.world_rank = world_rank
        cls.world_size = cls.communicator.world_size
        cls.profile_freq = comm_args.profile_freq

        entry = comm_args.entry_point
        if entry == DETECT:
            cls.communicator.init_threads(DETECT)
            cls.communicator.exit_threads(DETECT)
            cls.communicator.init_threads(PROFILE)
            cls.communicator.exit_threads(PROFILE)
        elif entry == PROFILE:
            cls.communicator.init_threads(PROFILE)
            cls.communicator.exit_threads(PROFILE)
        elif entry == SKIP_BOOTSTRAP:
            pass
        else:
            raise ValueError(f"no supported entry point for init: {entry}")

    @classmethod
    def setup(cls, prim: int) -> None:
        cls.communicator.init_threads(prim)

    @classmethod
    def allreduce(
        cls,
        tensor: jnp.ndarray,
        size: Optional[int] = None,
        chunk_bytes: Optional[int] = None,
        active_gpus: Optional[Sequence[int]] = None,
        op: ReduceOp = ReduceOp.SUM,
    ) -> jnp.ndarray:
        return cls.communicator.all_reduce(tensor, size, chunk_bytes, active_gpus, op=op)

    @classmethod
    def reduce(
        cls,
        tensor: jnp.ndarray,
        size: Optional[int] = None,
        chunk_bytes: Optional[int] = None,
        active_gpus: Optional[Sequence[int]] = None,
        op: ReduceOp = ReduceOp.SUM,
    ) -> jnp.ndarray:
        return cls.communicator.reduce(tensor, size, chunk_bytes, active_gpus, op=op)

    @classmethod
    def boardcast(
        cls,
        tensor: jnp.ndarray,
        size: Optional[int] = None,
        chunk_bytes: Optional[int] = None,
        active_gpus: Optional[Sequence[int]] = None,
    ) -> jnp.ndarray:
        return cls.communicator.boardcast(tensor, size, chunk_bytes, active_gpus)

    @classmethod
    def alltoall(
        cls,
        tensor: jnp.ndarray,
        size: Optional[int] = None,
        chunk_bytes: Optional[int] = None,
        active_gpus: Optional[Sequence[int]] = None,
    ) -> jnp.ndarray:
        return cls.communicator.alltoall(tensor, size, chunk_bytes, active_gpus)

    @classmethod
    def reconstruct_topology(cls, args: Any, prim: int) -> None:
        """Clear contexts, re-run the adaptive bootstrap, rebuild the context
        (adapcc.py:63-67) — the periodic re-adaptation driven by
        ``profile_freq`` in training loops."""
        cls.clear(prim)
        cls.init(
            args,
            cls.local_rank,
            cls.world_rank,
            cls.world_size,
            mesh=cls.communicator.mesh if cls.communicator else None,
        )
        cls.setup(prim)

    @classmethod
    def set_profile_freq(cls, freq: int) -> None:
        cls.profile_freq = freq

    @classmethod
    def clear(cls, prim: int) -> None:
        cls.communicator.exit_threads(prim)
        cls.communicator.clear()


def smoke_benchmark(world: int = 4) -> None:
    """The reference's ``__main__`` smoke benchmark (adapcc.py:81-117): full
    adaptive bootstrap, then 16-float allreduces of ``ones*i`` over ``world``
    ranks — every rank must print ``i*world`` — plus a subset (relay)
    allreduce.  Output is deterministic; ``log/primitive`` holds the golden
    copy (README.md:104 analog), asserted by the test suite.
    """
    import tempfile

    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import numpy as np

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.primitives import ALLREDUCE

    mesh = build_world_mesh(world)  # raises if the backend has fewer devices
    w = int(mesh.devices.size)
    with tempfile.TemporaryDirectory(prefix="adapcc_smoke_") as workdir:
        args = CommArgs(
            strategy_file=f"{workdir}/strategy.xml",
            logical_graph=f"{workdir}/logical_graph.xml",
            topology_dir=workdir,
            entry_point=DETECT,
            parallel_degree=2,
        )
        AdapCC.init(args, mesh=mesh)
        AdapCC.setup(ALLREDUCE)

        for i in (1, 2, 3):
            x = jnp.stack([jnp.ones(16) * i for _ in range(w)])
            out = np.asarray(AdapCC.allreduce(x, size=16, chunk_bytes=8))
            for r in range(w):
                vals = out[r].astype(int).tolist()
                print(f"rank {r} allreduce(ones*{i}) -> {vals}")

        # subset collective: the last rank is a relay; active ranks still sum
        x = jnp.stack([jnp.ones(16) * (r + 1) for r in range(w)])
        active = list(range(w - 1))
        out = np.asarray(AdapCC.allreduce(x, active_gpus=active))
        print(f"partial allreduce over active {active} -> {int(out[0][0])}")

        AdapCC.clear(ALLREDUCE)
    print("smoke benchmark complete")


if __name__ == "__main__":
    smoke_benchmark()
