"""adapcc-tpu: TPU-native adaptive collective-communication framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of JoeyYoung/adapcc
(reference layer map in SURVEY.md §1): topology detection, online network
profiling, communication-strategy synthesis (parallel spanning trees), chunked
pipelined tree/ring collectives, relay control (subset collectives with
straggler ranks demoted to forwarding relays), and heartbeat-based fault
tolerance — built on `jax.sharding.Mesh` + `shard_map` + XLA collectives +
Pallas ICI kernels instead of CUDA IPC / MPI / NCCL.

Public surface mirrors the reference's `adapcc.py` (reference adapcc.py:6-77):
``AdapCC.init / setup / allreduce / reduce / boardcast / alltoall /
reconstruct_topology / set_profile_freq / clear``.
"""

from adapcc_tpu.primitives import (
    ALLREDUCE,
    REDUCE,
    BOARDCAST,
    ALLGATHER,
    ALLTOALL,
    REDUCESCATTER,
    DETECT,
    PROFILE,
)
from adapcc_tpu.api import AdapCC

__version__ = "0.1.0"

__all__ = [
    "AdapCC",
    "ALLREDUCE",
    "REDUCE",
    "BOARDCAST",
    "ALLGATHER",
    "ALLTOALL",
    "REDUCESCATTER",
    "DETECT",
    "PROFILE",
    "__version__",
]
