"""Input pipeline: host-side batching + async device prefetch.

The reference feeds its workloads with torch ``DataLoader`` iterators
(models/image-classification/main_elastic.py, models/gpt2/train_gpt2_ddp.py
dataset → padded batches); the host-to-GPU copy rides inside torch.  On TPU
the equivalent overlap must be built explicitly: a background thread moves
the next host batch to device (optionally already laid out in its
``NamedSharding``) while the current step computes, so the device never
waits on PCIe/host for input — the standard double-buffering recipe.

``device_batches`` is the one-call path used by the workloads: shuffled
full batches of a packed array, sharded over the mesh's data axis, with a
bounded prefetch queue.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from adapcc_tpu.comm.mesh import RANKS_AXIS
from adapcc_tpu.utils.observability import default_registry

_END = object()


class _PrefetchError:
    """Private in-band wrapper for a producer failure — unambiguous even
    when the iterator legitimately yields tuples."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_to_device(
    it: Iterator[Any],
    size: int = 2,
    sharding: Optional[Any] = None,
) -> Iterator[Any]:
    """Yield ``device_put`` results of ``it`` with ``size`` batches in flight.

    A daemon producer thread stages host→device transfers into a bounded
    queue: while the consumer computes on batch *n*, batches *n+1..n+size*
    are already copying.  ``sharding`` (a ``NamedSharding`` or pytree of
    them) commits each batch to its device layout at transfer time, so the
    compiled step never reshards its input.  Producer exceptions re-raise at
    the consumer's next pull, preserving the failure's traceback cause.
    """
    if size < 1:
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()  # consumer gone: unblock + stop the producer
    metrics = default_registry()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce() -> None:
        try:
            while not stop.is_set():
                # the layer's busy time per batch: next(it) materialises the
                # host batch, device_put hands it to the device (the pass
                # that finds the iterator exhausted is one more, empty, span)
                with metrics.span("data.h2d") as live:
                    batch = next(it, _END)
                    if batch is not _END:
                        if sharding is not None:
                            batch = jax.device_put(batch, sharding)
                        else:
                            batch = jax.device_put(batch)
                        if live:
                            metrics.incr(
                                "data.h2d_bytes",
                                sum(x.nbytes for x in jax.tree_util.tree_leaves(batch)),
                            )
                if not _put(batch) or batch is _END:
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at the consumer
            _put(_PrefetchError(e))

    t = threading.Thread(target=produce, daemon=True, name="adapcc-prefetch")
    t.start()
    try:
        while True:
            with metrics.span("data.pull") as live:
                if live:
                    metrics.sample("data.queue_depth", q.qsize())
                item = q.get()
            if item is _END:
                return
            if isinstance(item, _PrefetchError):
                raise RuntimeError("prefetch producer failed") from item.exc
            yield item
    finally:
        # an abandoned iterator (break / exception in the consumer) must not
        # leave the producer blocked holding device batches alive
        stop.set()


def batch_indices(
    n: int, batch: int, seed: Optional[int], drop_last: bool = True
) -> Iterator[np.ndarray]:
    """Index blocks for one epoch: shuffled when ``seed`` is given."""
    idx = (
        np.random.default_rng(seed).permutation(n)
        if seed is not None
        else np.arange(n)
    )
    end = n - batch + 1 if drop_last else n
    for i in range(0, end, batch):
        yield idx[i : i + batch]


def device_batches(
    packed: np.ndarray,
    batch: int,
    mesh: Optional[Mesh] = None,
    axis_name: str = RANKS_AXIS,
    seed: Optional[int] = 0,
    prefetch: int = 2,
) -> Iterator[Any]:
    """Shuffled ``[batch, ...]`` device batches of a packed host array.

    With a ``mesh``, each batch is committed sharded over ``axis_name``
    (the DDP layout) while the previous step runs; without one, it lands on
    the default device.  One pass = one epoch; reseed for the next.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if mesh is not None and batch % mesh.shape[axis_name]:
        raise ValueError(
            f"batch {batch} not divisible by mesh axis '{axis_name}' "
            f"({mesh.shape[axis_name]})"
        )
    sharding = (
        NamedSharding(mesh, P(axis_name)) if mesh is not None else None
    )

    def host_batches() -> Iterator[np.ndarray]:
        for idx in batch_indices(len(packed), batch, seed):
            yield packed[idx]

    return prefetch_to_device(host_batches(), size=prefetch, sharding=sharding)
