"""Input pipeline: prefetcher ordering, sharding, laziness, failure path."""

import threading
import time

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from adapcc_tpu.data import batch_indices, device_batches, prefetch_to_device


def test_prefetch_preserves_order_and_values():
    src = [np.full((4,), i, np.float32) for i in range(7)]
    out = list(prefetch_to_device(iter(src), size=3))
    assert len(out) == 7
    for i, x in enumerate(out):
        assert isinstance(x, jax.Array)
        np.testing.assert_array_equal(np.asarray(x), src[i])


def test_prefetch_runs_ahead_of_consumer():
    """With size=2 the producer stages batches before they are pulled."""
    produced = []
    gate = threading.Event()

    def slow_consumer_source():
        for i in range(5):
            produced.append(i)
            yield np.asarray([i])
        gate.set()

    it = prefetch_to_device(slow_consumer_source(), size=2)
    first = next(it)
    # producer keeps going without further pulls: eventually ≥3 produced
    deadline = time.time() + 5
    while len(produced) < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert len(produced) >= 3, produced
    rest = list(it)
    assert [int(np.asarray(x)[0]) for x in [first, *rest]] == [0, 1, 2, 3, 4]
    assert gate.is_set()


def test_prefetch_propagates_producer_error():
    def bad():
        yield np.zeros(2)
        raise KeyError("boom")

    it = prefetch_to_device(bad(), size=2)
    next(it)
    with pytest.raises(RuntimeError, match="prefetch producer failed") as ei:
        next(it)
    assert isinstance(ei.value.__cause__, KeyError)


def test_prefetch_rejects_bad_size():
    with pytest.raises(ValueError, match="size"):
        next(prefetch_to_device(iter([]), size=0))


def test_batch_indices_shuffle_and_drop_last():
    blocks = list(batch_indices(10, 4, seed=0))
    assert [len(b) for b in blocks] == [4, 4]  # tail of 2 dropped
    # deterministic under the same seed, different under another
    again = list(batch_indices(10, 4, seed=0))
    other = list(batch_indices(10, 4, seed=1))
    np.testing.assert_array_equal(np.concatenate(blocks), np.concatenate(again))
    assert not np.array_equal(np.concatenate(blocks), np.concatenate(other))
    # unshuffled keeps order
    plain = list(batch_indices(10, 4, seed=None))
    np.testing.assert_array_equal(np.concatenate(plain), np.arange(8))


def test_device_batches_sharded_over_mesh(mesh8):
    packed = np.arange(64 * 3, dtype=np.int32).reshape(64, 3)
    got = []
    for b in device_batches(packed, 16, mesh=mesh8, seed=5):
        assert b.sharding == NamedSharding(mesh8, P("ranks"))
        assert b.addressable_shards[0].data.shape == (2, 3)
        got.append(np.asarray(b))
    # one epoch covers each row exactly once
    rows = np.concatenate(got).tolist()
    assert len(rows) == 64
    assert sorted(tuple(r) for r in rows) == [tuple(r) for r in packed.tolist()]


def test_device_batches_validates_divisibility(mesh8):
    with pytest.raises(ValueError, match="not divisible"):
        next(device_batches(np.zeros((32, 2)), 12, mesh=mesh8))


def test_prefetch_abandoned_consumer_stops_producer():
    """Breaking out mid-epoch must unblock and stop the producer thread
    instead of leaving it parked on q.put holding device batches."""
    state = {"produced": 0}

    def source():
        for i in range(1000):
            state["produced"] = i + 1
            yield np.asarray([i])

    it = prefetch_to_device(source(), size=2)
    next(it)
    it.close()  # GeneratorExit at the yield → finally → stop event
    time.sleep(0.5)
    n = state["produced"]
    time.sleep(0.3)
    assert state["produced"] == n  # producer stopped advancing
    assert n < 1000
    assert not any(
        t.name == "adapcc-prefetch" and t.is_alive() for t in threading.enumerate()
    )


# --------------------------------------------------------------------------- #
# the feed's spans (docs/OBSERVABILITY.md): on under a profile, nothing off
# --------------------------------------------------------------------------- #


def _feed_series(snapshot):
    return {
        kind: {k: v for k, v in snapshot[kind].items() if k.startswith("data.")}
        for kind in ("timings", "samples", "counters")
    }


def test_feed_without_a_profile_records_nothing(mesh8):
    from adapcc_tpu.utils import default_registry

    before = _feed_series(default_registry().snapshot())
    assert len(list(device_batches(np.zeros((64, 4), np.float32), 8, mesh=mesh8))) == 8
    assert _feed_series(default_registry().snapshot()) == before


@pytest.mark.parametrize("prefetch", [1, 3])
def test_profiled_feed_counts_pulls_and_transfers(mesh8, profile, prefetch):
    from adapcc_tpu.utils import default_registry

    reg = default_registry()
    packed = np.zeros((64, 4), np.float32)
    n, batch_bytes = 8, 8 * 4 * 4
    # an epoch outside any profile first: it records nothing, and its off
    # checks are what tells this test's session from the one before it
    assert sum(1 for _ in device_batches(packed, 8, mesh=mesh8, prefetch=prefetch)) == n
    sent = reg.snapshot()["counters"].get("data.h2d_bytes", 0.0)
    with profile() as prof:
        pulled = sum(1 for _ in device_batches(packed, 8, mesh=mesh8, prefetch=prefetch))
    assert pulled == n
    snap = reg.snapshot()
    # one pull and one producer pass per batch, plus the one that finds the end
    assert snap["timings"]["data.pull"]["count"] == n + 1
    assert snap["timings"]["data.h2d"]["count"] == n + 1
    assert snap["counters"]["data.h2d_bytes"] - sent == n * batch_bytes
    depth = snap["samples"]["data.queue_depth"]
    assert depth["count"] == n + 1 and 0 <= depth["mean"] <= depth["max"] <= prefetch
    names = [name for name, *_ in prof.spans()]
    assert names.count("adapcc.data.pull") == n + 1 and names.count("adapcc.data.h2d") == n + 1
