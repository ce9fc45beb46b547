"""Coordinator plane: rent-or-buy relay decisions + heartbeat fault detection.

Emulated multi-worker scenarios run each "rank" as a thread, the analog of
the reference's fake-multi-node localhost launches; timings are scaled down
so the suite stays fast and deterministic.
"""

import threading
import time

import pytest

from adapcc_tpu.coordinator import CoordinatorLogic, CoordinatorServer, Controller, Hooker


def run_workers(n, fn):
    """Run fn(rank) in n threads, return {rank: result}."""
    results = {}
    errors = []

    def wrap(r):
        try:
            results[r] = fn(r)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=wrap, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    return results


# --------------------------------------------------------------------------- #
# logic layer
# --------------------------------------------------------------------------- #

def fast_logic(world, **kw):
    kw.setdefault("relay_threshold", 0.05)
    kw.setdefault("time_slot", 0.002)
    kw.setdefault("fault_timeout", 0.5)
    return CoordinatorLogic(world, **kw)


def patient_logic(world, **kw):
    """For rounds in which every rank arrives: no clock may decide them.  At
    the nominal cost constants rent-or-buy buys a partial collective some
    10 ms after the second arrival, and threads (or gRPC channels) on a host
    shared with other test workers do not land that close.  Here the
    collective is priced at a minute, so the leader waits 15 s and more."""
    return fast_logic(
        world, relay_threshold=60.0, accumulated_size=60.0,
        accumulated_bandwidth=float(world), **kw
    )


def test_all_arrive_full_active_list():
    logic = patient_logic(4)
    out = run_workers(4, lambda r: logic.hook_arrive(step=0, rank=r))
    for r, active in out.items():
        assert sorted(active) == [0, 1, 2, 3]


def test_straggler_demoted_to_relay():
    # the healthy three are decided by no clock they could miss: at 0.15 s a
    # unit of the collective the leader rents 0.3 s with two ranks ready and
    # 0.5 s with three (at the nominal constants it bought some 10 ms after
    # the second arrival, and a third thread on a busy host lands later than
    # that); the straggler comes a second after the freeze
    logic = fast_logic(
        4, relay_threshold=1.0, accumulated_size=0.15, accumulated_bandwidth=1.0
    )
    results = {}

    def worker(r):
        if r == 3:
            time.sleep(1.5)  # way past the freeze and the relay threshold
        results[r] = logic.hook_arrive(step=0, rank=r)

    run_workers(4, worker)
    # fast ranks froze an active list without rank 3
    for r in (0, 1, 2):
        assert 3 not in results[r]
        assert sorted(results[r]) == [0, 1, 2]
    # the relay worker learns the frozen list, not a new one
    assert sorted(results[3]) == [0, 1, 2]


def test_leader_waits_for_near_arrivals():
    # second rank arrives within one time slot: rent-or-buy should wait for it
    logic = fast_logic(2, relay_threshold=0.5)
    out = {}

    def worker(r):
        if r == 1:
            time.sleep(0.004)
        out[r] = logic.hook_arrive(step=0, rank=r)

    run_workers(2, worker)
    assert sorted(out[0]) == [0, 1]


def test_sole_leader_escapes_after_fault_timeout():
    # world of 3 but only rank 0 ever arrives: the rent-or-buy conditions are
    # all gated on num_ready > 1, so without a fault-timeout escape the
    # leader would wait forever (the reference's rpc_server.py:69-96 does)
    logic = fast_logic(3, fault_timeout=0.05)
    start = time.monotonic()
    active = logic.hook_arrive(step=0, rank=0)
    elapsed = time.monotonic() - start
    assert active == [0]
    assert elapsed < 5, "sole leader failed to escape promptly"


def test_controller_barrier_all_alive():
    logic = patient_logic(3)
    # hook phase freezes the active list first
    run_workers(3, lambda r: logic.hook_arrive(step=5, rank=r))
    out = run_workers(3, lambda r: logic.controller_arrive(step=5, rank=r))
    for active, status in out.values():
        assert status == 1
        assert sorted(active) == [0, 1, 2]


def test_controller_fault_timeout_returns_alive_subset():
    logic = fast_logic(3, fault_timeout=0.1)
    # rank 2 never heartbeats
    out = run_workers(2, lambda r: logic.controller_arrive(step=0, rank=r))
    for active, status in out.values():
        assert status == 0
        assert sorted(active) == [0, 1]


def test_steps_are_independent():
    logic = patient_logic(2)
    run_workers(2, lambda r: logic.hook_arrive(step=0, rank=r))
    out = run_workers(2, lambda r: logic.hook_arrive(step=1, rank=r))
    assert sorted(out[0]) == [0, 1]
    logic.forget_steps_before(1)
    assert logic.active_list(0) is None
    assert logic.active_list(1) == [0, 1] or sorted(logic.active_list(1)) == [0, 1]


# --------------------------------------------------------------------------- #
# gRPC transport
# --------------------------------------------------------------------------- #

@pytest.fixture
def server():
    logic = patient_logic(3)
    srv = CoordinatorServer(3, port=0, logic=logic).start()
    yield srv
    srv.stop()


def test_grpc_hook_and_controller_roundtrip(server):
    port = server.port

    def worker(r):
        hooker = Hooker("127.0.0.1", port)
        controller = Controller("127.0.0.1", port)
        active = hooker.send_ready_request(0, r)
        relay = controller.send_relay_request(0, r)
        hooker.close()
        controller.close()
        return active, relay

    out = run_workers(3, worker)
    for active, (relay_active, status) in out.values():
        assert sorted(active) == [0, 1, 2]
        assert status == 1
        assert sorted(relay_active) == [0, 1, 2]


def test_grpc_fault_detection(server):
    port = server.port

    def worker(r):
        controller = Controller("127.0.0.1", port)
        try:
            return controller.send_relay_request(0, r)
        finally:
            controller.close()

    out = run_workers(2, worker)  # rank 2 missing
    for active, status in out.values():
        assert status == 0
        assert sorted(active) == [0, 1]


def test_stop_drains_blocked_hook_waiters():
    """A worker blocked on send_ready_request while the coordinator dies
    must unblock with a clean RPC error, not hang: stop() fires the logic's
    shutdown sentinel (CoordinatorShutdown -> UNAVAILABLE abort) before the
    transport goes down."""
    import grpc

    # huge timeouts: without the drain, the blocked waiter would sit for
    # minutes — the test passing quickly IS the property
    logic = CoordinatorLogic(
        3, relay_threshold=60.0, time_slot=0.01, fault_timeout=60.0
    )
    srv = CoordinatorServer(3, port=0, logic=logic).start()
    port = srv.port
    outcome = {}

    def blocked_worker():
        hooker = Hooker("127.0.0.1", port)
        try:
            outcome["result"] = hooker.send_ready_request(0, 0)
        except grpc.RpcError as e:
            outcome["error"] = e.code()
        finally:
            hooker.close()

    t = threading.Thread(target=blocked_worker)
    t.start()
    # let the RPC land and start its rent-or-buy wait (sole leader)
    deadline = time.monotonic() + 5
    while not logic._ready.get(0) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert logic._ready.get(0) == [0], "worker never reached the hook funnel"
    t0 = time.monotonic()
    srv.stop()
    t.join(timeout=5)
    assert not t.is_alive(), "blocked hook waiter did not drain on stop()"
    assert time.monotonic() - t0 < 5
    assert outcome.get("error") is not None, (
        f"expected a clean RPC error, got {outcome!r}"
    )


def test_stop_drains_blocked_controller_waiters():
    import grpc

    logic = CoordinatorLogic(
        2, relay_threshold=60.0, time_slot=0.01, fault_timeout=60.0
    )
    srv = CoordinatorServer(2, port=0, logic=logic).start()
    outcome = {}

    def blocked_worker():
        controller = Controller("127.0.0.1", srv.port)
        try:
            outcome["result"] = controller.send_relay_request(0, 0)
        except grpc.RpcError as e:
            outcome["error"] = e.code()
        finally:
            controller.close()

    t = threading.Thread(target=blocked_worker)
    t.start()
    deadline = time.monotonic() + 5
    while not logic._heartbeats.get(0) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert logic._heartbeats.get(0) == [0]
    srv.stop()
    t.join(timeout=5)
    assert not t.is_alive(), "blocked controller waiter did not drain"
    assert outcome.get("error") is not None


# --------------------------------------------------------------------------- #
# communicator integration
# --------------------------------------------------------------------------- #

def test_calibrate_sets_dimensionally_honest_costs():
    """calibrate() replaces the reference's unit-less constants: the initial
    rent becomes the ring-allreduce seconds estimate 2(n-1)/n * bytes/bw,
    and the commit threshold scales with gradient volume — a bigger model
    waits longer before paying the partial-collective make-up cost."""
    logic = CoordinatorLogic(8)
    logic.calibrate(total_grad_bytes=400e6, link_bandwidth_gbps=100.0)
    expect = 2 * 7 / 8 * 400e6 / (100.0 * 1e9)
    assert logic._initial_rent_cost() == pytest.approx(expect)

    small, big = CoordinatorLogic(8), CoordinatorLogic(8)
    small.calibrate(1e6, 100.0)
    big.calibrate(1e9, 100.0)
    # rent the leader tolerates before freezing a 7-of-8 partial set
    slack = lambda lg: lg._buy_cost(7) - lg._initial_rent_cost()  # noqa: E731
    assert slack(big) > slack(small) > 0

    with pytest.raises(ValueError, match="positive"):
        logic.calibrate(0, 100.0)


def test_communicator_calibrates_from_profiled_bandwidth(tmp_path, mesh4):
    """calibrate_coordinator reads the bootstrap's gathered profile CSVs and
    feeds the measured mean link bandwidth into the server's logic."""
    from adapcc_tpu.communicator import Communicator
    from adapcc_tpu.config import CommArgs

    topo = tmp_path / "topo"
    topo.mkdir()
    with open(topo / "topo_profile_0", "w") as f:
        for s in range(4):
            for d in range(4):
                if s != d:
                    f.write(f"{s},{d},lat,0.00001\n")
                    f.write(f"{s},{d},bw,25.0\n")
    args = CommArgs(
        topology_dir=str(topo),
        strategy_file=str(topo / "strategy.xml"),
        logical_graph=str(topo / "lg.xml"),
    )
    # launcher-written 2-host ip table: calibration must average ONLY the
    # inter-process links (fast intra-host ICI would inflate the estimate)
    with open(topo / "ip_table.txt", "w") as f:
        f.write("\n".join(["10.0.0.1", "10.0.0.1", "10.0.0.2", "10.0.0.2"]))
    with open(topo / "topo_profile_0", "w") as f:  # overwrite: 100 intra / 10 inter
        for s in range(4):
            for d in range(4):
                if s != d:
                    bw = 100.0 if (s < 2) == (d < 2) else 10.0
                    f.write(f"{s},{d},lat,0.00001\n")
                    f.write(f"{s},{d},bw,{bw}\n")
    comm = Communicator(args, mesh=mesh4)
    # without a server (worker process): no-op, defaults stay
    assert comm.calibrate_coordinator(1e6) is False
    comm.enable_coordinator(is_master=True, process_rank=0, num_processes=2, port=0)
    try:
        assert comm.calibrate_coordinator(100e6) is True
        logic = comm._coordinator_server.logic
        assert logic.accumulated_size == pytest.approx(0.1)  # GB
        # the coordinator's world is PROCESSES (n=2): the cost model prices
        # the inter-process collective, so only the 10 GB/s links count
        assert logic.accumulated_bandwidth == pytest.approx(2 * 10.0)
    finally:
        comm.clear()


def test_trainer_pushes_calibration_on_first_step(tmp_path, mesh4):
    """DDPTrainer's first step feeds its real gradient volume into the
    in-process coordinator's rent-or-buy model (closing the loop from
    profile + model to policy)."""
    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.communicator import Communicator
    from adapcc_tpu.config import CommArgs
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy

    topo = tmp_path / "topo"
    topo.mkdir()
    with open(topo / "topo_profile_0", "w") as f:
        for s in range(4):
            for d in range(4):
                if s != d:
                    f.write(f"{s},{d},lat,0.00001\n{s},{d},bw,25.0\n")
    args = CommArgs(
        topology_dir=str(topo),
        strategy_file=str(topo / "strategy.xml"),
        logical_graph=str(topo / "lg.xml"),
    )
    comm = Communicator(args, mesh=mesh4)
    comm.enable_coordinator(is_master=True, process_rank=0, num_processes=1, port=0)
    try:
        params = {"w": jnp.ones((8, 4), jnp.float32)}  # 128 bytes
        tx = optax.sgd(0.1)
        trainer = DDPTrainer(
            lambda p, b: jnp.mean((b @ p["w"]) ** 2), tx, mesh4,
            Strategy.ring(4), communicator=comm,
        )
        state = TrainState.create(params, tx)
        batch = jnp.ones((8, 8), jnp.float32)
        trainer.step(state, batch)
        logic = comm._coordinator_server.logic
        assert trainer._coord_calibrated
        assert logic.accumulated_size == pytest.approx(128 / 1e9)
    finally:
        comm.clear()


def test_communicator_coordinator_plane(tmp_path, mesh4):
    from adapcc_tpu.communicator import Communicator
    from adapcc_tpu.config import CommArgs

    args = CommArgs(
        topology_dir=str(tmp_path / "topo"),
        strategy_file=str(tmp_path / "topo" / "strategy.xml"),
        logical_graph=str(tmp_path / "topo" / "lg.xml"),
    )
    comm = Communicator(args, mesh=mesh4)
    comm.enable_coordinator(is_master=True, process_rank=0, num_processes=1, port=0)
    comm.update_relay(0)
    active = comm.hook_ready(0)
    assert active == [0]
    deadline = time.time() + 2
    while comm.relay_active_list(0) is None and time.time() < deadline:
        time.sleep(0.01)
    assert comm.relay_active_list(0) == [0]
    assert comm.fault_worker_list == []
    comm.clear()
    assert comm._controller_thread is None
