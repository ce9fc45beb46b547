"""Job launcher — the reference launcher.py, with jax.distributed as the world.

The reference packs an ``mpirun -np N -H host:slots,...`` command line, writes
``topology/ip_table.txt`` (one host line per rank), scp-disseminates it, and
execs the training script with the required flag contract forwarded
(launcher.py:34-86).  The TPU analog keeps steps 2-4 byte-compatible and
replaces mpirun with per-host process launch wired to the
``jax.distributed`` coordinator: one process per host (each process owns all
its local chips), with ``JAX_COORDINATOR_ADDRESS`` plus
``ADAPCC_NUM_PROCESSES`` / ``ADAPCC_PROCESS_ID`` replacing ``MASTER_ADDR`` /
world size / rank.  Workloads call :func:`maybe_initialize_distributed` to
consume that contract (the analog of reading ``OMPI_COMM_WORLD_*``,
reference commu.py:446-448).

Single-host virtual pods (the test rig) get
``XLA_FLAGS=--xla_force_host_platform_device_count=N JAX_PLATFORMS=cpu``
instead — the analog of the reference's fake multi-node localhost launches
(units-test/launch_get_wait_time.sh ``-H 127.0.0.1:4,127.0.0.1:4``).
"""

from __future__ import annotations

import argparse
import functools
import os
import shlex
import subprocess
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from adapcc_tpu.launch.dispatcher import Dispatcher


@dataclass(frozen=True)
class HostSpec:
    ip: str
    num_chips: int


def parse_ips(spec: str) -> List[HostSpec]:
    """Parse ``host:chips,host:chips,...`` (reference ``--ips`` format)."""
    hosts = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        ip, _, n = item.partition(":")
        hosts.append(HostSpec(ip=ip, num_chips=int(n) if n else 1))
    if not hosts:
        raise ValueError(f"empty --ips spec: {spec!r}")
    return hosts


def order_hosts(hosts: Sequence[HostSpec], master: Optional[str]) -> List[HostSpec]:
    """Master's host first — rank 0 lives on the master node (launcher.py:8-9)."""
    hosts = list(hosts)
    if master is None:
        return hosts
    for i, h in enumerate(hosts):
        if h.ip == master:
            return [hosts[i], *hosts[:i], *hosts[i + 1 :]]
    raise ValueError(f"--master {master!r} is not one of the --ips hosts")


def write_ip_table(hosts: Sequence[HostSpec], path: str) -> List[str]:
    """One line per rank, in host order (launcher.py:64-79); callers pass
    the master-first ordering from :func:`order_hosts`."""
    from adapcc_tpu.strategy.xml_io import write_ip_table as write_lines

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    lines = [h.ip for h in hosts for _ in range(h.num_chips)]
    write_lines(lines, path)
    return lines


def forwarded_flags(args: argparse.Namespace) -> List[str]:
    """The required flag contract every exec-file accepts (launcher.py:53-62)."""
    return [
        f"--port={args.socket_port}",
        f"--entry_point={args.entry_point}",
        f"--strategy_file={args.strategy_file}",
        f"--logical_graph={args.logical_graph}",
        f"--parallel_degree={args.parallel_degree}",
        f"--profile_freq={args.profile_freq}",
    ]


def _exec_argv(exec_file: str, flags: Sequence[str]) -> List[str]:
    """``python script.py`` or ``python -m pkg.mod`` + forwarded flags."""
    if exec_file.startswith("-m "):
        return [sys.executable, "-m", exec_file[3:].strip(), *flags]
    return [sys.executable, exec_file, *flags]


@functools.lru_cache(maxsize=1)
def _local_identities() -> frozenset:
    """Every name/address this machine answers to, computed once per process.

    DNS of the hostname alone is unreliable (Debian maps the hostname to
    127.0.1.1; interface IPs often have no PTR/A records), so also discover
    the primary interface addresses via the UDP connect trick — no packets
    are sent, the kernel just picks the source address it would route with.
    """
    import socket

    ids = set()
    try:
        ids.add(socket.gethostname())
        ids.add(socket.getfqdn())
        for name in list(ids):
            try:
                ids.update(socket.gethostbyname_ex(name)[2])
            except OSError:
                pass
    except OSError:
        pass
    for probe in ("8.8.8.8", "2001:4860:4860::8888"):
        fam = socket.AF_INET6 if ":" in probe else socket.AF_INET
        try:
            with socket.socket(fam, socket.SOCK_DGRAM) as s:
                s.connect((probe, 80))
                ids.add(s.getsockname()[0])
        except OSError:
            pass
    return frozenset(ids)


def _is_local_host(ip: str) -> bool:
    """Does ``ip`` name the machine the launcher runs on?"""
    if ip in ("127.0.0.1", "::1", "localhost"):
        return True
    return ip in _local_identities()


def _virtual_env(num_chips: int) -> Dict[str, str]:
    """Forced-CPU virtual-pod env for one process."""
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={num_chips}"
        ).strip(),
    }


def build_launch_plan(
    args: argparse.Namespace, hosts: Optional[List[HostSpec]] = None
) -> List[Dict]:
    """One launch record per process: command + env.

    Multi-host: one process per host (master first), ssh-wrapped for remote
    hosts, with the jax.distributed coordinator env.  Single host: one local
    process exposing all chips (virtual CPU devices when ``--virtual``).
    """
    if hosts is None:
        hosts = order_hosts(parse_ips(args.ips), args.master)
    master = args.master or hosts[0].ip
    coordinator = f"{master}:{args.coordinator_port}"
    argv = _exec_argv(args.exec_file, forwarded_flags(args))

    plan: List[Dict] = []
    if len(hosts) == 1:
        env = _virtual_env(hosts[0].num_chips) if args.virtual else {}
        plan.append({"host": hosts[0].ip, "cmd": argv, "env": env})
        return plan

    for idx, h in enumerate(hosts):
        env = {
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "ADAPCC_NUM_PROCESSES": str(len(hosts)),
            "ADAPCC_PROCESS_ID": str(idx),
        }
        if args.virtual:
            # fake multi-node on localhost: every process gets its own
            # forced-CPU device set, joined through the coordinator (the
            # reference's -H 127.0.0.1:4,127.0.0.1:4 localhost launches)
            env.update(_virtual_env(h.num_chips))
        if args.virtual or _is_local_host(h.ip):
            cmd = argv  # local process; env rides the Popen env dict
        else:
            exports = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
            remote = " ".join(shlex.quote(a) for a in argv)
            cmd = [
                "ssh", h.ip,
                f"cd {shlex.quote(os.getcwd())} && {exports} {remote}",
            ]
        plan.append({"host": h.ip, "cmd": cmd, "env": env})
    return plan


def maybe_initialize_distributed() -> bool:
    """Join the multi-host world described by the launcher env contract.

    Reads ``JAX_COORDINATOR_ADDRESS`` + ``ADAPCC_NUM_PROCESSES`` /
    ``ADAPCC_PROCESS_ID`` and calls ``jax.distributed.initialize``; returns
    False when launched single-host.  Call before first device use.
    """
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    num = os.environ.get("ADAPCC_NUM_PROCESSES")
    if not addr or not num or int(num) <= 1:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=int(num),
        process_id=int(os.environ.get("ADAPCC_PROCESS_ID", "0")),
    )
    return True


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    # reference launcher flag contract (launcher.py:19-32); mpi-path/net-device
    # have no TPU meaning and are accepted-but-ignored for script compat
    p.add_argument("--num-process", type=int, default=None, help="ignored; derived from --ips")
    p.add_argument("--ips", type=str, default="127.0.0.1:8")
    p.add_argument("--master", type=str, default=None)
    p.add_argument("--mpi-path", type=str, default=None, help="ignored (no MPI on TPU)")
    p.add_argument("--net-device", type=str, default=None, help="ignored (ICI/DCN is implicit)")
    p.add_argument("--exec-file", type=str, default="-m adapcc_tpu.workloads.train_ddp")
    p.add_argument("--socket_port", type=str, default="5000")
    p.add_argument("--entry_point", type=int, default=-1, help="6:detect, 7:profile, -1:skip")
    p.add_argument("--strategy_file", type=str, default="topology/strategy.xml")
    p.add_argument("--logical_graph", type=str, default="topology/logical_graph.xml")
    p.add_argument("--parallel_degree", type=int, default=4)
    p.add_argument("--profile_freq", type=int, default=500)
    # TPU-native knobs
    p.add_argument("--coordinator_port", type=int, default=8476)
    p.add_argument("--ip_table", type=str, default="topology/ip_table.txt")
    # kvstore transport is runtime-only (needs a live coordinator) — not here
    p.add_argument("--transport", choices=["local", "ssh"], default="local")
    p.add_argument("--virtual", action="store_true", help="virtual CPU pod on one host")
    p.add_argument("--dry-run", action="store_true", help="print the plan, launch nothing")
    return p


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    hosts = order_hosts(parse_ips(args.ips), args.master)

    lines = write_ip_table(hosts, args.ip_table)
    dispatcher = Dispatcher(lines, transport=args.transport)
    dispatcher.dispatch_ip_table(args.ip_table, os.path.dirname(args.ip_table) or ".")

    plan = build_launch_plan(args, hosts)

    if args.dry_run:
        for rec in plan:
            print(rec["host"], " ".join(rec["cmd"]), rec["env"])
        return 0

    procs = []
    for rec in plan:
        env = {**os.environ, **rec["env"]}
        procs.append(subprocess.Popen(rec["cmd"], env=env))
    rcs = [p.wait() for p in procs]
    return next((rc for rc in rcs if rc != 0), 0)


if __name__ == "__main__":
    raise SystemExit(main())
