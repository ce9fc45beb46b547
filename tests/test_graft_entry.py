"""The driver-contract entry file: parent-side behavior of dryrun_multichip.

A parent that has touched a JAX backend holds the chip, and cannot grow
virtual CPU devices afterwards.  These tests pin the contract: the module
imports without initializing a backend, and the parent unconditionally
spawns an unbuffered child pinned to the CPU pod — it never asks for a chip.
"""

import importlib
import sys


def _load_graft_entry():
    sys.path.insert(0, "/root/repo")
    try:
        return importlib.import_module("__graft_entry__")
    finally:
        sys.path.pop(0)


def test_module_import_does_not_init_backend():
    # a fresh interpreter importing the module must not initialize any XLA
    # backend (importing the jax *module* is fine; a backend holds the chip)
    import subprocess

    code = (
        "import sys; sys.path.insert(0, '/root/repo'); "
        "import __graft_entry__; "
        "import jax; "
        "assert not jax._src.xla_bridge._backends, 'module import initialized a backend'; "
        "print('clean')"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_parent_spawns_unbuffered_cpu_pod_child(monkeypatch):
    g = _load_graft_entry()
    calls = {}

    def fake_run(cmd, cwd=None, env=None, check=None):
        calls["cmd"], calls["env"], calls["check"] = cmd, env, check

        class R:
            returncode = 0

        return R()

    monkeypatch.delenv("_ADAPCC_DRYRUN_INPROC", raising=False)
    monkeypatch.setattr(g.subprocess, "run", fake_run)
    g.dryrun_multichip(8)

    assert calls["check"] is True
    assert "-u" in calls["cmd"], "child stdout must be unbuffered"
    env = calls["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONUNBUFFERED"] == "1"
    assert env["_ADAPCC_DRYRUN_INPROC"] == "1"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    code = calls["cmd"][-1]
    assert "_dryrun_impl(8)" in code


def test_parent_replaces_preset_device_count(monkeypatch):
    g = _load_graft_entry()
    captured = {}

    def fake_run(cmd, cwd=None, env=None, check=None):
        captured["env"] = env

        class R:
            returncode = 0

        return R()

    monkeypatch.delenv("_ADAPCC_DRYRUN_INPROC", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    monkeypatch.setattr(g.subprocess, "run", fake_run)
    g.dryrun_multichip(16)
    flags = captured["env"]["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=16" in flags
    assert "count=2" not in flags


def test_inproc_gate_runs_body_directly(monkeypatch):
    g = _load_graft_entry()
    ran = {}
    monkeypatch.setenv("_ADAPCC_DRYRUN_INPROC", "1")
    monkeypatch.setattr(g, "_dryrun_impl", lambda n: ran.setdefault("n", n))
    monkeypatch.setattr(
        g.subprocess, "run",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError("child spawned inside child")),
    )
    g.dryrun_multichip(8)
    assert ran["n"] == 8
