"""Test harness: a virtual 8-device CPU "pod".

Multi-chip behavior is tested without TPU hardware by forcing the host
platform to expose 8 XLA CPU devices (the analog of the reference's
fake-multi-node localhost launches, e.g. ``-H 127.0.0.1:4,127.0.0.1:4`` in
units-test/launch_get_wait_time.sh).  Must run before the first jax import.

The CPU backend compiles at optimisation level 0.  The suite's seconds are
the CPU compiler's: six workers keep the box's cores busy, and what a test
computes is tiny beside what compiling it costs (CHANGES.md, PR 38, has the
whole run at both levels; every test passes at the tolerance it had).  The
level reaches the CPU's code generation alone: a compile for the described
TPU (``tests/test_chip_compile.py``) gives the same text with and without
it, and nothing outside the tests sets it.  An ``XLA_FLAGS`` that names a
level (or a device count) is left as the caller set it; child processes
inherit both.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
for flag in ("xla_force_host_platform_device_count=8", "xla_backend_optimization_level=0"):
    if flag.split("=")[0] not in flags:
        flags = f"{flags} --{flag}".strip()
os.environ["XLA_FLAGS"] = flags

import time  # noqa: E402

import pytest  # noqa: E402

_SUITE_T0 = time.time()

#: The driver runs the whole suite as ``timeout 1470 python -m pytest tests/ -q
#: -m 'not slow' -p xdist -n 6 --dist loadfile`` (six workers, a file to a
#: worker) and counts only what finished inside the limit.  The guard below
#: warns at 85% of that limit, while there is still room.
_SUITE_BUDGET_S = 1250


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: not run by the driver, whose command deselects it (python -m "
        "pytest tests/ -q -m 'not slow' -n 6 --dist loadfile): a test marked "
        "slow guards nothing there.  Run them with -m slow.",
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Wall-time guard of the whole suite: prints the run's wall beside
    ``_SUITE_BUDGET_S`` and warns when a whole run went over it.  Non-fatal
    (a loaded box must not turn green tests red) but loudly visible, so that
    the change which adds the seconds is the one that takes them out again:
    compute what a module's tests share once a module (CHANGES.md, PR 38,
    has the cost by file).  Under xdist this runs on the controller, so the
    wall is the run's."""
    wall = time.time() - _SUITE_T0
    # count tests that RAN (deselected tests must not trip the whole-suite
    # gate; stats keys are public API, unlike _numcollected)
    n_run = sum(
        len(terminalreporter.stats.get(k, []))
        for k in ("passed", "failed", "error", "skipped")
    )
    terminalreporter.write_sep(
        "-", f"suite wall {wall:.0f}s (budget {_SUITE_BUDGET_S}s, {n_run} ran)"
    )
    if n_run > 400 and wall > _SUITE_BUDGET_S:  # whole-suite runs only
        terminalreporter.write_line(
            f"WARNING: the suite went over its {_SUITE_BUDGET_S}s budget by "
            f"{wall - _SUITE_BUDGET_S:.0f}s and the driver cuts it at 1470s: "
            "take the seconds out (pytest --durations=15 names the heaviest "
            "tests); a test marked slow is not run at all",
            red=True,
        )

# Build the native runtime once per checkout so the ctypes parity tests run
# instead of skipping (the .so is a build artifact, not committed).
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not os.path.exists(os.path.join(_REPO, "libadapcc_rt.so")):
    import subprocess

    try:
        subprocess.run(["make"], cwd=_REPO, capture_output=True, timeout=120)
    except Exception:
        pass  # no toolchain / wedged compile: the parity tests just skip


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return Mesh(devices[:8], ("ranks",))


@pytest.fixture(scope="session")
def mesh4():
    import jax
    from jax.sharding import Mesh

    return Mesh(jax.devices()[:4], ("ranks",))


@pytest.fixture(scope="session")
def mesh2():
    import jax
    from jax.sharding import Mesh

    return Mesh(jax.devices()[:2], ("ranks",))


class _Profile:
    """One JAX profiler session (Python frames off: they only slow the host)
    as a context manager; afterwards ``spans()`` gives the program's
    ``adapcc.*`` host events as ``(name, start_ns, duration_ns, stats)``."""

    def __init__(self, log_dir) -> None:
        self.log_dir = str(log_dir)

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.profiler.stop_trace()

    def spans(self):
        import glob
        import warnings

        from jax.profiler import ProfileData

        (path,) = glob.glob(self.log_dir + "/plugins/profile/*/*.xplane.pb")
        with warnings.catch_warnings():
            # iterating an event's stats warns about the binding's own type
            warnings.simplefilter("ignore", DeprecationWarning)
            return sorted(
                (e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats))
                for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU"
                for line in plane.lines
                for e in line.events
                if e.name.startswith("adapcc.")
            )


@pytest.fixture
def profile(tmp_path):
    """``with profile("a") as p: ...`` runs the block under a profiler
    session of its own; the program's spans are live inside it."""
    return lambda name="prof": _Profile(tmp_path / name)
