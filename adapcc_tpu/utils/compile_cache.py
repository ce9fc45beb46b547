"""JAX's persistent compilation cache, placed from outside or at one fixed
path, and the process's one watch of what JAX compiles.

The path is part of the cache key's lookup: a directory that moves between
runs never hits.  So the cache lives where ``JAX_COMPILATION_CACHE_DIR``
says — JAX reads that variable itself, nothing is set in code — and
otherwise at ``<checkout>/.jax_cache`` (git-ignored), never under a
temporary name, a pid or a timestamp.

The watch (:func:`compile_watch`) listens to ``jax.monitoring``'s compile
events and records them into the default registry (docs/OBSERVABILITY.md):
what a program cost to trace, to lower and to load, whether the persistent
cache held it, and which training step compiled on the hot path.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from pathlib import Path
from typing import Any, Iterator, Optional

from adapcc_tpu.utils.observability import MetricsRegistry, default_registry

logger = logging.getLogger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout cache directory (this file is
#: ``<checkout>/adapcc_tpu/utils/compile_cache.py``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile;
    returns the directory in force."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


# --- the watch ----------------------------------------------------------------

#: JAX's duration events that are kept, and the timing each is kept under
_TIMINGS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    # XLA's compile, or the persistent cache's read where it holds the program
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_read",
}
_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    # JAX counts a miss where it writes the entry, so a program under the
    # cache's thresholds is neither
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}


class Build:
    """What JAX reported on one thread inside one program's first call."""

    __slots__ = ("load_s", "cache_hits")

    def __init__(self) -> None:
        self.load_s = 0.0  # backend seconds: the compile, or the cache's read
        self.cache_hits = 0


class _Stall:
    """A training step found compiling: its host index, where on the clock
    the first event of it began and the last ended, and what it compiled."""

    __slots__ = ("step", "start", "end", "compiled")

    def __init__(self, step: int, start: float) -> None:
        self.step = step
        self.start = self.end = start
        self.compiled: list = []  # the programs' fun_names, as their backend events arrived


class _Here(threading.local):
    """What this thread is inside, so that an event is put where it belongs
    (JAX calls a listener on the thread that compiles)."""

    #: the host index of the ``DDPTrainer.step`` call, where that call is no
    #: program's first; a :class:`_Stall` once an event arrived inside it
    step: Any = None
    #: the :class:`Build` a program's first call is collecting into
    build: Optional[Build] = None


class CompileWatch:
    """One a process (:func:`compile_watch`): JAX keeps every listener it is
    given for the life of the process."""

    def __init__(self, registry: MetricsRegistry) -> None:
        import jax

        self.registry = registry
        self.here = _Here()
        # the counters a reader has to find at 0, not missing
        registry.incr("step.recompiles", 0)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **kwargs: Any) -> None:
        name = _TIMINGS.get(event)
        if name is None:
            return
        # an event carries its duration and no start: it ends now, on the
        # clock the benchmark's stamps are read from
        end = time.perf_counter()
        # the cache's read comes unnamed, inside the backend event of the program it read
        fun_name = str(kwargs.get("fun_name", "?"))
        self.registry.observe(name, seconds, end=end, fun_name=fun_name)
        here = self.here
        if here.build is not None:
            if name == "compile.backend":
                here.build.load_s += seconds
        elif here.step is not None:
            self._recompiled(here, name, fun_name, end - seconds, end)

    def _on_event(self, event: str, **kwargs: Any) -> None:
        name = _COUNTERS.get(event)
        if name is None:
            return
        self.registry.incr(name)
        build = self.here.build
        if build is not None and name == "compile.cache_hits":
            build.cache_hits += 1

    def _recompiled(self, here: _Here, name: str, fun_name: str, start: float, end: float) -> None:
        """An event inside a training step that is no program's first call:
        the step is counted once, whatever it compiles."""
        stall = here.step
        if not isinstance(stall, _Stall):
            stall = here.step = _Stall(stall, start)
            self.registry.incr("step.recompiles")
            self.registry.gauge("step.recompiles.last_step", stall.step)
        stall.end = end
        if name == "compile.backend":
            stall.compiled.append(fun_name)

    def left_step(self, stall: _Stall) -> None:
        """The step that :meth:`_recompiled` counted has returned: say once
        what it cost."""
        logger.warning(
            "step %d compiled on the hot path, %.3f s from the first event's start to the last one's end: %s",
            stall.step, stall.end - stall.start, ", ".join(stall.compiled) or "traced or lowered, nothing compiled",
        )

    @contextlib.contextmanager
    def building(self) -> Iterator[Build]:
        """A program's first call: this thread's events inside the block
        are the build's own, and no recompile."""
        here = self.here
        outer, here.build = here.build, Build()
        try:
            yield here.build
        finally:
            here.build = outer


_WATCH: Optional[CompileWatch] = None
_WATCH_LOCK = threading.Lock()


def compile_watch() -> CompileWatch:
    """THE process's watch, installed by the first caller (a trainer's
    construction) and recording into :func:`default_registry` from then on.
    It costs a step that compiles nothing nothing: JAX calls it only when it
    traces, lowers or compiles."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = CompileWatch(default_registry())
        return _WATCH
