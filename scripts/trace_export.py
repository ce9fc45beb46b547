"""Record a short collective session and export it as a Perfetto trace.

Drives a virtual-pod (or real-TPU) engine through a handful of traced
dispatches with the tuner in ``record`` mode — so events carry measured
``duration_s`` — then writes ``chrome://tracing`` JSON via
:meth:`adapcc_tpu.utils.observability.CollectiveTrace.dump_chrome_trace`.
Open the output at https://ui.perfetto.dev (``make trace-export``).

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m scripts.trace_export [out.json]
"""

from __future__ import annotations

import os
import sys


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    # --no-summary drops the per-impl p50/p99 summary track from the
    # export (the raw-events-only view); default keeps it, so decode-step
    # tail behavior is one Perfetto click, no hand-aggregation
    summary = "--no-summary" not in argv
    argv = [a for a in argv if a != "--no-summary"]
    out = argv[0] if argv else os.path.join(
        "benchmarks", "results", "trace_export.json"
    )
    # record mode: time every dispatch into the trace (and the tuning db,
    # pointed at a scratch file so a demo run never pollutes the real one)
    os.environ.setdefault("ADAPCC_TUNER", "record")
    os.environ.setdefault(
        "ADAPCC_TUNER_DB",
        os.path.join("benchmarks", "results", "trace_export_tuning.jsonl"),
    )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils.observability import CollectiveTrace

    world = len(jax.devices())
    mesh = build_world_mesh(world)
    trace = CollectiveTrace()
    engine = CollectiveEngine(mesh, Strategy.ring(world), trace=trace)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(world, 8192)), jnp.float32
    )
    for _ in range(3):
        jax.block_until_ready(engine.all_reduce(x))
        jax.block_until_ready(engine.all_gather(x))
        if world >= 2:
            jax.block_until_ready(engine.ring_allreduce(x, wire_dtype="int8"))
            jax.block_until_ready(engine.ring_allreduce(x))
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    engine.trace.dump_chrome_trace(out, impl_summary=summary)
    timed = sum(1 for e in trace.events() if "duration_s" in e.extra)
    print(
        f"[trace-export] {len(trace.events())} events ({timed} timed) -> {out}"
    )
    for impl, stats in trace.impl_summary().items():
        p50 = stats["p50_s"]
        p99 = stats["p99_s"]
        print(
            f"[trace-export]   {impl:<14} n={stats['count']:>4} "
            f"timed={stats['timed']:>4}"
            + (
                f"  p50={p50 * 1e6:>10.1f}us  p99={p99 * 1e6:>10.1f}us"
                if p50 is not None else ""
            )
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
