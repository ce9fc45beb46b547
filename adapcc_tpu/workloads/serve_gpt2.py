"""Latency-SLO GPT-2 serving workload — the decode twin of train_gpt2.

The first non-training workload the adaptive-CC stack serves end to end
(docs/SERVING.md): a tensor-parallel GPT-2 behind the continuous batcher
(:mod:`adapcc_tpu.serve`), driven by a deterministic synthetic arrival
trace — seeded Poisson via ``jax.random``, or a replayed JSON artifact
through ``ADAPCC_SERVE_TRACE`` — with every decode-step allreduce routed
through the traced :class:`~adapcc_tpu.comm.engine.CollectiveEngine`, so
the size-adaptive algorithm selection (at serving payloads: the
small-message plane, docs/LATENCY.md) and the dispatch trace apply to
decode traffic.  The combine runs fp32 on purpose — exactness buys the
bit parity the acceptance drill pins; a quantized decode wire is open
work (ROADMAP item 3).

The run prints one ledger row per request (sojourn / TTFT on the
deterministic step clock, EOS eviction) and a summary with step-time
percentiles, SLO attainment, and the executed decode-collective algorithm
histogram read back from the dispatch trace — the serving analog of the
training workloads' step meters.

Run (virtual pod)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
    python -m adapcc_tpu.workloads.serve_gpt2 --requests 8 --slots 4
"""

from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--requests", type=int, default=8,
                   help="synthetic requests to serve (ignored when "
                        "ADAPCC_SERVE_TRACE replays an artifact)")
    p.add_argument("--rate", type=float, default=0.25,
                   help="Poisson arrival rate (requests per decode step)")
    p.add_argument("--slots", type=int, default=None,
                   help="decode-slot count (default: ADAPCC_SERVE_SLOTS "
                        "env > 4)")
    p.add_argument("--seed", type=int, default=0,
                   help="arrival-trace seed (per-request RNG seeds derive "
                        "from it)")
    p.add_argument("--slo-ms", type=float, default=None,
                   help="per-request sojourn SLO in milliseconds "
                        "(default: ADAPCC_SERVE_SLO_MS env > none)")
    p.add_argument("--algo", default="auto",
                   help="decode-step collective algorithm "
                        "(auto/ring/rd/tree; ADAPCC_COLL_ALGO outranks)")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0)
    p.add_argument("--eos-id", type=int, default=None,
                   help="EOS token: a sampled EOS latches the stream and "
                        "evicts the lane early (slot reuse)")
    p.add_argument("--max-new-tokens", type=int, default=12,
                   help="upper bound of the per-request generation budget")
    p.add_argument("--disagg", action="store_true",
                   help="serve disaggregated: split the mesh into a "
                        "prefill pod and a decode pod (world must be "
                        "even), KV pages migrating over the traced "
                        "kv_transfer DCN stream (ADAPCC_DISAGG outranks)")
    p.add_argument("--kv-wire-dtype", default=None,
                   help="disagg KV-migration wire codec (off/bf16/int8; "
                        "ADAPCC_KV_WIRE_DTYPE outranks; 'off' = fp32, "
                        "bit-exact; lossy codecs are admitted only under "
                        "the ADAPCC_KV_KL_BOUND token-level KL bound)")
    p.add_argument("--ckpt", "--checkpoint", dest="ckpt", default=None,
                   help="serve trained params (TrainCheckpointState file "
                        "from train_gpt2 --checkpoint-file; shape flags "
                        "must match training)")
    p.add_argument("--trace-out", default=None,
                   help="save the (synthesized) arrival trace as a JSON "
                        "artifact replayable via ADAPCC_SERVE_TRACE")
    # model shape: same flags and defaults as train_gpt2 (vocab follows the
    # serving trace's synthetic token range when untrained)
    p.add_argument("--vocab", type=int, default=258)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=None,
                   help="default: one head per rank (n_head must divide "
                        "over the TP world)")
    p.add_argument("--dmodel", type=int, default=128)
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--json", action="store_true",
                   help="one JSON row per request plus a summary row")
    return p


def run(args) -> dict:
    """Serve the trace; returns the summary dict (the printed artifact)."""
    import jax
    import jax.numpy as jnp

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config
    from adapcc_tpu.serve import GPT2Server, resolve_disagg
    from adapcc_tpu.serve.trace import (
        load_serve_trace,
        synthesize_arrival_trace,
    )
    from adapcc_tpu.utils.observability import CollectiveTrace

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)
    disagg = resolve_disagg(getattr(args, "disagg", False))
    heads = args.heads if args.heads is not None else max(1, world)
    if heads % world:
        raise SystemExit(
            f"--heads {heads} must divide over the TP world {world} "
            "(head-sharded decode)"
        )
    if disagg:
        if world < 2 or world % 2:
            raise SystemExit(
                f"--disagg splits the mesh into two equal pods: world "
                f"{world} must be an even count >= 2"
            )
        if heads % (world // 2):
            raise SystemExit(
                f"--heads {heads} must divide over the per-pod TP world "
                f"{world // 2} under --disagg"
            )
    if args.dmodel % heads:
        raise SystemExit(
            f"--dmodel {args.dmodel} must divide over --heads {heads}"
        )
    if args.max_new_tokens < 1 or args.max_new_tokens > args.seq - 2:
        # seq - 2: the KV cache holds prompt + generation together and
        # the shortest synthesized prompt is 2 tokens
        raise SystemExit(
            f"--max-new-tokens {args.max_new_tokens} must be in "
            f"[1, --seq - 2 = {args.seq - 2}]: the KV cache holds the "
            "prompt (>= 2 tokens) and the generation together"
        )
    cfg = GPT2Config(
        vocab_size=args.vocab, max_seq=args.seq, n_layer=args.layers,
        n_head=heads, d_model=args.dmodel, dtype=jnp.float32,
    )
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    if args.ckpt:
        from adapcc_tpu.checkpoint import TrainCheckpointState, load_checkpoint

        state = TrainCheckpointState(params={"params": params})
        if not load_checkpoint(state, args.ckpt):
            raise SystemExit(
                f"checkpoint {args.ckpt!r} not found or incompatible with "
                "the model shape (--vocab/--seq/--layers/--heads/--dmodel "
                "must match training)"
            )
        params = state.params["params"]

    trace = load_serve_trace(world=world)
    if trace is None:
        # prompts must fit the cache next to the generation budget
        max_prompt = max(2, min(12, args.seq - args.max_new_tokens - 1))
        trace = synthesize_arrival_trace(
            world, args.requests, args.rate, seed=args.seed,
            prompt_len=(2, max_prompt),
            max_new_tokens=(max(1, args.max_new_tokens // 2),
                            args.max_new_tokens),
            vocab_size=args.vocab, eos_id=args.eos_id,
        )
    if args.trace_out:
        trace.save(args.trace_out)
        print(f"[serve] arrival trace -> {args.trace_out}")

    dispatch_trace = CollectiveTrace()
    if disagg:
        import numpy as np
        from jax.sharding import Mesh

        from adapcc_tpu.serve import ClusterRouter

        pw = world // 2
        devs = mesh.devices.flatten()
        server = ClusterRouter(
            cfg, params,
            Mesh(np.asarray(devs[:pw]), ("ranks",)),
            Mesh(np.asarray(devs[pw:]), ("ranks",)),
            prefill_slots=args.slots, decode_slots=args.slots,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id, algo=args.algo,
            trace=dispatch_trace, slo_ms=args.slo_ms,
            kv_wire_dtype=args.kv_wire_dtype,
        )
    else:
        server = GPT2Server(
            cfg, params, mesh, slots=args.slots,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id, algo=args.algo,
            trace=dispatch_trace, slo_ms=args.slo_ms,
        )
    server.submit_trace(trace)
    results = server.run()

    for r in results:
        row = {
            "req_id": r.req_id,
            "arrival_step": r.arrival_step,
            "admitted_step": r.admitted_step,
            "ttft_steps": r.ttft_steps,
            "sojourn_steps": r.sojourn_steps,
            "eos_evicted": r.eos_evicted,
            "generated": r.generated,
        }
        if args.json:
            print(json.dumps(row))
        else:
            print(
                f"[serve] req={r.req_id:>3} arrive={r.arrival_step:>4} "
                f"admit={r.admitted_step:>4} ttft={r.ttft_steps:>3} "
                f"sojourn={r.sojourn_steps:>4}"
                f"{' EOS' if r.eos_evicted else '    '} "
                f"tokens={r.generated}"
            )
    summary = server.summary()
    # the executed decode collectives, read back from the dispatch trace:
    # which algorithm actually ran (auto → the small-message plane at
    # serving payloads) — the observable the tail claims hang on
    algos: dict = {}
    kv_events = 0
    for e in dispatch_trace.events():
        if e.primitive == "allreduce":
            algos[e.impl] = algos.get(e.impl, 0) + 1
        elif e.primitive == "kv_transfer":
            kv_events += 1
    summary["decode_collectives"] = algos
    if disagg:
        # every KV migration must be visible in the dispatch trace — the
        # acceptance drill cross-checks this count against kv_stream
        summary["kv_transfer_events"] = kv_events
    summary["trace_label"] = trace.label
    if args.json:
        print(json.dumps({"summary": summary}, sort_keys=True))
    else:
        print(f"[serve] summary: {json.dumps(summary, sort_keys=True)}")
    return summary


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
