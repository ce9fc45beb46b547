"""DDP training driver — the reference ``train_ddp.py`` re-shaped for TPU.

The reference flow (train_ddp.py:30-58): init AdapCC with the launcher flag
contract, register the allreduce bucket hook on a torch DDP model, call
``update_relay(step)`` every iteration, and ``reconstruct_topology`` every
``profile_freq`` steps.  This driver keeps that flow — same flags, same
lifecycle — with the jitted :class:`DDPTrainer` as the data plane and
synthetic data (the reference benchmarks run synthetic batches too).

Run (virtual pod):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m adapcc_tpu.workloads.train_ddp --model mlp --steps 20
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from adapcc_tpu import ALLREDUCE, AdapCC
from adapcc_tpu.comm.mesh import build_world_mesh
from adapcc_tpu.config import CommArgs
from adapcc_tpu.ddp import DDPTrainer, TrainState
from adapcc_tpu.primitives import DETECT


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    # reference launcher flag contract (launcher.py:19-32)
    p.add_argument("--port", type=int, default=50051)
    p.add_argument("--strategy_file", type=str, default="topology/strategy.xml")
    p.add_argument("--logical_graph", type=str, default="topology/logical_graph.xml")
    p.add_argument("--entry_point", type=int, default=DETECT)
    p.add_argument("--parallel_degree", type=int, default=2)
    p.add_argument("--profile_freq", type=int, default=0)
    # workload knobs
    p.add_argument(
        "--model",
        choices=["mlp", "vgg", "resnet18", "resnet50", "vit", "gpt2"],
        default="mlp",
    )
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--world", type=int, default=None, help="mesh size (default: all devices)")
    p.add_argument("--coordinator", action="store_true", help="enable the relay/fault coordinator")
    p.add_argument(
        "--dp-mode", choices=["ddp", "fsdp", "zero1"], default="ddp",
        help="data-parallel state layout: ddp replicates (adaptive bucket "
        "hook); fsdp shards params+optimizer via GSPMD; zero1 shards the "
        "optimizer on a flat fp32 master (both beyond the reference)",
    )
    p.add_argument(
        "--zero1-ring", action="store_true",
        help="zero1: ride the Pallas ICI ring kernels for the "
        "reduce-scatter/all-gather pair instead of XLA's (the hand-tuned "
        "data plane; shards become VMEM-tile aligned)",
    )
    p.add_argument(
        "--ring-chunk-bytes", type=int, default=0,
        help="zero1-ring staging granularity in bytes (0 = the synthesized "
        "default); payloads above it stream through fixed HBM→VMEM staging. "
        "ADAPCC_RING_CHUNK_BYTES overrides for sweeps",
    )
    p.add_argument(
        "--min-shard-elems", type=int, default=2**14,
        help="fsdp: leaves smaller than this stay replicated",
    )
    p.add_argument(
        "--no-bsp", dest="is_bsp", action="store_false", default=True,
        help="async relay mode: straggler gradients are buffered and folded "
        "into their next active step instead of dropped (reference is_bsp)",
    )
    p.add_argument(
        "--grad-compress", choices=["off", "bf16", "int8"], default="off",
        help="gradient-sync wire codec (quant registry): bf16 halves ICI/DCN "
        "bytes (~bf16-eps error on the synced mean); int8 quantizes "
        "block-wise with per-block fp32 scales (docs/QUANT.md)",
    )
    p.add_argument(
        "--wire-dtype", choices=["off", "bf16", "int8", "strategy"],
        default=None,
        help="wire codec for the data plane, overriding --grad-compress "
        "when given: ddp mode feeds the gradient hook ('strategy' adopts "
        "the synthesized Strategy.wire_dtype); zero1 mode feeds the "
        "reduce-scatter contribution.  ADAPCC_WIRE_DTYPE overrides for "
        "sweeps (malformed value -> loud error)",
    )
    p.add_argument(
        "--error-feedback", action="store_true",
        help="carry the per-rank quantization residual into the next step's "
        "gradient (closes the int8 accuracy gap; requires --dp-mode ddp)",
    )
    p.add_argument(
        "--tune", action="store_true",
        help="measurement-driven autotuning (adapcc_tpu/tuner): record each "
        "step's walltime into the tuning database (ADAPCC_TUNER_DB, default "
        "topology/tuning.jsonl) and adopt the policy's choices — the "
        "gradient-sync wire codec in ddp mode, the ring staging chunk in "
        "zero1 mode.  ADAPCC_TUNER=off disables globally; "
        "ADAPCC_RING_CHUNK_BYTES / ADAPCC_WIRE_DTYPE still override "
        "whatever the tuner picks (docs/TUNER.md)",
    )
    p.add_argument(
        "--overlap", choices=["off", "microbatch", "bucket"], default="off",
        help="overlapped gradient sync (docs/OVERLAP.md): bucket = "
        "per-bucket rolling collectives honoring the plan's chunk_bytes "
        "(bitwise-identical gradients); microbatch = pipeline each "
        "microbatch delta's allreduce behind the next microbatch's "
        "compute (needs --accum >= 2, --dp-mode ddp).  ADAPCC_OVERLAP "
        "overrides for sweeps (malformed value -> loud error)",
    )
    p.add_argument(
        "--accum", type=int, default=1,
        help="gradient accumulation microbatches per step (ddp mode; the "
        "axis the microbatch overlap schedule pipelines over)",
    )
    p.add_argument(
        "--sync-mode", choices=["auto", "psum", "schedule"], default="auto",
        help="gradient-sync data plane: psum = masked XLA collective per "
        "leaf; schedule = bucketed strategy-tree allreduce (multi-tree "
        "strategies run merged rounds); auto picks by topology",
    )
    p.add_argument(
        "--adapt", choices=["off", "detect", "swap"], default="off",
        help="closed-loop online adaptation (docs/ADAPT.md; requires "
        "--dp-mode ddp): feed each step's walltime to the passive drift "
        "detector and run detect -> recalibrate -> re-rank every "
        "--adapt-every steps; 'swap' additionally adopts the re-ranked "
        "strategy through the epoch hot-swap.  ADAPCC_ADAPT overrides "
        "(malformed value -> loud error); ADAPCC_DRIFT_FACTOR / "
        "ADAPCC_DRIFT_WINDOW tune the detector",
    )
    p.add_argument(
        "--adapt-every", type=int, default=8,
        help="steps between adaptation passes (--adapt detect|swap)",
    )
    p.add_argument(
        "--supervisor", action="store_true",
        help="autonomous supervisor daemon (docs/SUPERVISOR.md; requires "
        "--dp-mode ddp): an out-of-band thread owns detect -> decide -> "
        "swap — heartbeat/fault-plan detection, fsync'd decision journal "
        "(topology/supervisor.journal), standby-cache failover, and the "
        "--adapt loop when armed — while the training loop only observes "
        "epoch bumps.  ADAPCC_SUPERVISOR=on|off overrides (malformed -> "
        "loud error)",
    )
    p.add_argument(
        "--supervisor-period", type=float, default=0.25,
        help="supervisor poll cadence in seconds (--supervisor)",
    )
    return p


def make_workload(name: str, batch: int, rng):
    """Returns (loss_fn, params, batch_fn)."""
    if name == "mlp":
        from adapcc_tpu.models import MLP

        model = MLP(features=(64, 64, 10))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(batch, 32)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(1).integers(0, 10, size=(batch,)))
        params = model.init(rng, x[:1])

        def loss_fn(p, b):
            bx, by = b
            logits = model.apply(p, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        return loss_fn, params, lambda: (x, y)

    if name in ("vgg", "resnet18", "resnet50"):
        if name == "vgg":
            from adapcc_tpu.models.vgg import VGG16

            model = VGG16(num_classes=10, classifier_width=512)
        else:
            # stateless GroupNorm variant: drops into the same loss_fn
            # contract as every other workload (SyncBN runs in main_elastic)
            from adapcc_tpu.models.resnet import ResNet18, ResNet50

            ctor = ResNet18 if name == "resnet18" else ResNet50
            model = ctor(num_classes=10, small_inputs=True, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(batch, 32, 32, 3)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(1).integers(0, 10, size=(batch,)))
        params = model.init(rng, x[:1])

        def loss_fn(p, b):
            bx, by = b
            logits = model.apply(p, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        return loss_fn, params, lambda: (x, y)

    if name == "vit":
        from adapcc_tpu.models.vit import ViT, ViTConfig

        cfg = ViTConfig(image_size=64, patch_size=8, num_classes=100, d_model=192, n_layer=6, n_head=3)
        model = ViT(cfg)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(batch, 64, 64, 3)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(1).integers(0, 100, size=(batch,)))
        params = model.init(rng, x[:1])

        def loss_fn(p, b):
            bx, by = b
            logits = model.apply(p, bx)
            return optax.softmax_cross_entropy_with_integer_labels(logits, by).mean()

        return loss_fn, params, lambda: (x, y)

    if name == "gpt2":
        from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss

        cfg = GPT2Config(vocab_size=8192, max_seq=256, n_layer=4, n_head=4, d_model=256)
        model = GPT2(cfg)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, size=(batch, cfg.max_seq))
        )
        params = model.init(rng, tokens[:1])

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

        return loss_fn, params, lambda: tokens

    raise ValueError(name)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    # the adaptation mode actually in force (ADAPCC_ADAPT wins over the
    # flag; malformed env/flag -> loud error before any engine side effects)
    from adapcc_tpu.adapt import adapt_mode

    adapt = adapt_mode(args.adapt)
    if args.adapt_every < 1:
        raise ValueError(f"--adapt-every must be >= 1, got {args.adapt_every}")
    if adapt != "off" and args.dp_mode != "ddp":
        raise ValueError(
            "--adapt/ADAPCC_ADAPT requires --dp-mode ddp: the closed loop "
            "re-ranks and hot-swaps the DDP gradient hook's strategy "
            "(zero1/fsdp sync via GSPMD and carry no strategy to swap)"
        )
    # the supervisor mode actually in force (ADAPCC_SUPERVISOR wins over
    # the flag; malformed env -> loud error before any engine side effects)
    from adapcc_tpu.supervisor import supervisor_enabled

    supervised = supervisor_enabled(args.supervisor)
    if args.supervisor_period <= 0:
        raise ValueError(
            f"--supervisor-period must be > 0, got {args.supervisor_period}"
        )
    if supervised and args.dp_mode != "ddp":
        raise ValueError(
            "--supervisor/ADAPCC_SUPERVISOR requires --dp-mode ddp: the "
            "daemon actuates the DDP gradient hook's strategy through the "
            "standby cache (zero1/fsdp carry no strategy to swap)"
        )
    if args.dp_mode != "ddp":
        # sharded-state modes sync via GSPMD/psum, not the adaptive hook —
        # the relay/straggler machinery rides the hook, so reject the combo
        # up front (before any server/engine side effects) instead of
        # silently ignoring the flags
        if args.coordinator or not args.is_bsp or args.profile_freq:
            raise ValueError(
                "--coordinator/--no-bsp/--profile_freq require --dp-mode ddp "
                "(relay and re-adaptation ride the DDP gradient hook)"
            )
        import os as _os

        from adapcc_tpu.elastic import FAULT_PLAN_ENV

        if _os.environ.get(FAULT_PLAN_ENV, "").strip():
            # fault injection rides the DDP hook's relay masks; silently
            # running a healthy world under a set plan would be the exact
            # "set-but-broken is quiet" failure the env contract forbids
            raise ValueError(
                f"{FAULT_PLAN_ENV} requires --dp-mode ddp (fault injection "
                "drives the DDP trainer's per-step relay masks; zero1/fsdp "
                "have no relay plane to inject into)"
            )
        from adapcc_tpu.sim.congestion import CONGESTION_PROFILE_ENV as _CONG

        if _os.environ.get(_CONG, "").strip():
            # congestion injection rides the DDP adaptation controller;
            # same set-but-quiet contract as the fault plan above
            raise ValueError(
                f"{_CONG} requires --dp-mode ddp (congestion injection "
                "feeds the adaptation controller's observation funnel, "
                "which rides the DDP gradient hook)"
            )
    if args.zero1_ring and args.dp_mode != "zero1":
        raise ValueError("--zero1-ring requires --dp-mode zero1")
    # one wire-codec knob across modes: --wire-dtype wins over the older
    # --grad-compress spelling when both are given
    wire_dtype = args.wire_dtype if args.wire_dtype is not None else args.grad_compress
    if args.error_feedback and args.dp_mode != "ddp":
        raise ValueError(
            "--error-feedback requires --dp-mode ddp (the residual bank "
            "rides the DDP gradient hook)"
        )
    if wire_dtype == "strategy" and args.dp_mode != "ddp":
        raise ValueError(
            "--wire-dtype strategy requires --dp-mode ddp (only the "
            "gradient hook carries a synthesized strategy to adopt)"
        )
    if args.tune and args.dp_mode == "fsdp":
        raise ValueError(
            "--tune requires --dp-mode ddp or zero1: fsdp syncs via GSPMD "
            "and exposes none of the tuner's knobs (chunk/codec)"
        )
    # the overlap schedule actually in force (ADAPCC_OVERLAP wins over the
    # flag; malformed env -> loud error before any engine side effects)
    from adapcc_tpu.ddp import resolve_overlap_mode

    overlap = resolve_overlap_mode(args.overlap)
    if args.dp_mode == "fsdp" and overlap != "off":
        raise ValueError(
            "--overlap requires --dp-mode ddp or zero1: fsdp's collectives "
            "are GSPMD-inserted and expose no overlap schedule"
        )
    if args.dp_mode == "zero1" and overlap == "microbatch":
        raise ValueError(
            "--overlap microbatch requires --dp-mode ddp: the pipeline "
            "rides the DDP trainer's accumulation scan (zero1 supports "
            "--overlap bucket — chunked reduce-scatter/all-gather)"
        )
    if args.accum < 1:
        raise ValueError(f"--accum must be >= 1, got {args.accum}")
    if args.accum > 1 and args.dp_mode != "ddp":
        raise ValueError(
            "--accum requires --dp-mode ddp (gradient accumulation rides "
            "the DDP trainer's compiled scan)"
        )
    # join the multi-host world if the launcher set the coordinator env
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()
    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)

    comm_args = CommArgs.from_namespace(args)
    if args.dp_mode == "ddp":
        # the adaptive bootstrap + collective engine back the gradient hook;
        # the GSPMD modes never touch them, so they skip the whole lifecycle
        AdapCC.init(comm_args, mesh=mesh)
        AdapCC.setup(ALLREDUCE)
        if args.coordinator:
            AdapCC.communicator.enable_coordinator(
                is_master=True, num_processes=1, port=0
            )

    loss_fn, params, batch_fn = make_workload(args.model, args.batch, jax.random.PRNGKey(0))
    tx = optax.adam(args.lr)

    if args.dp_mode == "fsdp":
        from adapcc_tpu.parallel import fsdp_shardings, fsdp_train_step
        from jax.sharding import PartitionSpec

        sh = fsdp_shardings(params, mesh, min_shard_elems=args.min_shard_elems)
        params = jax.device_put(params, sh)
        n_sharded = sum(
            s.spec != PartitionSpec() for s in jax.tree_util.tree_leaves(sh)
        )
        print(f"fsdp: {n_sharded}/{len(jax.tree_util.tree_leaves(sh))} leaves sharded")
        opt_state = tx.init(params)
        fsdp_step = fsdp_train_step(
            loss_fn, tx, mesh, min_shard_elems=args.min_shard_elems
        )

        def run_step(step):
            nonlocal params, opt_state
            params, opt_state, loss = fsdp_step(params, opt_state, batch_fn())
            return loss

    elif args.dp_mode == "zero1":
        from adapcc_tpu.parallel import Zero1Optimizer, zero1_train_step

        z_tuner = None
        if args.tune:
            from adapcc_tpu.tuner import CollectiveTuner

            z_tuner = CollectiveTuner.for_mesh(mesh, mode="choose")
        z_opt = Zero1Optimizer(
            tx, mesh, ring=args.zero1_ring,
            ring_chunk_bytes=args.ring_chunk_bytes or None,
            wire_dtype=wire_dtype,
            tuner=z_tuner,
            # env-resolved above; the Pallas ring keeps one chunking plane
            overlap="off" if args.zero1_ring else overlap,
        )
        master, z_state = z_opt.init(params)
        # redundant shard placement (docs/RECOVERY.md §1): with
        # ADAPCC_SHARD_REPLICAS > 0 every step's freshly-written optimizer
        # shard rows are captured to their ring-neighbor holders inside
        # the post-step window — the elastic_rejoin battery A/Bs this
        # against k=0 to price the piggyback on real chips
        from adapcc_tpu.elastic.redundancy import (
            ShardReplicaStore,
            shard_replicas,
        )

        z_replicas = shard_replicas(default=0)
        z_store = None
        if z_replicas:
            z_store = ShardReplicaStore(world, replicas=z_replicas)
            print(
                f"redundancy: zero1 shard replicas k={z_replicas} "
                f"(ring-neighbor placement over world={world})"
            )
        if z_opt.tuned_plan is not None:
            tp = z_opt.tuned_plan
            print(
                f"tuner: zero1 ring chunk_bytes={z_opt.ring_chunk_bytes} "
                f"(source={tp.source})"
            )
        z_step = zero1_train_step(loss_fn, z_opt, mesh)

        # step walltimes must land in the SAME cell the next run's
        # init()-time choose("zero1_ring", ...) ranks, or the feedback loop
        # never closes; tuning_key() is that cell (None off the ring path —
        # plain zero1 has no tuner knob, so nothing is recorded)
        z_cell = z_opt.tuning_key() if z_tuner is not None else None

        def run_step(step):
            nonlocal params, master, z_state
            if z_cell is not None and z_tuner.recording:
                import time as _time

                t0 = _time.perf_counter()
                params, master, z_state, losses = z_step(
                    params, master, z_state, batch_fn()
                )
                jax.block_until_ready(losses)
                z_tuner.observe_dispatch(
                    z_cell, ("zero1_step",), _time.perf_counter() - t0
                )
            else:
                params, master, z_state, losses = z_step(
                    params, master, z_state, batch_fn()
                )
            if z_store is not None:
                # the piggyback window: the shard rows this step's update
                # just wrote ride to their holders, stamped for the
                # repair path's freshness guard
                z_store.capture((master, z_state), step)
            return losses

    else:
        trainer = DDPTrainer(
            loss_fn,
            tx,
            mesh,
            AdapCC.communicator.strategy,
            communicator=AdapCC.communicator,
            use_xla_fastpath=comm_args.use_xla_fastpath,
            bsp=comm_args.is_bsp,
            sync_mode=args.sync_mode,
            grad_compress=wire_dtype,
            error_feedback=args.error_feedback,
            tune=args.tune,
            accum_steps=args.accum,
            overlap=overlap,
            # loop-owned state: see train_gpt2 donation note
            donate_state=True,
        )
        state = TrainState.create(params, tx)

        # deterministic fault injection (docs/ELASTIC.md): with
        # ADAPCC_FAULT_PLAN set, each step's relay mask is derived from the
        # plan's fault state — down/slow ranks stop contributing (and
        # recover on schedule) through the SAME compiled dynamic-mask step,
        # so the run exercises a real world shrink + recovery.  This is the
        # data plane the elastic_failover battery entry measures.
        from adapcc_tpu.elastic import load_fault_plan
        from adapcc_tpu.sim.congestion import (
            CONGESTION_PROFILE_ENV,
            load_congestion_profile,
        )

        fault_plan = load_fault_plan(world=world)
        if fault_plan is not None:
            print(f"fault injection: {fault_plan!r}")
        congestion_profile = load_congestion_profile(world=world)
        if congestion_profile is not None and adapt == "off":
            # the profile feeds the adaptation controller's triage; a set
            # profile with the loop disarmed would silently inject nothing
            # — the exact "set-but-broken is quiet" failure the env
            # contract forbids
            raise ValueError(
                f"{CONGESTION_PROFILE_ENV} requires --adapt detect|swap "
                "(congestion injection rides the adaptation controller's "
                "observation funnel; with the loop off nothing consumes it)"
            )

        # closed-loop online adaptation (docs/ADAPT.md): the controller
        # rides the communicator's own seams (engine, synthesizer, tuning
        # database, calibration artifact); step walltimes are its passive
        # measurement feed — zero probe traffic
        adapt_ctl = None
        grad_bytes = 0
        if adapt != "off":
            # prewarm the TRAINER's step program for a winning candidate
            # before adoption, so the swap is a cache hit there too (no
            # recompile on the failover step).  The closure reads the live
            # `state`, so the AOT trace sees the real shapes.  Banked
            # trainer modes (async relay / error feedback) cannot prewarm
            # — adoption falls back to the documented cold rebuild.
            prewarm = None
            if comm_args.is_bsp and not args.error_feedback:
                prewarm = lambda s: trainer.prewarm(s, state, batch_fn())  # noqa: E731
            adapt_ctl = AdapCC.communicator.adaptation_controller(
                trainer=trainer, mode=args.adapt, trainer_prewarm=prewarm,
            )
            grad_bytes = sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
            )
            print(f"online adaptation: mode={adapt} every={args.adapt_every}")
            # deterministic congestion injection (docs/FABRIC.md §4): with
            # ADAPCC_CONGESTION_PROFILE set, each step ticks the profile's
            # windows into the controller's PRICED observation feed (the
            # observation-funnel twin of the fault-plan injection above),
            # so the congestion-vs-degradation triage is exercisable on a
            # live run — re-route inside a window, restore after it
            if congestion_profile is not None:
                adapt_ctl.attach_congestion_profile(congestion_profile)
                print(f"congestion injection: {congestion_profile!r}")

        # autonomous supervisor (docs/SUPERVISOR.md): the daemon — not
        # this loop — folds the fault plan (and any heartbeat silence)
        # into the worldview, journals every decision, and actuates the
        # standby-cache swap + trainer adoption; the loop only consumes
        # the last actuated mask through the attached-trainer seam and
        # retries EpochMismatch as it always did
        supervisor = None
        current_step = [0]
        if supervised:
            import os as _os

            # a FRESH run must not replay the previous run's journal into
            # its healthy world; an elastic restart of the SAME run (the
            # replay case the journal exists for) is marked by the
            # launcher's ADAPCC_RESTART_GEN and keeps it
            journal_path = _os.path.join(
                comm_args.topology_dir, "supervisor.journal"
            )
            if (
                not _os.environ.get("ADAPCC_RESTART_GEN", "").strip()
                and _os.path.exists(journal_path)
            ):
                _os.remove(journal_path)
            supervisor = AdapCC.communicator.supervisor(
                journal_path=journal_path,
                trainer=trainer,
                fault_plan=fault_plan,
                step_source=(
                    (lambda: current_step[0])
                    if fault_plan is not None else None
                ),
                adapt=adapt_ctl,
                # polls, not steps: the daemon's clock is its own
                adapt_every=args.adapt_every if adapt_ctl is not None else 0,
            )
            trainer.attach_supervisor(supervisor)
            if comm_args.is_bsp and not args.error_feedback and args.accum == 1:
                # AOT-prewarm the step for the top standby plans so the
                # daemon's adoption is a cache hit on the trainer plane too
                for splan in supervisor.cache.ranked()[: supervisor.cache.top_k]:
                    trainer.prewarm(splan.strategy, state, batch_fn())
            supervisor.start(period_s=args.supervisor_period)
            print(
                f"supervisor: period={args.supervisor_period}s "
                f"journal={supervisor.journal.path}"
            )

        def run_step(step):
            nonlocal state
            current_step[0] = step
            if supervisor is not None and fault_plan is not None:
                # the injected feed is STEP-indexed, so its natural clock
                # is the step counter: one deterministic tick per step
                # (the decisions stay the daemon's; wall-clock heartbeat
                # detection keeps riding the background thread)
                supervisor.poll()
            # periodic re-adaptation (reference train_ddp.py:45-46)
            if args.profile_freq and step > 0 and step % args.profile_freq == 0:
                AdapCC.reconstruct_topology(comm_args, ALLREDUCE)
                trainer.rebuild(AdapCC.communicator.strategy)
            mask = None
            if fault_plan is not None and supervisor is None:
                mask = jnp.asarray(fault_plan.mask_at(step))
            t0 = time.perf_counter() if adapt_ctl is not None else 0.0
            state, loss = trainer.step(
                state, batch_fn(), step_idx=step, active_mask=mask
            )
            if adapt_ctl is not None:
                # the block serializes the loop by design: the sample is
                # the step's dispatch-to-completion walltime (the tuner's
                # record-mode contract)
                jax.block_until_ready(loss)
                adapt_ctl.observe_step(time.perf_counter() - t0, grad_bytes)
                # the congestion profile's step tick (no-op when no
                # profile is attached): window steps feed contended priced
                # samples, healthy steps feed reversal evidence
                adapt_ctl.tick(step)
                if supervisor is not None:
                    pass  # the daemon runs maybe_adapt on its own cadence
                elif step > 0 and step % args.adapt_every == 0:
                    rep = adapt_ctl.maybe_adapt()
                    if rep.swapped:
                        print(
                            f"adapt: step {step} swapped to "
                            f"{rep.winner_label} ({rep.winner_fingerprint}) "
                            f"stall={rep.stall_s:.6f}s "
                            f"trainer_hit={rep.trainer_adopt_hit}"
                        )
                    elif rep.outcome == "uninvertible":
                        # step walltimes alone carry no link algebra, so a
                        # pure-DDP loop can DETECT drift but not attribute
                        # it to links — say so instead of silently idling
                        print(
                            f"adapt: step {step} drift detected but "
                            "uninvertible (step-walltime evidence only; "
                            "link-attributable samples — tuner-recorded "
                            "engine dispatches — are needed to "
                            "re-calibrate and swap)"
                        )
                    elif rep.outcome not in ("no-drift", "off"):
                        print(f"adapt: step {step} {rep.outcome}")
            return loss

    t_last = time.perf_counter()
    for step in range(args.steps):
        loss = run_step(step)
        if step % 5 == 0 or step == args.steps - 1:
            now = time.perf_counter()
            print(
                f"step {step:4d}  loss {float(jnp.mean(loss)):.4f}  "
                f"({(now - t_last):.3f}s since last log)  world={world} "
                f"mode={args.dp_mode}"
            )
            t_last = now

    if args.dp_mode == "ddp":
        if supervisor is not None:
            supervisor.stop()
            wv = supervisor.worldview()
            print(
                f"supervisor: {supervisor.decisions} decisions, "
                f"wv_epoch={wv.epoch} alive={sorted(wv.alive)} "
                f"relays={sorted(wv.relays)} "
                f"journal={supervisor.journal.path}"
            )
        AdapCC.clear(ALLREDUCE)


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
