"""Elastic image-classification training — the reference main_elastic.py flow.

Reference behavior (models/image-classification/main_elastic.py): torchrun
elastic workers restore the newest checkpoint at rendezvous, train epochs
with DDP, atomically checkpoint each epoch, and survive ``--max_restarts``
crashes.  Here the worker trains a VGG classifier under the AdapCC DDP
trainer, checkpoints through :mod:`adapcc_tpu.checkpoint`, and the
``--supervise`` mode wraps the worker in the elastic restart loop.

Run (virtual pod):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m adapcc_tpu.workloads.main_elastic --epochs 3 --steps-per-epoch 5
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import optax

from adapcc_tpu.checkpoint import (
    AsyncCheckpointManager,
    TrainCheckpointState,
    async_checkpointing_enabled,
    load_checkpoint,
    restore_newest_across_processes,
    run_elastic,
    save_checkpoint,
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--steps-per-epoch", type=int, default=5)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--checkpoint-file", type=str, default="/tmp/adapcc_elastic/checkpoint.ckpt")
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--model", choices=("resnet18", "resnet50", "vgg11", "mlp"),
                   default="vgg11",
                   help="resnet18 is the reference's default --arch "
                        "(main_elastic.py:75); vgg11 compiles much faster on "
                        "the virtual pod; mlp compiles in seconds for "
                        "restart-path tests")
    p.add_argument("--norm", choices=("group", "batch"), default="batch",
                   help="resnet norm layer: batch = SyncBN running stats "
                        "carried in the checkpoint (reference torchvision "
                        "behavior, cross-replica synced); group = stateless")
    p.add_argument("--crash-at-epoch", type=int, default=None,
                   help="fault injection: die after checkpointing this epoch")
    p.add_argument("--supervise", action="store_true",
                   help="run as the elastic supervisor wrapping a worker")
    p.add_argument("--max-restarts", type=int, default=3)
    return p


def worker(args) -> int:
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.strategy.ir import Strategy

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)

    stateful = False
    if args.model in ("resnet18", "resnet50"):
        from adapcc_tpu.models.resnet import ResNet18, ResNet50

        from adapcc_tpu.comm.mesh import RANKS_AXIS

        ctor = ResNet18 if args.model == "resnet18" else ResNet50
        # small_inputs: the 32x32 synthetic data below is CIFAR-shaped
        model = ctor(
            num_classes=10, small_inputs=True, dtype=jnp.float32,
            norm=args.norm,
            axis_name=RANKS_AXIS if args.norm == "batch" else None,
        )
        stateful = args.norm == "batch"
    elif args.model == "vgg11":
        from adapcc_tpu.models.vgg import VGG11

        model = VGG11(num_classes=10, classifier_width=128, dtype=jnp.float32)
    else:
        from adapcc_tpu.models.mlp import MLP

        class _Flat(MLP):
            def __call__(self, x):
                return super().__call__(x.reshape(x.shape[0], -1))

        model = _Flat(features=(16, 10))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(args.batch, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(args.batch,)))

    if stateful:
        # SyncBN: running statistics ride in TrainState.model_state and the
        # checkpoint's extra dict (the reference's State carries the whole
        # torchvision module incl. BN buffers)
        variables = model.init(jax.random.PRNGKey(0), images[:1], train=True)
        params, model_state = variables["params"], variables["batch_stats"]

        def loss_fn(p, ms, batch):
            x, y = batch
            logits, upd = model.apply(
                {"params": p, "batch_stats": ms}, x, train=True,
                mutable=["batch_stats"],
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return ce.mean(), upd["batch_stats"]
    else:
        params = model.init(jax.random.PRNGKey(0), images[:1])
        model_state = ()

        def loss_fn(p, batch):
            x, y = batch
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    tx = optax.sgd(args.lr, momentum=0.9)
    trainer = DDPTrainer(
        loss_fn, tx, mesh, Strategy.ring(world), stateful_loss=stateful,
        # loop-owned state: see train_gpt2 donation note
        donate_state=True,
    )
    train_state = trainer.init_state(params, model_state=model_state)

    # rendezvous restore: newest checkpoint wins across the (new) world
    ckpt = TrainCheckpointState(
        params=train_state.params,
        opt_state=train_state.opt_state,
        extra={"model_state": model_state} if stateful else {},
    )
    # async crash-consistent checkpointing (ADAPCC_ASYNC_CKPT,
    # docs/RECOVERY.md §2): epoch saves run on the manager's background
    # pipeline (snapshot → serialize → checksum → atomic publish) and the
    # local restore reads the newest VERIFIED step — a mid-save crash
    # leaves only ignorable .tmp debris, never a torn live checkpoint
    amgr = None
    if async_checkpointing_enabled():
        steps_dir = args.checkpoint_file + ".steps"
        if jax.process_count() > 1:
            # every process owns its own step directory: two publishers
            # racing one shared step-<n>/ rename is exactly the
            # cross-process collision the manager's loud
            # already-published guard rejects (the legacy single-file
            # path tolerates the race only because last-rename-wins)
            steps_dir += f".p{jax.process_index()}"
        amgr = AsyncCheckpointManager(steps_dir)
    try:
        restored_step = None
        if amgr is not None:
            restored_step = amgr.latest_good_step()
            if restored_step is not None:
                amgr.restore(ckpt, restored_step)
        if restored_step is not None:
            # the legacy single-file checkpoint may still be FRESHER
            # (async was off in an earlier run); adopt it only then —
            # loading it unconditionally would rewind the verified step
            # restore under a stale leftover file
            legacy = TrainCheckpointState(
                params=ckpt.params,
                opt_state=ckpt.opt_state,
                extra=dict(ckpt.extra),
            )
            try:
                fresher = (
                    load_checkpoint(legacy, args.checkpoint_file)
                    and legacy.epoch > ckpt.epoch
                )
            except (KeyError, ValueError, TypeError):
                # an unreadable/incompatible stale file simply LOSES the
                # freshness comparison — it must not abort a worker that
                # already holds a good verified restore
                fresher = False
            if fresher:
                ckpt = legacy
            ckpt = restore_newest_across_processes(
                ckpt, args.checkpoint_file, load_local=False
            )
        else:
            ckpt = restore_newest_across_processes(ckpt, args.checkpoint_file)
    except (KeyError, ValueError, TypeError) as e:
        # flax from_bytes raises a raw dict-key/shape mismatch when the file
        # was written under a different --norm mode (e.g. a pre-SyncBN ckpt
        # without extra["model_state"]); surface the actual cause instead
        print(
            f"=> checkpoint {args.checkpoint_file!r} is incompatible with "
            f"--norm {args.norm!r} (was it written under a different norm "
            f"mode?): {e}",
            file=sys.stderr,
        )
        return 2
    start_epoch = ckpt.epoch + 1
    if start_epoch > 0:
        print(f"=> resuming from epoch {start_epoch}")
        train_state = TrainState(
            params=ckpt.params, opt_state=ckpt.opt_state, step=ckpt.step,
            model_state=ckpt.extra.get("model_state", ()) if stateful else (),
        )

    for epoch in range(start_epoch, args.epochs):
        for _ in range(args.steps_per_epoch):
            train_state, loss = trainer.step(train_state, (images, labels))
        print(f"epoch {epoch:3d}  loss {float(jnp.mean(loss)):.4f}  world={world}")

        ckpt.params = train_state.params
        ckpt.opt_state = train_state.opt_state
        ckpt.epoch = epoch
        ckpt.step = int(train_state.step)
        if stateful:
            ckpt.extra["model_state"] = train_state.model_state
        if amgr is not None:
            amgr.save_async(epoch, ckpt)
        else:
            save_checkpoint(ckpt, args.checkpoint_file)

        # fault injection fires only in the first generation, so the
        # supervisor's restart actually makes progress past the crash point
        gen = int(os.environ.get("ADAPCC_RESTART_GEN", "0"))
        if args.crash_at_epoch is not None and epoch == args.crash_at_epoch and gen == 0:
            if amgr is not None:
                # the INJECTED crash is deterministic by contract — the
                # genuinely-mid-save kill is the chaos drill's job
                amgr.wait()
            print(f"=> injected fault at epoch {epoch}", flush=True)
            return 17  # nonzero: the supervisor restarts us
    if amgr is not None:
        amgr.wait()
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.supervise:
        worker_argv = [
            sys.executable, "-m", "adapcc_tpu.workloads.main_elastic",
            "--epochs", str(args.epochs),
            "--steps-per-epoch", str(args.steps_per_epoch),
            "--batch", str(args.batch),
            "--lr", str(args.lr),
            "--checkpoint-file", args.checkpoint_file,
            "--model", args.model,
            "--norm", args.norm,
        ]
        if args.world:
            worker_argv += ["--world", str(args.world)]
        if args.crash_at_epoch is not None:
            worker_argv += ["--crash-at-epoch", str(args.crash_at_epoch)]
        return run_elastic(worker_argv, max_restarts=args.max_restarts)
    return worker(args)


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
