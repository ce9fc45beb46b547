"""Accuracy benchmark — the reference's image-classification accuracy study.

Reference behavior (models/image-classification/accuracy_benchmark.py):
epoch-based classifier training with AverageMeter/ProgressMeter progress
lines, top-1/top-5 accuracy, a validation pass per epoch, optional
gradient-noise-scale hooks (commented there at accuracy_benchmark.py:369-374
— first-class here via ``DDPTrainer(measure_gns=True)``), and accuracy
traces dumped to .txt for the committed plots.

The dataset is synthetic-but-learnable (Gaussian class blobs): accuracy
starts at chance and climbs, so the benchmark validates end-to-end learning
through the adaptive DDP stack, not just step mechanics.

Run (virtual pod):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m adapcc_tpu.workloads.accuracy_benchmark --epochs 3
"""

from __future__ import annotations

import argparse
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from adapcc_tpu.utils import AverageMeter, ProgressMeter


def topk_accuracy(logits, labels, ks: Sequence[int] = (1, 5)):
    """Top-k accuracies (%) for ``logits [B, C]`` vs ``labels [B]`` —
    the reference's ``accuracy(output, target, topk=(1, 5))``."""
    import jax.numpy as jnp

    ks = tuple(min(k, logits.shape[-1]) for k in ks)
    ranked = jnp.argsort(logits, axis=-1)[:, ::-1]
    out = []
    for k in ks:
        hit = (ranked[:, :k] == labels[:, None]).any(axis=-1)
        out.append(100.0 * jnp.mean(hit.astype(jnp.float32)))
    return out


def make_blob_dataset(
    n: int, num_classes: int, image_size: int = 8, channels: int = 3,
    noise: float = 1.0, seed: int = 0, means_seed: int = 1234,
) -> Tuple[np.ndarray, np.ndarray]:
    """Learnable synthetic classification data: one Gaussian blob per class
    in pixel space, noise-corrupted.  Linear separability makes accuracy an
    honest end-to-end training signal without any dataset download.

    ``means_seed`` fixes the class centers independently of ``seed`` (the
    sample draw), so train and validation splits share one distribution.
    """
    means = np.random.default_rng(means_seed).normal(
        size=(num_classes, image_size, image_size, channels)
    )
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=(n,))
    images = means[labels] + noise * rng.normal(size=(n, image_size, image_size, channels))
    return images.astype(np.float32), labels.astype(np.int32)


def batches(
    images: np.ndarray, labels: np.ndarray, batch: int, seed: int
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Shuffled full batches (drops the ragged tail, like the reference's
    DataLoader with drop_last)."""
    idx = np.random.default_rng(seed).permutation(len(images))
    for i in range(0, len(idx) - batch + 1, batch):
        sel = idx[i : i + batch]
        yield images[sel], labels[sel]


def validate(apply_fn, params, images, labels, batch: int = 64) -> Tuple[float, float]:
    """Full-dataset top-1/top-5 (%), batched to bound memory.
    ``apply_fn(params, images) -> logits``; pass an already-jitted function
    (as :func:`run` does) — wrapping in a fresh ``jax.jit`` here would start
    every call with an empty compilation cache."""
    import jax.numpy as jnp

    hits1, hits5, seen = 0.0, 0.0, 0
    for i in range(0, len(images), batch):
        x = jnp.asarray(images[i : i + batch])
        y = jnp.asarray(labels[i : i + batch])
        a1, a5 = topk_accuracy(apply_fn(params, x), y)
        hits1 += float(a1) * len(x)
        hits5 += float(a5) * len(x)
        seen += len(x)
    return hits1 / max(seen, 1), hits5 / max(seen, 1)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--train-size", type=int, default=512)
    p.add_argument("--val-size", type=int, default=128)
    # VGG11's five 2x pooling stages need ≥32px inputs
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--noise", type=float, default=1.0,
                   help="blob corruption; lower = easier problem")
    p.add_argument("--model", choices=["vgg", "resnet18", "mlp"], default="vgg",
                   help="resnet18 = the reference study's default --arch "
                        "(stateless GroupNorm variant here; the SyncBN path "
                        "runs in main_elastic); vgg = fast conv benchmark; "
                        "mlp = fast smoke")
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--measure-gns", action="store_true")
    p.add_argument("--accuracy-trace", type=str, default=None,
                   help="append 'epoch top1 top5' lines (reference .txt traces)")
    p.add_argument("--print-freq", type=int, default=5)
    return p


def run(args) -> Tuple[float, float]:
    """Train + validate; returns the final (top1, top5)."""
    from adapcc_tpu.launch import maybe_initialize_distributed

    # re-pins jax_platforms from the env (site customizations override the
    # env var at startup) and joins a multi-host world when launched as one
    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.models.vgg import VGG11
    from adapcc_tpu.strategy.ir import Strategy

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)

    train_x, train_y = make_blob_dataset(
        args.train_size, args.num_classes, args.image_size, noise=args.noise, seed=0
    )
    val_x, val_y = make_blob_dataset(
        args.val_size, args.num_classes, args.image_size, noise=args.noise, seed=1
    )

    if args.model == "vgg":
        net = VGG11(num_classes=args.num_classes, classifier_width=64, dtype=jnp.float32)
        apply_fn = net.apply
        params = net.init(jax.random.PRNGKey(0), jnp.asarray(train_x[:1]))
    elif args.model == "resnet18":
        from adapcc_tpu.models.resnet import ResNet18

        net = ResNet18(
            num_classes=args.num_classes, small_inputs=True, dtype=jnp.float32
        )
        apply_fn = net.apply
        params = net.init(jax.random.PRNGKey(0), jnp.asarray(train_x[:1]))
    else:
        from adapcc_tpu.models.mlp import MLP

        net = MLP(features=(128, 64, args.num_classes))

        def apply_fn(p, x):
            return net.apply(p, x.reshape(x.shape[0], -1))

        params = net.init(
            jax.random.PRNGKey(0), jnp.asarray(train_x[:1]).reshape(1, -1)
        )

    def loss_fn(p, batch):
        x, y = batch
        logits = apply_fn(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    tx = optax.adam(args.lr)
    trainer = DDPTrainer(
        loss_fn, tx, mesh, Strategy.ring(world),
        measure_gns=args.measure_gns and world > 1,
        # loop-owned state: see train_gpt2 donation note
        donate_state=True,
    )
    state = TrainState.create(params, tx)
    eval_forward = jax.jit(apply_fn)  # one cache for all validation epochs

    top1 = top5 = 0.0
    for epoch in range(args.epochs):
        losses = AverageMeter("loss", ":.4f")
        steps = max(1, args.train_size // args.batch)
        progress = ProgressMeter(steps, [losses], prefix=f"epoch {epoch} ")
        for i, (x, y) in enumerate(batches(train_x, train_y, args.batch, seed=epoch)):
            state, loss = trainer.step(state, (jnp.asarray(x), jnp.asarray(y)))
            losses.update(float(jnp.mean(loss)), len(x))
            if i % args.print_freq == 0:
                progress.display(i)
        top1, top5 = validate(eval_forward, state.params, val_x, val_y)
        gns = trainer.gns.gns if trainer.gns is not None else None
        gns_txt = f"  gns {gns:.1f}" if gns is not None else ""
        print(f"epoch {epoch:3d}  val top1 {top1:.2f}%  top5 {top5:.2f}%{gns_txt}")
        if args.accuracy_trace:
            with open(args.accuracy_trace, "a") as f:
                f.write(f"{epoch} {top1:.4f} {top5:.4f}\n")
    return top1, top5


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
