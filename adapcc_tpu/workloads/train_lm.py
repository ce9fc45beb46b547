"""The one road of the decoder language models' entry points
(``train_trinity``, ``train_kimi_linear``, ``train_joyai_flash``,
``train_granite_hybrid``, ``train_phi4_flash``) to ``DDPTrainer.step``: the
trainer, the loop on the synthetic Markov corpus, the job's flags and the
``__main__`` block, each once.  An entry point adds its ``config.json`` keys
to the parser, names its model and its ``stateful_loss`` in ``build_trainer``,
builds its configuration and hands :func:`train` a :class:`Job`.

The default sizes are a toy (seconds on the CPU pod, the kernels in the
interpreter); the published widths are one command line away on a chip that
holds them.  Every step donates its state (the old parameters and moments are
updated in place: 16 bytes a parameter instead of 28) and returns the model's
state beside the loss (``TrainState.model_state``).
"""

from __future__ import annotations

import argparse
from typing import Any, Callable, NamedTuple, Optional, Tuple

import numpy as np

from adapcc_tpu.workloads.train_gpt2 import markov_corpus, pack_sequences


class Job(NamedTuple):
    """What differs between the models' loops: the banner's first word; the
    entry's ``build_trainer(cfg, tx, mesh, loss)``; the ``model_state`` the
    first state carries; what the banner says of the model; under which span
    and by what a step's ``model_state`` is sampled under a live profile (None:
    nothing is read between steps); what an epoch's line says after its loss."""

    name: str
    build_trainer: Callable[..., tuple]
    first_state: Callable[[Any], Any] = lambda cfg: ()
    banner: Callable[[Any], str] = lambda cfg: f"layers {list(cfg.kinds)}"
    span: Optional[str] = None
    record: Optional[Callable[[Any], None]] = None
    epoch: Callable[[Any], str] = lambda model_state: ""


def expert_job(name: str, build_trainer, first_state, record=None) -> Job:
    """The :class:`Job` of a model whose step hands out ``moe_sizes [expert
    layers, held]``: the banner names the experts held, the epoch's line the
    assignments they were given and the fullest expert's over the mean, and
    ``record`` (the routing counts by default) samples a step under the span
    ``moe.read_routing``."""
    from adapcc_tpu.models.moe import record_routing

    def banner(cfg):
        return (
            f"layers {list(cfg.kinds)}, "
            f"experts {cfg.expert_offset}..{cfg.expert_offset + cfg.held} of {cfg.num_experts} held"
        )

    def epoch(model_state):
        sizes = np.asarray(model_state["moe_sizes"])
        load = sizes.max(axis=1) / np.maximum(sizes.mean(axis=1), 1e-9) if sizes.size else np.zeros(0)
        return f"  assignments here {sizes.sum(axis=1).tolist()}  fullest/mean {np.round(load, 2).tolist()}"

    record = record or (lambda model_state: record_routing(model_state["moe_sizes"]))
    return Job(name, build_trainer, first_state, banner, "moe.read_routing", record, epoch)


def job_parser(doc: str, dense_width: str = "intermediate_size") -> argparse.ArgumentParser:
    """The flags every entry point takes, at the toy defaults; the entry adds
    its own ``config.json`` keys.  ``dense_width``: the key ``--dense-width`` is."""
    p = argparse.ArgumentParser(description=doc, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--dense-width", type=int, default=128, help=dense_width)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--batch", type=int, default=8, help="global rows per step")
    p.add_argument("--corpus-tokens", type=int, default=16384)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--loss", choices=("dense", "chunked"), default="dense")
    p.add_argument("--remat", choices=("none", "dots", "full"), default="none")
    p.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32")
    return p


def build_trainer(model, loss_fn, tx, mesh, donate_state: bool = True):
    """``(trainer, model)``: ``loss_fn`` (a model's ``stateful_loss(model,
    loss)``) under ``DDPTrainer`` on a ring of the mesh's size."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.strategy.ir import Strategy

    trainer = DDPTrainer(
        loss_fn, tx, mesh, Strategy.ring(int(mesh.devices.size)),
        stateful_loss=True, donate_state=donate_state,
    )
    return trainer, model


def train(args, cfg, job: Job, report: Optional[dict] = None) -> Tuple[float, float]:
    """The loop of every entry point: ``args`` carries :func:`job_parser`'s
    flags, ``cfg`` is the model's configuration, ``job`` what is the model's
    own; ``report`` receives the trainer, the last state and the epochs' mean
    losses.  Returns (first epoch's mean loss, last epoch's)."""
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.data import device_batches
    from adapcc_tpu.utils.observability import default_registry

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)
    if args.batch % world:
        raise ValueError(f"--batch {args.batch} must divide by world {world}")
    rows = pack_sequences(markov_corpus(args.corpus_tokens, args.vocab, seed=0), args.seq)
    if len(rows) < args.batch:
        raise ValueError(f"corpus too small: {len(rows)} rows of {args.seq} for a batch of {args.batch}")

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(args.lr, weight_decay=0.01))
    trainer, model = job.build_trainer(cfg, tx, mesh, args.loss)
    # one program: run eagerly, a kernel in the interpreter is dispatched an operation at a time
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.asarray(rows[:1]))
    state = trainer.init_state(params, job.first_state(cfg))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"{job.name}: {n_params / 1e6:.2f} M parameters, {job.banner(cfg)}, world {world}")

    metrics = default_registry()
    means = []
    for epoch in range(args.epochs):
        losses = []
        for batch in device_batches(rows, args.batch, mesh=mesh, seed=epoch):
            state, loss = trainer.step(state, batch)
            losses.append(jnp.mean(loss))
            if job.record is not None:
                with metrics.span(job.span) as live:
                    if live:   # per-step values only under a profile (docs/OBSERVABILITY.md)
                        job.record(jax.device_get(state.model_state))
        means.append(float(np.mean(jax.device_get(losses))))
        print(f"epoch {epoch:3d}  lm_loss {means[-1]:.4f}{job.epoch(jax.device_get(state.model_state))}")
    if report is not None:
        report.update(trainer=trainer, state=state, losses=means)
    return means[0], means[-1]


def main(build_parser, run) -> None:
    """An entry point's ``__main__`` block: the compile cache on, the command
    line parsed by ``build_parser()`` and trained by ``run(args)``."""
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(build_parser().parse_args())
