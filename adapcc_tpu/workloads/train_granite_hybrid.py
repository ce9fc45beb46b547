"""Train Granite 4.0-H's block (``adapcc_tpu/models/granite_hybrid.py``) on the
synthetic Markov corpus, through ``DDPTrainer.step`` as ``train_kimi_linear``
trains Kimi-Linear's: Mamba-2 state-space layers nine to one with
grouped-query attention that carries no positions, a dense gated MLP in every
layer, scaled residuals, embedding and logits over a tied head.

The default sizes are a toy (seconds on the CPU pod, the kernels in the
interpreter); the published widths are one command line away on a chip that
holds them::

    python -m adapcc_tpu.workloads.train_granite_hybrid --epochs 2
    python -m adapcc_tpu.workloads.train_granite_hybrid --hidden 2048 --dense-width 8192 --heads 32 \\
        --kv-heads 8 --ssm-heads 64 --ssm-head-dim 64 --ssm-state 128 --layers 10 --seq 8192 --batch 1 \\
        --vocab 12544 --dtype bfloat16 --loss chunked --remat full

The step donates its state and hands out, beside the loss, the smallest decay
a chunk of a scan laid on its state (``TrainState.model_state``).
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence, Tuple


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--dense-width", type=int, default=128, help="shared_intermediate_size")
    p.add_argument("--layers", type=int, default=6, help="the first of the published 40: layer 5 (from 0) is attention")
    p.add_argument("--heads", type=int, default=4, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=2, help="num_key_value_heads")
    p.add_argument("--ssm-heads", type=int, default=8, help="mamba_n_heads; times --ssm-head-dim it is twice --hidden")
    p.add_argument("--ssm-head-dim", type=int, default=16, help="mamba_d_head")
    p.add_argument("--ssm-state", type=int, default=16, help="mamba_d_state")
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


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with its stateful
    loss, which hands the scans' decay floor out beside the loss."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.granite_hybrid import GraniteHybrid, stateful_loss
    from adapcc_tpu.strategy.ir import Strategy

    model = GraniteHybrid(cfg)
    trainer = DDPTrainer(
        stateful_loss(model, loss), tx, mesh, Strategy.ring(int(mesh.devices.size)),
        stateful_loss=True, donate_state=donate_state,
    )
    return trainer, model


def run(args, report: Optional[dict] = None) -> Tuple[float, float]:
    """Train; returns (first epoch's mean loss, last epoch's)."""
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.data import device_batches
    from adapcc_tpu.models.granite_hybrid import GraniteHybridConfig, initial_model_state, record_step
    from adapcc_tpu.utils.observability import default_registry
    from adapcc_tpu.workloads.train_gpt2 import markov_corpus, pack_sequences

    cfg = GraniteHybridConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, shared_intermediate_size=args.dense_width,
        num_hidden_layers=args.layers, num_attention_heads=args.heads, num_key_value_heads=args.kv_heads,
        attention_multiplier=1.0 / (args.hidden // args.heads), mamba_n_heads=args.ssm_heads,
        mamba_d_head=args.ssm_head_dim, mamba_d_state=args.ssm_state, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)
    if args.batch % world:
        raise ValueError(f"--batch {args.batch} must divide by world {world}")
    rows = pack_sequences(markov_corpus(args.corpus_tokens, args.vocab, seed=0), args.seq)
    if len(rows) < args.batch:
        raise ValueError(f"corpus too small: {len(rows)} rows of {args.seq} for a batch of {args.batch}")

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(args.lr, weight_decay=0.01))
    trainer, model = build_trainer(cfg, tx, mesh, args.loss)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(rows[:1]))
    state = trainer.init_state(params, initial_model_state())
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"granite_hybrid: {n_params / 1e6:.2f} M parameters, layers {list(cfg.kinds)}, world {world}")

    metrics = default_registry()
    means = []
    for epoch in range(args.epochs):
        losses = []
        for batch in device_batches(rows, args.batch, mesh=mesh, seed=epoch):
            state, loss = trainer.step(state, batch)
            losses.append(jnp.mean(loss))
            with metrics.span("ssd.read_floor") as live:
                if live:   # per-step values only under a profile (docs/OBSERVABILITY.md)
                    record_step(jax.device_get(state.model_state))
        means.append(float(np.mean(jax.device_get(losses))))
        floor = float(jax.device_get(state.model_state["ssd_decay_floor"]))
        print(f"epoch {epoch:3d}  lm_loss {means[-1]:.4f}  smallest chunk decay {floor:.3e}")
    if report is not None:
        report.update(trainer=trainer, state=state, losses=means)
    return means[0], means[-1]


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
