"""Train Phi-4-mini-flash-reasoning's SambaY decoder
(``adapcc_tpu/models/phi4_flash.py``) on the synthetic Markov corpus, through
``DDPTrainer.step`` as ``train_granite_hybrid`` trains Granite 4.0-H's:
Mamba-1 selective scans alternating with differential attention (a window in
the first half), and in the second half gated memory units that read the
middle layer's scan and cross-attention on one full layer's keys and values.

The default sizes are a toy (seconds on the CPU pod, the kernels in the
interpreter); the published widths are one command line away on a chip that
holds them::

    python -m adapcc_tpu.workloads.train_phi4_flash --epochs 2
    python -m adapcc_tpu.workloads.train_phi4_flash --hidden 2560 --dense-width 10240 --heads 40 \\
        --kv-heads 20 --window 512 --published-layers 32 --layers-held 0,1,16,17,18,19 --seq 8192 --batch 1 \\
        --vocab 25008 --dtype bfloat16 --loss chunked --remat dots

The step donates its state.
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence, Tuple


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--dense-width", type=int, default=128, help="intermediate_size")
    p.add_argument("--published-layers", type=int, default=8, help="num_hidden_layers: where a layer's kind changes")
    p.add_argument("--layers-held", default="0,1,4,5,6,7",
                   help="published indices of the layers run; layer L/2 makes the memory, L/2 + 1 the shared K/V")
    p.add_argument("--heads", type=int, default=4, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=2, help="num_key_value_heads")
    p.add_argument("--window", type=int, default=16, help="sliding_window")
    p.add_argument("--ssm-state", type=int, default=8, help="the scan's states a channel")
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
    """``(trainer, model)``: the model under ``DDPTrainer`` with its loss."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.phi4_flash import Phi4Flash, stateful_loss
    from adapcc_tpu.strategy.ir import Strategy

    model = Phi4Flash(cfg)
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
    from adapcc_tpu.models.phi4_flash import Phi4FlashConfig
    from adapcc_tpu.workloads.train_gpt2 import markov_corpus, pack_sequences

    cfg = Phi4FlashConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        num_hidden_layers=args.published_layers, layers_held=tuple(int(i) for i in args.layers_held.split(",")),
        num_attention_heads=args.heads, num_key_value_heads=args.kv_heads, sliding_window=args.window,
        mamba_d_state=args.ssm_state, dtype=jnp.dtype(args.dtype), remat=args.remat,
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
    state = trainer.init_state(params)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"phi4_flash: {n_params / 1e6:.2f} M parameters, layers {list(zip(cfg.held, cfg.kinds))}, world {world}")

    means = []
    for epoch in range(args.epochs):
        losses = []
        for batch in device_batches(rows, args.batch, mesh=mesh, seed=epoch):
            state, loss = trainer.step(state, batch)
            losses.append(jnp.mean(loss))
        means.append(float(np.mean(jax.device_get(losses))))
        print(f"epoch {epoch:3d}  lm_loss {means[-1]:.4f}")
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
