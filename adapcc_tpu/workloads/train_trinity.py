"""Train Trinity-Mini's block (``adapcc_tpu/models/trinity.py``) on the
synthetic Markov corpus, through ``DDPTrainer.step`` as ``train_gpt2`` trains
GPT-2: windowed and full grouped-query attention with a gated output,
sigmoid-routed sparse experts beside a shared expert, a chip's share of the
experts where ``--experts-held`` says so.

The default sizes are a toy (seconds on the CPU pod); every ``config.json``
key of docs/TRINITY.md has a flag, so the published widths are one command
line away on a chip that holds them::

    python -m adapcc_tpu.workloads.train_trinity --epochs 2
    python -m adapcc_tpu.workloads.train_trinity --hidden 2048 --heads 32 --kv-heads 4 \\
        --head-dim 128 --layers 5 --dense-layers 1 --experts 128 --experts-held 16 \\
        --top-k 8 --seq 8192 --batch 1 --vocab 25024 --loss chunked --dtype bfloat16

The step donates its state (the old parameters and moments are updated in
place: 16 bytes a parameter instead of 28) and hands out, beside the loss,
the assignments each held expert was given (``TrainState.model_state``).
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence, Tuple

import numpy as np

from adapcc_tpu.workloads.train_gpt2 import markov_corpus, pack_sequences


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--dense-width", type=int, default=128, help="intermediate_size")
    p.add_argument("--expert-width", type=int, default=32, help="moe_intermediate_size")
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--dense-layers", type=int, default=1)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("--head-dim", type=int, default=16)
    p.add_argument("--window", type=int, default=32, help="sliding_window")
    p.add_argument("--global-every", type=int, default=4, help="global_attn_every_n_layers")
    p.add_argument("--experts", type=int, default=8, help="num_experts (the router's width)")
    p.add_argument("--top-k", type=int, default=2, help="num_experts_per_tok")
    p.add_argument("--route-scale", type=float, default=2.826)
    p.add_argument("--experts-held", type=int, default=None, help="routed experts on this chip (default: all)")
    p.add_argument("--expert-offset", type=int, default=0)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--batch", type=int, default=8, help="global rows per step")
    p.add_argument("--corpus-tokens", type=int, default=16384)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--attn", choices=("flash", "xla"), default="xla")
    p.add_argument("--loss", choices=("dense", "chunked"), default="dense")
    p.add_argument("--remat", choices=("none", "dots", "full"), default="none")
    p.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32")
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss that hands the routing counts out beside the loss."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.trinity import Trinity, stateful_loss
    from adapcc_tpu.strategy.ir import Strategy

    model = Trinity(cfg)
    trainer = DDPTrainer(
        stateful_loss(model, loss), tx, mesh, Strategy.ring(int(mesh.devices.size)),
        stateful_loss=True, donate_state=donate_state,
    )
    return trainer, model


def train(args, cfg, build, banner: str, report: Optional[dict] = None, first_model_state=None,
          record=None) -> Tuple[float, float]:
    """The loop of an expert model's entry point (this one's,
    ``train_kimi_linear``'s and ``train_joyai_flash``'s): ``build(cfg, tx,
    mesh, loss)`` gives ``(trainer, model)``; ``args`` carries ``vocab``,
    ``seq``, ``batch``, ``corpus_tokens``, ``epochs``, ``lr``, ``world``,
    ``loss``.  ``first_model_state(cfg)`` is what the first state carries
    beside the parameters (Trinity's by default) and ``record(model_state)``
    what is sampled, under a profile, from what a step returned (the routing
    counts by default).  Returns (first epoch's mean loss, last epoch's)."""
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.data import device_batches
    from adapcc_tpu.models.moe import record_routing
    from adapcc_tpu.models.trinity import initial_model_state
    from adapcc_tpu.utils.observability import default_registry

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)
    if args.batch % world:
        raise ValueError(f"--batch {args.batch} must divide by world {world}")
    rows = pack_sequences(markov_corpus(args.corpus_tokens, args.vocab, seed=0), args.seq)
    if len(rows) < args.batch:
        raise ValueError(f"corpus too small: {len(rows)} rows of {args.seq} for a batch of {args.batch}")

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(args.lr, weight_decay=0.01))
    trainer, model = build(cfg, tx, mesh, args.loss)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(rows[:1]))
    state = trainer.init_state(params, (first_model_state or initial_model_state)(cfg))
    record = record or (lambda model_state: record_routing(model_state["moe_sizes"]))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(
        f"{banner}: {n_params / 1e6:.2f} M parameters, layers {list(cfg.kinds)}, "
        f"experts {cfg.expert_offset}..{cfg.expert_offset + cfg.held} of {cfg.num_experts} held, world {world}"
    )

    metrics = default_registry()
    means = []
    for epoch in range(args.epochs):
        losses = []
        for batch in device_batches(rows, args.batch, mesh=mesh, seed=epoch):
            state, loss = trainer.step(state, batch)
            losses.append(jnp.mean(loss))
            with metrics.span("moe.read_routing") as live:
                if live:   # per-step values only under a profile (docs/OBSERVABILITY.md)
                    record(jax.device_get(state.model_state))
        sizes = np.asarray(jax.device_get(state.model_state["moe_sizes"]))
        means.append(float(np.mean(jax.device_get(losses))))
        load = sizes.max(axis=1) / np.maximum(sizes.mean(axis=1), 1e-9) if sizes.size else np.zeros(0)
        print(
            f"epoch {epoch:3d}  lm_loss {means[-1]:.4f}  assignments here {sizes.sum(axis=1).tolist()}"
            f"  fullest/mean {np.round(load, 2).tolist()}"
        )
    if report is not None:
        report.update(trainer=trainer, state=state, losses=means)
    return means[0], means[-1]


def run(args, report: Optional[dict] = None) -> Tuple[float, float]:
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.trinity import TrinityConfig

    cfg = TrinityConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.layers,
        num_dense_layers=args.dense_layers, num_attention_heads=args.heads,
        num_key_value_heads=args.kv_heads, head_dim=args.head_dim, sliding_window=args.window,
        global_attn_every_n_layers=args.global_every, num_experts=args.experts,
        num_experts_per_tok=args.top_k, route_scale=args.route_scale,
        experts_held=args.experts_held, expert_offset=args.expert_offset,
        dtype=jnp.dtype(args.dtype), attention=args.attn, remat=args.remat,
    )
    return train(args, cfg, build_trainer, "trinity", report)


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
