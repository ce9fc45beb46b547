"""Train Kimi-Linear's block (``adapcc_tpu/models/kimi_linear.py``) on the
synthetic Markov corpus, through ``DDPTrainer.step`` as ``train_trinity``
trains Trinity's: Kimi Delta Attention layers three to one with latent
attention, sigmoid-routed sparse experts beside a shared expert, a chip's
share of the experts where ``--experts-held`` says so.

The default sizes are a toy (seconds on the CPU pod, the kernels in the
interpreter); the published widths are one command line away on a chip that
holds them::

    python -m adapcc_tpu.workloads.train_kimi_linear --epochs 2
    python -m adapcc_tpu.workloads.train_kimi_linear --hidden 2304 --dense-width 9216 --heads 32 \\
        --head-dim 128 --kv-rank 512 --nope-dim 128 --pe-dim 64 --layers 5 --experts 256 \\
        --experts-held 8 --top-k 8 --seq 8192 --batch 1 --vocab 20480 --dtype bfloat16

The step donates its state and hands out, beside the loss, the assignments
each held expert was given (``TrainState.model_state``), as Trinity's does.
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
    p.add_argument("--expert-width", type=int, default=32, help="moe_intermediate_size")
    p.add_argument("--layers", type=int, default=4, help="the first of the published 27: layer 4 is the latent one")
    p.add_argument("--heads", type=int, default=2, help="heads of both kinds of mixer")
    p.add_argument("--head-dim", type=int, default=16, help="linear_attn_config.head_dim and v_head_dim")
    p.add_argument("--kv-rank", type=int, default=16, help="kv_lora_rank")
    p.add_argument("--nope-dim", type=int, default=16, help="qk_nope_head_dim")
    p.add_argument("--pe-dim", type=int, default=8, help="qk_rope_head_dim")
    p.add_argument("--experts", type=int, default=8, help="num_experts (the router's width)")
    p.add_argument("--top-k", type=int, default=2, help="num_experts_per_token")
    p.add_argument("--experts-held", type=int, default=None, help="routed experts on this chip (default: all)")
    p.add_argument("--expert-offset", type=int, default=0)
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
    """``(trainer, model)``: the model under ``DDPTrainer`` with Trinity's
    stateful loss, which hands the routing counts out beside the loss."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.kimi_linear import KimiLinear
    from adapcc_tpu.models.trinity import stateful_loss
    from adapcc_tpu.strategy.ir import Strategy

    model = KimiLinear(cfg)
    trainer = DDPTrainer(
        stateful_loss(model, loss), tx, mesh, Strategy.ring(int(mesh.devices.size)),
        stateful_loss=True, donate_state=donate_state,
    )
    return trainer, model


def run(args, report: Optional[dict] = None) -> Tuple[float, float]:
    """Train (``train_trinity.train``'s loop); returns (first epoch's mean
    loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.kimi_linear import KimiLinearConfig
    from adapcc_tpu.workloads.train_trinity import train

    cfg = KimiLinearConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.layers,
        linear_attn_num_heads=args.heads, linear_attn_head_dim=args.head_dim,
        num_attention_heads=args.heads, kv_lora_rank=args.kv_rank, qk_nope_head_dim=args.nope_dim,
        qk_rope_head_dim=args.pe_dim, v_head_dim=args.head_dim, num_experts=args.experts,
        num_experts_per_token=args.top_k, experts_held=args.experts_held,
        expert_offset=args.expert_offset, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    return train(args, cfg, build_trainer, "kimi_linear", report)


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
