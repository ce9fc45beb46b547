"""Train JoyAI-LLM-Flash's block (``adapcc_tpu/models/joyai_flash.py``) on the
synthetic Markov corpus, through ``DDPTrainer.step`` as ``train_trinity``
trains Trinity's: rotated latent attention with a query rank on every layer,
sigmoid-routed sparse experts beside a shared expert, a chip's share of the
experts where ``--experts-held`` says so, and a multi-token-prediction module
whose loss term rides beside the trunk's.

The default sizes are a toy (seconds on the CPU pod, the flash kernels in the
interpreter); the published widths are one command line away on a chip that
holds them::

    python -m adapcc_tpu.workloads.train_joyai_flash --epochs 2
    python -m adapcc_tpu.workloads.train_joyai_flash --hidden 2048 --dense-width 7168 --expert-width 768 \\
        --heads 32 --q-rank 1536 --kv-rank 512 --nope-dim 128 --pe-dim 64 --v-dim 128 --layers 5 \\
        --experts 256 --experts-held 16 --top-k 8 --seq 8192 --batch 1 --vocab 16160 --dtype bfloat16

The step donates its state and hands out, beside the loss, both of its terms
and the assignments each held expert was given (``TrainState.model_state``).
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
    p.add_argument("--layers", type=int, default=3, help="trunk layers: the first is dense; the MTP module comes on top")
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--q-rank", type=int, default=24, help="q_lora_rank")
    p.add_argument("--kv-rank", type=int, default=16, help="kv_lora_rank")
    p.add_argument("--nope-dim", type=int, default=16, help="qk_nope_head_dim")
    p.add_argument("--pe-dim", type=int, default=8, help="qk_rope_head_dim")
    p.add_argument("--v-dim", type=int, default=16, help="v_head_dim")
    p.add_argument("--experts", type=int, default=8, help="n_routed_experts (the router's width)")
    p.add_argument("--top-k", type=int, default=2, help="num_experts_per_tok")
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
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss of its two terms, which hands them and the routing counts out."""
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.joyai_flash import JoyAIFlash, stateful_loss
    from adapcc_tpu.strategy.ir import Strategy

    model = JoyAIFlash(cfg)
    trainer = DDPTrainer(
        stateful_loss(model, loss), tx, mesh, Strategy.ring(int(mesh.devices.size)),
        stateful_loss=True, donate_state=donate_state,
    )
    return trainer, model


def run(args, report: Optional[dict] = None) -> Tuple[float, float]:
    """Train (``train_trinity.train``'s loop); returns (first epoch's mean
    loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.joyai_flash import JoyAIFlashConfig, initial_model_state, record_step
    from adapcc_tpu.workloads.train_trinity import train

    cfg = JoyAIFlashConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.layers,
        num_attention_heads=args.heads, q_lora_rank=args.q_rank, kv_lora_rank=args.kv_rank,
        qk_nope_head_dim=args.nope_dim, qk_rope_head_dim=args.pe_dim, v_head_dim=args.v_dim,
        n_routed_experts=args.experts, num_experts_per_tok=args.top_k,
        experts_held=args.experts_held, expert_offset=args.expert_offset,
        dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    return train(args, cfg, build_trainer, "joyai_flash", report, initial_model_state, record_step)


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
