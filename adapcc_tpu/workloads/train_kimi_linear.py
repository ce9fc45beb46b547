"""Train Kimi-Linear's block (``adapcc_tpu/models/kimi_linear.py``) by the
language models' one loop (``train_lm.train``): Kimi Delta Attention layers
three to one with latent attention, sigmoid-routed sparse experts beside a
shared expert, a chip's share of the experts where ``--experts-held`` says so.
The step hands out, beside the loss, the assignments each held expert was
given, as Trinity's does.  Toy sizes by default, the published widths on a
chip that holds them::

    python -m adapcc_tpu.workloads.train_kimi_linear --epochs 2
    python -m adapcc_tpu.workloads.train_kimi_linear --hidden 2304 --dense-width 9216 --heads 32 \\
        --head-dim 128 --kv-rank 512 --nope-dim 128 --pe-dim 64 --layers 5 --experts 256 \\
        --experts-held 8 --top-k 8 --seq 8192 --batch 1 --vocab 20480 --dtype bfloat16
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__)
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
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with its stateful
    loss (Trinity's, by ``models/kimi_linear``'s name), which hands the routing
    counts out beside the loss."""
    from adapcc_tpu.models.kimi_linear import KimiLinear, stateful_loss

    model = KimiLinear(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.kimi_linear import KimiLinearConfig, initial_model_state

    cfg = KimiLinearConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.layers,
        linear_attn_num_heads=args.heads, linear_attn_head_dim=args.head_dim,
        num_attention_heads=args.heads, kv_lora_rank=args.kv_rank, qk_nope_head_dim=args.nope_dim,
        qk_rope_head_dim=args.pe_dim, v_head_dim=args.head_dim, num_experts=args.experts,
        num_experts_per_token=args.top_k, experts_held=args.experts_held,
        expert_offset=args.expert_offset, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    return train_lm.train(args, cfg, train_lm.expert_job("kimi_linear", build_trainer, initial_model_state), report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
