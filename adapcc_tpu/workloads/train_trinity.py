"""Train Trinity-Mini's block (``adapcc_tpu/models/trinity.py``) by the language
models' one loop (``train_lm.train``): windowed and full grouped-query
attention with a gated output, sigmoid-routed sparse experts beside a shared
expert, a chip's share of the experts where ``--experts-held`` says so.  The
step hands out, beside the loss, the assignments each held expert was given.
Toy sizes by default; every ``config.json`` key of docs/TRINITY.md has a flag::

    python -m adapcc_tpu.workloads.train_trinity --epochs 2
    python -m adapcc_tpu.workloads.train_trinity --hidden 2048 --heads 32 --kv-heads 4 \\
        --head-dim 128 --layers 5 --dense-layers 1 --experts 128 --experts-held 16 \\
        --top-k 8 --seq 8192 --batch 1 --vocab 25024 --loss chunked --dtype bfloat16
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__)
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
    p.add_argument("--attn", choices=("flash", "xla"), default="xla")
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss that hands the routing counts out beside the loss."""
    from adapcc_tpu.models.trinity import Trinity, stateful_loss

    model = Trinity(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.trinity import TrinityConfig, initial_model_state

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
    return train_lm.train(args, cfg, train_lm.expert_job("trinity", build_trainer, initial_model_state), report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
