"""Train LFM2-24B-A2B's block (``adapcc_tpu/models/lfm2_moe.py``) by the
language models' one loop (``train_lm.train``): a double-gated short
convolution as the mixer of three layers in four, rotated grouped-query
attention with per-head q/k norms on the fourth, a dense gated MLP in the
leading layers and sigmoid-routed sparse experts with no shared one after
them, a tied head; a chip's share of the experts where ``--experts-held`` says
so.  The step hands out, beside the loss, the assignments each held expert was
given.  Toy sizes by default, the published widths on a chip that holds them::

    python -m adapcc_tpu.workloads.train_lfm2_moe --epochs 2
    python -m adapcc_tpu.workloads.train_lfm2_moe --hidden 2048 --dense-width 11776 --expert-width 1536 \\
        --heads 32 --kv-heads 8 --published-layers 40 --dense-layers 2 --layers-held 1,2,3,4,5 --experts 64 \\
        --experts-held 16 --top-k 4 --seq 8192 --batch 1 --vocab 16384 --dtype bfloat16 --loss chunked
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__)
    p.add_argument("--expert-width", type=int, default=32, help="moe_intermediate_size")
    p.add_argument("--published-layers", type=int, default=8, help="num_hidden_layers: every fourth from 2 is attention")
    p.add_argument("--dense-layers", type=int, default=2, help="num_dense_layers, of the published ones")
    p.add_argument("--layers-held", default="1,2,3,4,5", help="published indices of the layers run")
    p.add_argument("--heads", type=int, default=4, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=2, help="num_key_value_heads")
    p.add_argument("--taps", type=int, default=3, help="conv_L_cache")
    p.add_argument("--experts", type=int, default=8, help="num_experts (the router's width)")
    p.add_argument("--top-k", type=int, default=2, help="num_experts_per_tok")
    p.add_argument("--experts-held", type=int, default=None, help="routed experts on this chip (default: all)")
    p.add_argument("--expert-offset", type=int, default=0)
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss that hands the routing counts out beside the loss."""
    from adapcc_tpu.models.lfm2_moe import Lfm2Moe, stateful_loss

    model = Lfm2Moe(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.lfm2_moe import Lfm2MoeConfig, initial_model_state

    cfg = Lfm2MoeConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.published_layers,
        num_dense_layers=args.dense_layers,
        layer_types=tuple("full_attention" if i % 4 == 2 else "conv" for i in range(args.published_layers)),
        layers_held=tuple(int(i) for i in args.layers_held.split(",")), num_attention_heads=args.heads,
        num_key_value_heads=args.kv_heads, conv_L_cache=args.taps, num_experts=args.experts,
        num_experts_per_tok=args.top_k, experts_held=args.experts_held, expert_offset=args.expert_offset,
        dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    return train_lm.train(args, cfg, train_lm.expert_job("lfm2_moe", build_trainer, initial_model_state), report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
