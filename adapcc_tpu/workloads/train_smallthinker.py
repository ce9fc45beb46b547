"""Train SmallThinker-21BA3B-Instruct's block (``adapcc_tpu/models/smallthinker.py``)
by the language models' one loop (``train_lm.train``): a router that reads the
layer's input before attention, a softmax over the chosen experts' logits,
ReGLU experts with no shared one, windowed rotated and global position-free
grouped-query attention three to one, an untied head; a chip's share of the
experts where ``--experts-held`` says so.  The step hands out, beside the
loss, the assignments each held expert was given.  Toy sizes by default, the
published widths on a chip that holds them::

    python -m adapcc_tpu.workloads.train_smallthinker --epochs 2
    python -m adapcc_tpu.workloads.train_smallthinker --hidden 2560 --expert-width 768 --heads 28 --kv-heads 4 \\
        --head-dim 128 --published-layers 52 --layers-held 0,1,2,3 --window 4096 --experts 64 --experts-held 16 \\
        --top-k 6 --seq 8192 --batch 1 --vocab 37984 --dtype bfloat16 --loss chunked
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__, dense_width="not read: every layer feeds forward through the experts")
    p.add_argument("--expert-width", type=int, default=32, help="moe_ffn_hidden_size")
    p.add_argument("--published-layers", type=int, default=8, help="num_hidden_layers: every fourth from 0 is global, no positions")
    p.add_argument("--layers-held", default="0,1,2,3", help="published indices of the layers run")
    p.add_argument("--heads", type=int, default=7, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=1, help="num_key_value_heads")
    p.add_argument("--head-dim", type=int, default=8, help="head_dim")
    p.add_argument("--window", type=int, default=16, help="sliding_window_size")
    p.add_argument("--experts", type=int, default=8, help="moe_num_primary_experts (the router's width)")
    p.add_argument("--top-k", type=int, default=3, help="moe_num_active_primary_experts")
    p.add_argument("--experts-held", type=int, default=None, help="routed experts on this chip (default: all)")
    p.add_argument("--expert-offset", type=int, default=0)
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss that hands the routing counts out beside the loss."""
    from adapcc_tpu.models.smallthinker import SmallThinker, stateful_loss

    model = SmallThinker(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.smallthinker import SmallThinkerConfig, initial_model_state

    layout = tuple(int(i % 4 != 0) for i in range(args.published_layers))
    cfg = SmallThinkerConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, moe_ffn_hidden_size=args.expert_width,
        num_hidden_layers=args.published_layers, rope_layout=layout, sliding_window_layout=layout,
        layers_held=tuple(int(i) for i in args.layers_held.split(",")), num_attention_heads=args.heads,
        num_key_value_heads=args.kv_heads, head_dim=args.head_dim, sliding_window_size=args.window,
        moe_num_primary_experts=args.experts, moe_num_active_primary_experts=args.top_k,
        experts_held=args.experts_held, expert_offset=args.expert_offset, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    return train_lm.train(args, cfg, train_lm.expert_job("smallthinker", build_trainer, initial_model_state), report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
