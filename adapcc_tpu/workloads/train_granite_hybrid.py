"""Train Granite 4.0-H's block (``adapcc_tpu/models/granite_hybrid.py``) by the
language models' one loop (``train_lm.train``): Mamba-2 state-space layers nine
to one with grouped-query attention that carries no positions, a dense gated
MLP in every layer, scaled residuals, embedding and logits over a tied head.
The step hands out, beside the loss, the smallest decay a chunk of a scan laid
on its state.  Toy sizes by default, the published widths on a chip that holds
them::

    python -m adapcc_tpu.workloads.train_granite_hybrid --epochs 2
    python -m adapcc_tpu.workloads.train_granite_hybrid --hidden 2048 --dense-width 8192 --heads 32 \\
        --kv-heads 8 --ssm-heads 64 --ssm-head-dim 64 --ssm-state 128 --layers 10 --seq 8192 --batch 1 \\
        --vocab 12544 --dtype bfloat16 --loss chunked --remat full
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__, dense_width="shared_intermediate_size")
    p.add_argument("--layers", type=int, default=6, help="the first of the published 40: layer 5 (from 0) is attention")
    p.add_argument("--heads", type=int, default=4, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=2, help="num_key_value_heads")
    p.add_argument("--ssm-heads", type=int, default=8, help="mamba_n_heads; times --ssm-head-dim it is twice --hidden")
    p.add_argument("--ssm-head-dim", type=int, default=16, help="mamba_d_head")
    p.add_argument("--ssm-state", type=int, default=16, help="mamba_d_state")
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with its stateful
    loss, which hands the scans' decay floor out beside the loss."""
    from adapcc_tpu.models.granite_hybrid import GraniteHybrid, stateful_loss

    model = GraniteHybrid(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.granite_hybrid import GraniteHybridConfig, initial_model_state, record_step

    cfg = GraniteHybridConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, shared_intermediate_size=args.dense_width,
        num_hidden_layers=args.layers, num_attention_heads=args.heads, num_key_value_heads=args.kv_heads,
        attention_multiplier=1.0 / (args.hidden // args.heads), mamba_n_heads=args.ssm_heads,
        mamba_d_head=args.ssm_head_dim, mamba_d_state=args.ssm_state, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    job = train_lm.Job(
        "granite_hybrid", build_trainer, first_state=lambda cfg: initial_model_state(),
        span="ssd.read_floor", record=record_step,
        epoch=lambda model_state: f"  smallest chunk decay {float(model_state['ssd_decay_floor']):.3e}",
    )
    return train_lm.train(args, cfg, job, report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
