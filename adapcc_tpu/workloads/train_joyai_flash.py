"""Train JoyAI-LLM-Flash's block (``adapcc_tpu/models/joyai_flash.py``) by the
language models' one loop (``train_lm.train``): rotated latent attention with a
query rank on every layer, sigmoid-routed sparse experts beside a shared
expert, a chip's share of the experts where ``--experts-held`` says so, and a
multi-token-prediction module whose loss term rides beside the trunk's.  The
step hands out, beside the loss, both of its terms and the assignments each
held expert was given.  Toy sizes by default, the published widths on a chip
that holds them::

    python -m adapcc_tpu.workloads.train_joyai_flash --epochs 2
    python -m adapcc_tpu.workloads.train_joyai_flash --hidden 2048 --dense-width 7168 --expert-width 768 \\
        --heads 32 --q-rank 1536 --kv-rank 512 --nope-dim 128 --pe-dim 64 --v-dim 128 --layers 5 \\
        --experts 256 --experts-held 16 --top-k 8 --seq 8192 --batch 1 --vocab 16160 --dtype bfloat16
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__)
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
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with the stateful
    loss of its two terms, which hands them and the routing counts out."""
    from adapcc_tpu.models.joyai_flash import JoyAIFlash, stateful_loss

    model = JoyAIFlash(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.joyai_flash import JoyAIFlashConfig, initial_model_state, record_step

    cfg = JoyAIFlashConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        moe_intermediate_size=args.expert_width, num_hidden_layers=args.layers,
        num_attention_heads=args.heads, q_lora_rank=args.q_rank, kv_lora_rank=args.kv_rank,
        qk_nope_head_dim=args.nope_dim, qk_rope_head_dim=args.pe_dim, v_head_dim=args.v_dim,
        n_routed_experts=args.experts, num_experts_per_tok=args.top_k,
        experts_held=args.experts_held, expert_offset=args.expert_offset,
        dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    job = train_lm.expert_job("joyai_flash", build_trainer, initial_model_state, record_step)
    return train_lm.train(args, cfg, job, report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
