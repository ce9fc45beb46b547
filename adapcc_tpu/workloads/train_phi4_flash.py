"""Train Phi-4-mini-flash-reasoning's SambaY decoder
(``adapcc_tpu/models/phi4_flash.py``) by the language models' one loop
(``train_lm.train``): Mamba-1 selective scans alternating with differential
attention (a window in the first half), and in the second half gated memory
units that read the middle layer's scan and cross-attention on one full
layer's keys and values.  The step hands nothing out beside its loss.  Toy
sizes by default, the published widths on a chip that holds them::

    python -m adapcc_tpu.workloads.train_phi4_flash --epochs 2
    python -m adapcc_tpu.workloads.train_phi4_flash --hidden 2560 --dense-width 10240 --heads 40 \\
        --kv-heads 20 --window 512 --published-layers 32 --layers-held 0,1,16,17,18,19 --seq 8192 --batch 1 \\
        --vocab 25008 --dtype bfloat16 --loss chunked --remat dots
"""

from adapcc_tpu.workloads import train_lm


def build_parser():
    p = train_lm.job_parser(__doc__)
    p.add_argument("--published-layers", type=int, default=8, help="num_hidden_layers: where a layer's kind changes")
    p.add_argument("--layers-held", default="0,1,4,5,6,7",
                   help="published indices of the layers run; layer L/2 makes the memory, L/2 + 1 the shared K/V")
    p.add_argument("--heads", type=int, default=4, help="num_attention_heads")
    p.add_argument("--kv-heads", type=int, default=2, help="num_key_value_heads")
    p.add_argument("--window", type=int, default=16, help="sliding_window")
    p.add_argument("--ssm-state", type=int, default=8, help="the scan's states a channel")
    return p


def build_trainer(cfg, tx, mesh, loss: str = "dense", donate_state: bool = True):
    """``(trainer, model)``: the model under ``DDPTrainer`` with its loss."""
    from adapcc_tpu.models.phi4_flash import Phi4Flash, stateful_loss

    model = Phi4Flash(cfg)
    return train_lm.build_trainer(model, stateful_loss(model, loss), tx, mesh, donate_state)


def run(args, report=None):
    """Train; returns (first epoch's mean loss, last epoch's)."""
    import jax.numpy as jnp

    from adapcc_tpu.models.phi4_flash import Phi4FlashConfig

    cfg = Phi4FlashConfig(
        vocab_size=args.vocab, hidden_size=args.hidden, intermediate_size=args.dense_width,
        num_hidden_layers=args.published_layers, layers_held=tuple(int(i) for i in args.layers_held.split(",")),
        num_attention_heads=args.heads, num_key_value_heads=args.kv_heads, sliding_window=args.window,
        mamba_d_state=args.ssm_state, dtype=jnp.dtype(args.dtype), remat=args.remat,
    )
    job = train_lm.Job("phi4_flash", build_trainer, banner=lambda cfg: f"layers {list(zip(cfg.held, cfg.kinds))}")
    return train_lm.train(args, cfg, job, report)


if __name__ == "__main__":
    train_lm.main(build_parser, run)
