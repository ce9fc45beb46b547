"""GPT-2 language-model training pipeline — the reference train_gpt2_ddp flow.

The reference fine-tunes HuggingFace GPT-2 on PersonaChat under torch DDP
with ignite (models/gpt2/train_gpt2_ddp.py): dataset → packed LM batches →
AdamW + linear LR decay + gradient clipping → periodic evaluation (the
convai_evaluation.py metric is perplexity) → interact.py sampling.  This
pipeline keeps that shape end to end on TPU: corpus → packed ``[B, T]``
batches → :class:`DDPTrainer` (adaptive allreduce) with warmup+decay LR and
global-norm clipping → held-out perplexity per epoch → a generation sample
from the trained weights.

The corpus is a seeded Markov chain over the vocabulary (zero-egress stand-in
for PersonaChat): it has real sequential structure, so validation perplexity
falls far below the uniform bound iff the model actually learns.

Run (virtual pod):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python -m adapcc_tpu.workloads.train_gpt2 --epochs 2
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence, Tuple

import numpy as np


# --- corpus (PersonaChat stand-in) --------------------------------------------


def markov_corpus(
    n_tokens: int, vocab_size: int, branching: int = 4, seed: int = 0
) -> np.ndarray:
    """A token stream from a sparse random Markov chain: each token has
    ``branching`` likely successors.  Entropy ≈ log(branching) ≪
    log(vocab_size), so a language model has real structure to learn."""
    rng = np.random.default_rng(seed)
    successors = rng.integers(0, vocab_size, size=(vocab_size, branching))
    probs = rng.dirichlet(np.ones(branching) * 2.0, size=vocab_size)
    # draw all uniforms up front and step via cumulative inverse transform —
    # per-token rng.choice(p=...) revalidates the distribution every call and
    # costs seconds at default corpus sizes
    cum = probs.cumsum(axis=1)
    uniforms = rng.random(n_tokens)
    out = np.empty(n_tokens, dtype=np.int32)
    tok = int(rng.integers(0, vocab_size))
    for i in range(n_tokens):
        out[i] = tok
        tok = int(successors[tok, np.searchsorted(cum[tok], uniforms[i])])
    return out


def pack_sequences(stream: np.ndarray, seq_len: int) -> np.ndarray:
    """Contiguous ``[N, seq_len]`` packing (drops the ragged tail) — the
    reference's padded-batch builder, minus padding (packing wastes nothing)."""
    n = len(stream) // seq_len
    return stream[: n * seq_len].reshape(n, seq_len)


# --- evaluation (convai_evaluation.py analog: perplexity + hits@1) ------------


#: per-model jitted NLL — a fresh @jax.jit closure per evaluate call would
#: discard the compile cache and recompile the forward pass every epoch
_NLL_CACHE: dict = {}

#: fp32 logits one evaluation call may materialize.  The evaluators score
#: ``[rows, T, vocab]`` fp32 logits (hits@1 a log-softmax of the same size
#: beside them): at GPT-2 small's T=1,024 / vocab 50,257 one row is 206 MB,
#: so a fixed 16-row (perplexity) or 64-row (hits@1) call is 3.3 / 13 GB on
#: a 16 GB chip.  Rows per call are cut to this budget instead.
_EVAL_LOGITS_BYTES = 1 << 30


def eval_rows(seq_len: int, vocab_size: int, cap: int = 16) -> int:
    """Rows per evaluation call: ``cap`` where the logits fit the budget
    (every toy/test width), fewer at real widths."""
    return max(1, min(cap, _EVAL_LOGITS_BYTES // (seq_len * vocab_size * 4)))


def evaluate_perplexity(
    model, params, packed: np.ndarray, batch: Optional[int] = None
) -> float:
    """exp(mean next-token NLL) over a held-out packed set."""
    import jax
    import jax.numpy as jnp

    from adapcc_tpu.models.gpt2 import lm_loss

    if batch is None:
        batch = eval_rows(packed.shape[1], model.cfg.vocab_size)
    nll = _NLL_CACHE.get(model)
    if nll is None:
        nll = jax.jit(lambda p, b: lm_loss(model.apply(p, b), b))
        _NLL_CACHE[model] = nll

    total, count = 0.0, 0
    for i in range(0, len(packed) - batch + 1, batch):
        b = jnp.asarray(packed[i : i + batch])
        total += float(nll(params, b)) * len(b)
        count += len(b)
    if count == 0:
        raise ValueError(f"held-out set smaller than one batch ({len(packed)} < {batch})")
    return float(np.exp(total / count))


_SCORE_CACHE: dict = {}


def evaluate_hits_at_1(
    model, params, packed: np.ndarray, n_candidates: int = 4, max_rows: int = 64
) -> float:
    """Candidate-ranking accuracy, the reference's ConvAI hits@1 metric
    (models/gpt2/convai_evaluation.py ranks each gold reply against
    distractor candidates; its double-head model uses a trained classifier,
    ours ranks by LM log-likelihood — the zero-extra-parameter variant).

    Each held-out row ``[T]`` splits into context (first half) and
    continuation (second half); the gold continuation competes against
    ``n_candidates - 1`` distractor continuations drawn from other rows.
    Score = sum of next-token log-probs over the continuation positions.
    Chance level is ``1 / n_candidates``.
    """
    import jax
    import jax.numpy as jnp

    rows = np.asarray(packed[:max_rows])
    M, T = rows.shape
    half = T // 2
    if M < n_candidates or half < 2:
        raise ValueError(f"need >= {n_candidates} rows of length >= 4, got {rows.shape}")

    # the closure bakes in `half`, so the cache key must carry it (a
    # hash-equal model with a different seq split must not collide)
    score = _SCORE_CACHE.get((model, half))
    if score is None:

        def _score(p, seqs):
            # logits[:, t] predicts token t+1; sum log p over the continuation
            logits = model.apply(p, seqs).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nxt = jnp.take_along_axis(
                logp[:, :-1], seqs[:, 1:, None], axis=-1
            )[..., 0]
            return nxt[:, half - 1 :].sum(axis=-1)

        score = jax.jit(_score)
        _SCORE_CACHE[(model, half)] = score

    # candidate c for row i = continuation of row (i + c·stride) mod M; c=0 is
    # the gold one.  A fixed stride keeps the distractor draw deterministic.
    seqs = np.stack([
        np.concatenate([rows[i, :half], rows[(i + c * max(1, M // n_candidates)) % M, half:]])
        for i in range(M)
        for c in range(n_candidates)
    ])
    # the M·C sequences score in calls of one fixed shape (one compile)
    # sized to the logits budget; the tail call is padded with its last row
    per_call = eval_rows(T, model.cfg.vocab_size, cap=len(seqs))
    scores = []
    for i in range(0, len(seqs), per_call):
        chunk = seqs[i : i + per_call]
        n = len(chunk)
        if n < per_call:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], per_call - n, axis=0)])
        scores.append(np.asarray(score(params, jnp.asarray(chunk)))[:n])
    s = np.concatenate(scores).reshape(M, n_candidates)
    return float(np.mean(np.argmax(s, axis=1) == 0))


# --- training -----------------------------------------------------------------


def _run_pipeline(
    args, mesh, world, model, cfg, params, tx, train_set, val_set
) -> Tuple[float, float]:
    """The --pp-stages branch: block stack split over stages, every hop
    through the traced engine, schedule resolved env > flag > tuner
    (docs/PIPELINE.md)."""
    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.pipe import (
        PipelineExecutor,
        merge_params,
        partition_gpt2,
        split_params,
        sync_tied_embedding,
    )
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils import AverageMeter

    partition = partition_gpt2(cfg, args.pp_stages)
    engine = CollectiveEngine(mesh, Strategy.ring(world))
    executor = PipelineExecutor(
        cfg,
        partition,
        engine,
        num_microbatches=args.pp_microbatches,
        schedule=args.pp_schedule,
    )
    stage_params = split_params(params["params"], partition)
    opt_state = tx.init(stage_params)
    print(
        f"pipeline: {args.pp_stages} stages x {args.pp_microbatches} "
        f"microbatches, schedule {executor.schedule_kind}, "
        f"params/stage {partition.param_counts}"
    )

    def merged():
        # merge_params already rebuilds the {"params": ...} wrapper
        return merge_params(stage_params, partition)

    initial_ppl = evaluate_perplexity(model, merged(), val_set)
    print(f"val ppl before training: {initial_ppl:.1f} (uniform bound {float(args.vocab):.0f})")

    rng = np.random.default_rng(0)
    steps_per_epoch = max(1, len(train_set) // args.batch)
    ppl = initial_ppl
    for epoch in range(args.epochs):
        losses = AverageMeter("lm_loss", ":.4f")
        order = rng.permutation(len(train_set))
        for i in range(steps_per_epoch):
            b = jnp.asarray(train_set[order[i * args.batch : (i + 1) * args.batch]])
            loss, grads, report = executor.forward_backward(stage_params, b)
            updates, opt_state = tx.update(grads, opt_state, stage_params)
            stage_params = optax.apply_updates(stage_params, updates)
            sync_tied_embedding(stage_params)
            losses.update(float(loss), args.batch)
        ppl = evaluate_perplexity(model, merged(), val_set)
        print(
            f"epoch {epoch:3d}  {losses}  val ppl {ppl:.2f}  "
            f"(bubble {report.bubble_fraction:.2f}, stash peak "
            f"{report.stash_peak})"
        )

    hits = evaluate_hits_at_1(model, merged(), val_set)
    print(f"hits@1 over 4 candidates: {hits:.2f} (chance 0.25)")
    return initial_ppl, ppl


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=20)
    p.add_argument("--clip-norm", type=float, default=1.0, help="reference max_norm=1.0")
    # 258 = the generate CLI's ByteTokenizer vocab (bytes + BOS/EOS), so a
    # default-trained checkpoint round-trips with a default generate command
    p.add_argument("--vocab", type=int, default=258)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--dmodel", type=int, default=128)
    p.add_argument("--corpus-tokens", type=int, default=200_000)
    p.add_argument("--world", type=int, default=None)
    p.add_argument("--checkpoint-file", type=str, default=None)
    p.add_argument("--sample", action="store_true", help="print a generation sample at the end")
    p.add_argument("--sp", choices=("none", "ring", "ulysses"), default="none",
                   help="sequence parallelism: shard the sequence (not the "
                        "batch) over the world — the long-context regime")
    p.add_argument("--attn", choices=("xla", "flash"), default="xla",
                   help="block attention implementation (flash = Pallas kernel)")
    p.add_argument("--loss", choices=("dense", "chunked"), default="dense",
                   help="LM loss: dense materializes [B,T,vocab] logits; "
                        "chunked fuses the head into an online-softmax scan")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient accumulation: microbatches per step "
                        "(DDP path; per-rank batch must divide by it)")
    p.add_argument("--zero1", action="store_true",
                   help="shard the optimizer state ZeRO-1 style inside the "
                        "adaptive DDP step (fp32 flat master)")
    p.add_argument("--grad-compress", choices=["off", "bf16"], default="off",
                   help="bf16 gradient-sync wire compression (DDP path)")
    p.add_argument("--pp-stages", type=int, default=0,
                   help="pipeline parallelism: split the block stack over "
                        "this many stages (0 = off; docs/PIPELINE.md)")
    p.add_argument("--pp-microbatches", type=int, default=4,
                   help="microbatches per pipelined step (--batch must "
                        "divide by it)")
    p.add_argument("--pp-schedule", choices=("gpipe", "1f1b"), default=None,
                   help="pipeline tick schedule; omitted = "
                        "ADAPCC_PIPE_SCHEDULE > tuner > 1f1b")
    return p


def run(args, report: Optional[dict] = None) -> Tuple[float, float]:
    """Train; returns (initial_val_ppl, final_val_ppl).

    ``report`` (a dict the caller owns) receives what the return value
    drops — ``step_losses`` (every step's mean loss, in order) and, on the
    DDP path, the ``trainer`` with the last ``state`` and ``batch`` it
    stepped — so a caller can check the compiled step itself
    (``chip_smoke.py`` counts its flash kernels)."""
    if args.sp != "none" and (args.accum != 1 or args.zero1):
        raise ValueError(
            "--accum/--zero1 ride the DDP trainer; they are not wired "
            "into the sequence-parallel step — drop --sp to use them"
        )
    if args.pp_stages:
        incompatible = []
        if args.sp != "none":
            incompatible.append("--sp")
        if args.accum != 1:
            incompatible.append("--accum")
        if args.zero1:
            incompatible.append("--zero1")
        if args.grad_compress != "off":
            incompatible.append("--grad-compress")
        if args.checkpoint_file:
            incompatible.append("--checkpoint-file")
        if incompatible:
            raise ValueError(
                f"{', '.join(incompatible)} ride the DDP trainer; the "
                "pipeline-parallel step (--pp-stages) runs its own "
                "executor — the pipeline already microbatches, syncs no "
                "gradients, and is not checkpoint-wired (docs/PIPELINE.md)"
            )
        if args.pp_stages < 2:
            raise ValueError(
                f"--pp-stages {args.pp_stages}: a pipeline needs at least "
                "2 stages (omit the flag for single-stage training)"
            )
        if args.batch % args.pp_microbatches:
            raise ValueError(
                f"--batch {args.batch} must divide by --pp-microbatches "
                f"{args.pp_microbatches}"
            )
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.data import device_batches
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils import AverageMeter

    mesh = build_world_mesh(args.world)
    world = int(mesh.devices.size)

    stream = markov_corpus(args.corpus_tokens, args.vocab, seed=0)
    packed = pack_sequences(stream, args.seq)
    n_val = max(16, len(packed) // 10)
    if len(packed) < n_val + args.batch:
        raise ValueError(
            f"corpus too small: {len(packed)} sequences of len {args.seq} can't "
            f"cover {n_val} validation rows plus one {args.batch}-row training "
            f"batch; raise --corpus-tokens or lower --seq/--batch"
        )
    train_set, val_set = packed[:-n_val], packed[-n_val:]

    cfg = GPT2Config(
        vocab_size=args.vocab, max_seq=args.seq, n_layer=args.layers,
        n_head=args.heads, d_model=args.dmodel, dtype=jnp.float32,
        attention=args.attn,
    )
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(train_set[:1]))

    if args.loss == "chunked":
        # fuse the LM head into the online-softmax loss: no [B, T, vocab]
        # logits tensor (ops/chunked_ce.py) — the long-vocab memory saver
        # (the SP branch passes loss= through to its own sharded variant)
        from adapcc_tpu.models.gpt2 import lm_loss_chunked

        def loss_fn(p, b):
            return lm_loss_chunked(model, p, b, block=min(1024, args.vocab))
    else:

        def loss_fn(p, b):
            return lm_loss(model.apply(p, b), b)

    steps_per_epoch = max(1, len(train_set) // args.batch)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=args.lr,
        warmup_steps=args.warmup_steps,
        decay_steps=max(args.warmup_steps + 1, steps_per_epoch * args.epochs),
    )
    # reference recipe: AdamW + clipping + decaying LR (train_gpt2_ddp.py's
    # PiecewiseLinear decay and max_norm clipping)
    tx = optax.chain(
        optax.clip_by_global_norm(args.clip_norm),
        optax.adamw(schedule, weight_decay=0.01),
    )
    if args.pp_stages:
        return _run_pipeline(
            args, mesh, world, model, cfg, params, tx, train_set, val_set
        )
    if args.sp != "none":
        # sequence parallelism: the batch is replicated and the SEQUENCE is
        # sharded over the world axis — the long-context regime (the DDP
        # axis is the reference's; SP is the new capability, SURVEY §5.7)
        import dataclasses

        from adapcc_tpu.parallel import gpt2_sp_train_step

        if args.seq % world:
            raise ValueError(f"--seq {args.seq} must divide by world {world} under --sp")
        sp_model = GPT2(dataclasses.replace(cfg, sp_axis="ranks", sp_impl=args.sp))
        sp_step = gpt2_sp_train_step(sp_model, tx, mesh, loss=args.loss)
        trainer = None
    else:
        trainer = DDPTrainer(
            loss_fn, tx, mesh, Strategy.ring(world),
            accum_steps=args.accum, zero1=args.zero1,
            grad_compress=args.grad_compress,
            # NO donate_state here, unlike the other workloads: this loop
            # feeds from the async device_batches prefetcher, and a donating
            # step racing the prefetch thread's device_put deadlocks the
            # XLA CPU collective rendezvous (verified on the 8-device pod:
            # only some ranks join, 40 s timeout, SIGABRT).  Whether that
            # still holds is ROADMAP S1's to find out, with the tests that
            # keep the old state; until then this loop does not donate.
        )
    state = (
        trainer.init_state(params) if trainer is not None
        else TrainState.create(params, tx)
    )

    initial_ppl = evaluate_perplexity(model, state.params, val_set)
    uniform = float(args.vocab)
    print(f"val ppl before training: {initial_ppl:.1f} (uniform bound {uniform:.0f})")

    ppl = initial_ppl
    for epoch in range(args.epochs):
        losses = AverageMeter("lm_loss", ":.4f")
        # keep per-step losses on device; one host sync per epoch preserves
        # the trainer's async dispatch (see DDPTrainer's host-step comment)
        epoch_losses = []
        # async input pipeline: the next batch lands on device — already
        # sharded over the data axis on the DDP path — while the current
        # step computes
        batches = device_batches(
            train_set, args.batch,
            mesh=None if trainer is None else mesh, seed=epoch,
        )
        for b in batches:
            if trainer is None:
                params2, opt_state2, loss = sp_step(
                    state.params, state.opt_state, b
                )
                state = TrainState(
                    params=params2, opt_state=opt_state2, step=state.step + 1
                )
            else:
                state, loss = trainer.step(state, b)
            epoch_losses.append(jnp.mean(loss))
        epoch_losses = np.asarray(jax.device_get(epoch_losses))
        for val in epoch_losses:
            losses.update(float(val), args.batch)
        if report is not None:
            report.setdefault("step_losses", []).extend(map(float, epoch_losses))
            if trainer is not None:
                report.update(trainer=trainer, state=state, batch=b)
        ppl = evaluate_perplexity(model, state.params, val_set)
        print(f"epoch {epoch:3d}  {losses}  val ppl {ppl:.2f}")

        if args.checkpoint_file:
            from adapcc_tpu.checkpoint import TrainCheckpointState, save_checkpoint

            save_checkpoint(
                TrainCheckpointState(
                    params=state.params, opt_state=state.opt_state,
                    epoch=epoch, step=int(state.step),
                    # --zero1 runs stamp the optimizer layout so a resume
                    # with --zero1-ring flipped fails loudly (checkpoint.py's
                    # apply_snapshot guard) instead of loading permuted
                    # master weights
                    extra=(
                        trainer.checkpoint_extra() if trainer is not None
                        else {}
                    ),
                ),
                args.checkpoint_file,
            )

    hits = evaluate_hits_at_1(model, state.params, val_set)
    print(f"hits@1 over 4 candidates: {hits:.2f} (chance 0.25)")

    if args.sample:
        from adapcc_tpu.models.gpt2_generate import generate

        prompt = jnp.asarray(val_set[:1, :8], jnp.int32)
        out = generate(
            model, state.params["params"],  # init() wraps in a "params" collection
            prompt, prompt_len=8, max_new_tokens=24, temperature=0.8, top_k=8,
        )
        print("sample continuation:", np.asarray(out[0])[8:].tolist())

    return initial_ppl, ppl


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
