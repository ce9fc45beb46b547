"""GPT-2 autoregressive generation (the reference's models/gpt2/interact.py).

The reference samples from its trained PersonaChat GPT-2 with a host-side
top-k/top-p loop (interact.py sample_sequence).  TPU-first shape: the whole
prefill+decode loop is ONE ``lax.scan`` inside one jitted program — fixed-
shape KV cache per layer (no growing arrays), one token per scan step, prompt
tokens force-fed for the first ``prompt_len`` steps and sampled thereafter.
No data-dependent Python control flow; EOS handling is a carried mask.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from adapcc_tpu.models.gpt2 import GPT2, GPT2Config


def filter_top_k(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask everything below the k-th largest logit to -inf."""
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, -jnp.inf, logits)


def filter_top_p(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of the sorted distribution
    with cumulative probability ≥ p (the first token always survives)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a sorted position is cut when the mass *before* it already reaches p
    cut = cum - probs >= p
    threshold = jnp.min(jnp.where(cut, jnp.inf, sorted_logits), axis=-1, keepdims=True)
    return jnp.where(logits < threshold, -jnp.inf, logits)


def sample_token(
    rng: jax.Array,
    logits: jnp.ndarray,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
) -> jnp.ndarray:
    """One token per batch row from filtered logits; greedy iff T == 0."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        logits = filter_top_k(logits, top_k)
    if top_p:
        logits = filter_top_p(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


@partial(
    jax.jit,
    static_argnames=(
        "model", "prompt_len", "max_new_tokens", "temperature", "top_k", "top_p",
        "eos_id",
    ),
)
def generate(
    model: GPT2,
    params: Any,
    prompt: jnp.ndarray,
    prompt_len: int,
    max_new_tokens: int,
    rng: Optional[jax.Array] = None,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Generate ``max_new_tokens`` past a ``[B, prompt_len]`` prompt.

    Returns ``[B, prompt_len + max_new_tokens]`` int32 (prompt included).
    After EOS a row emits ``eos_id`` forever.  The cache holds
    ``model.cfg.max_seq`` slots; total length must fit in it.
    """
    cfg = model.cfg
    B = prompt.shape[0]
    total = prompt_len + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(f"{total} tokens > max_seq={cfg.max_seq} cache slots")
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache = model.init(
        jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32), decode=True,
        pos=jnp.zeros((), jnp.int32),
    )["cache"]

    tokens0 = jnp.zeros((B, total), jnp.int32)
    tokens0 = jax.lax.dynamic_update_slice(tokens0, prompt.astype(jnp.int32), (0, 0))

    def step(carry, t):
        tokens, cache, rng, done = carry
        tok_in = jax.lax.dynamic_slice(tokens, (0, t), (B, 1))
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            tok_in,
            decode=True,
            pos=t,
            mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        nxt = sample_token(sub, logits[:, 0], temperature, top_k, top_p)
        if eos_id is not None:
            # only sampled tokens can latch EOS: positions t < prompt_len are
            # forced prompt tokens (which may legitimately contain eos as a
            # separator, e.g. PersonaChat dialogue turns)
            done = done | ((tok_in[:, 0] == eos_id) & (t >= prompt_len))
            nxt = jnp.where(done, eos_id, nxt)
        # prompt positions are forced, generated positions sampled
        forced = t + 1 < prompt_len
        prompt_next = tokens[:, jnp.minimum(t + 1, total - 1)]
        written = jnp.where(forced, prompt_next, nxt)
        tokens = jax.lax.dynamic_update_slice(tokens, written[:, None], (0, t + 1))
        return (tokens, mutated["cache"], rng, done), None

    done0 = jnp.zeros((B,), bool)
    (tokens, _, _, _), _ = jax.lax.scan(
        step, (tokens0, cache, rng, done0), jnp.arange(total - 1)
    )
    return tokens


# --------------------------------------------------------------------------- #
# interact CLI (models/gpt2/interact.py analog)
# --------------------------------------------------------------------------- #


class ByteTokenizer:
    """Offline fallback tokenizer: raw UTF-8 bytes + BOS/EOS (vocab 258).

    The reference's interact.py needs the downloaded GPT-2 BPE vocab; in a
    zero-egress environment a byte-level mapping keeps the loop usable.
    """

    vocab_size = 258
    bos_id = 256
    eos_id = 257

    def encode(self, text: str) -> list:
        return [self.bos_id] + list(text.encode("utf-8"))

    def decode(self, ids) -> str:
        data = bytes(i for i in ids if 0 <= i < 256)
        return data.decode("utf-8", errors="replace")


def load_tokenizer():
    """HuggingFace GPT-2 BPE when its files are available locally, else the
    byte fallback."""
    try:
        from transformers import GPT2TokenizerFast

        tok = GPT2TokenizerFast.from_pretrained("gpt2", local_files_only=True)
        tok.eos_id = tok.eos_token_id
        return tok
    except Exception:
        return ByteTokenizer()


def interact(argv: Optional[list] = None) -> None:
    """REPL (or one-shot with ``--prompt``): prompt in, continuation out.

    ``--ckpt`` loads trained params (TrainCheckpointState files written by
    workloads/train_gpt2.py ``--checkpoint-file``); the model-shape flags
    mirror train_gpt2's so the same command line that trained a model can
    sample from it.
    """
    import argparse


    ap = argparse.ArgumentParser(description="GPT-2 interactive sampling")
    ap.add_argument("--ckpt", "--checkpoint", dest="ckpt", default=None)
    ap.add_argument("--prompt", default=None,
                    help="one-shot mode: generate from this prompt and exit")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.9)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--top-p", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    # model shape: same flags and defaults as workloads/train_gpt2.py (except
    # --vocab, which follows the tokenizer), so a default-trained checkpoint
    # round-trips with a default generate command line
    ap.add_argument("--vocab", type=int, default=None,
                    help="default: tokenizer vocab size")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--dmodel", type=int, default=128)
    args = ap.parse_args(argv)

    if args.max_new_tokens >= args.seq:
        raise SystemExit(
            f"--max-new-tokens {args.max_new_tokens} must be < --seq "
            f"{args.seq}: the KV cache holds prompt + generation together"
        )
    import os

    mismatch = (
        f"checkpoint {args.ckpt!r} not found or incompatible with the "
        f"model shape (--vocab/--seq/--layers/--heads/--dmodel must "
        f"match training)"
    )
    if args.ckpt and not os.path.exists(args.ckpt):
        # fail before building/compiling the model; same message as the
        # post-load mismatch path so callers can match on one string
        raise SystemExit(mismatch)

    tok = load_tokenizer()
    vocab = args.vocab or max(getattr(tok, "vocab_size", 258), 258)
    cfg = GPT2Config(vocab_size=vocab, max_seq=args.seq,
                     n_layer=args.layers, n_head=args.heads, d_model=args.dmodel)
    model = GPT2(cfg)
    params = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    if args.ckpt:
        from adapcc_tpu.checkpoint import TrainCheckpointState, load_checkpoint

        state = TrainCheckpointState(params={"params": params})
        try:
            ok = load_checkpoint(state, args.ckpt)
        except Exception as e:  # flax from_bytes raises on shape mismatch
            raise SystemExit(f"{mismatch}\n  cause: {e}") from e
        if not ok:
            raise SystemExit(mismatch)
        params = state.params["params"]
        print(f"loaded checkpoint (epoch {state.epoch})")

    rng = jax.random.PRNGKey(args.seed)

    def respond(text: str, rng: jax.Array) -> str:
        ids = tok.encode(text)[-(cfg.max_seq - args.max_new_tokens):]
        prompt = jnp.asarray(np.array(ids)[None], jnp.int32)
        out = generate(
            model, params, prompt, prompt_len=len(ids),
            max_new_tokens=args.max_new_tokens, rng=rng,
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            eos_id=getattr(tok, "eos_id", None),
        )
        return tok.decode(np.asarray(out[0])[len(ids):].tolist())

    if args.prompt is not None:
        print(respond(args.prompt, rng))
        return

    while True:
        try:
            text = input(">>> ")
        except (EOFError, KeyboardInterrupt):
            break
        if not text.strip():
            continue
        rng, sub = jax.random.split(rng)
        print(respond(text, sub))


if __name__ == "__main__":
    interact()
