"""GPT-2: the flagship training workload.

The reference trains HuggingFace GPT-2 (PersonaChat) under torch DDP with its
adaptive allreduce (models/gpt2/train_gpt2_ddp.py); this is a from-scratch
flax implementation of the same architecture family, shaped for TPU:

- all matmuls in ``bfloat16`` with ``float32`` accumulation/params — the MXU
  sweet spot;
- static shapes everywhere (fixed ``max_seq``), causal mask via additive
  bias, no dynamic control flow under jit;
- optional ``nn.remat`` over blocks to trade FLOPs for HBM;
- weight-tied LM head (embedding transpose), GPT-2 initialization scheme
  (scaled residual projections).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    #: rematerialization granularity when ``remat`` is on: "full" recomputes
    #: everything in the block (max HBM savings, ~1/3 extra FLOPs); "dots"
    #: saves matmul outputs and recomputes only the cheap elementwise ops
    #: (jax.checkpoint_policies.checkpoint_dots) — the usual TPU sweet spot,
    #: since MXU FLOPs are the scarce resource and elementwise recompute is
    #: nearly free against HBM-bound steps
    remat_policy: str = "full"
    #: "xla" materializes [T, T] scores and lets XLA fuse; "flash" runs the
    #: blockwise Pallas kernel (ops/flash_attention.py) — O(T) memory, MXU
    #: tiles, no attention-matrix HBM traffic.  Training path only (decode
    #: uses the KV cache) and requires dropout == 0.
    attention: str = "xla"
    #: flash kernel tile edge (block_q == block_k).  None: by shape, from the
    #: table measured on the chip (ops/flash_attention.TILE_TABLE); an integer
    #: overrides it (tests at toy shapes pass small tiles)
    flash_block: Optional[int] = None
    #: sequence parallelism: when set (a mesh axis name), the model expects
    #: to run INSIDE shard_map with tokens sequence-sharded over that axis —
    #: attention crosses shards via the ring / Ulysses programs
    #: (parallel/gpt2_sp.py wraps the whole train step), positions are
    #: globally offset by the shard index, and ``attention == "flash"``
    #: selects the Pallas block kernel inside the SP program.  Training
    #: only (decode keeps a single-device KV cache); requires dropout == 0.
    sp_axis: Optional[str] = None
    #: which SP scheme carries attention across shards: "ring" rotates K/V
    #: blocks (O(T_local) memory), "ulysses" trades sequence for heads with
    #: one all-to-all each way (needs n_head % world == 0)
    sp_impl: str = "ring"

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny() -> "GPT2Config":
        """Test-sized config: compiles in seconds, fits anywhere."""
        return GPT2Config(vocab_size=512, max_seq=64, n_layer=2, n_head=2, d_model=64)


class CausalSelfAttention(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, deterministic: bool = True, decode: bool = False
    ) -> jnp.ndarray:
        cfg = self.cfg
        B, T, C = x.shape
        head_dim = cfg.d_model // cfg.n_head

        qkv = nn.Dense(3 * cfg.d_model, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, cfg.n_head, head_dim)
        k = k.reshape(B, T, cfg.n_head, head_dim)
        v = v.reshape(B, T, cfg.n_head, head_dim)

        scale = 1.0 / np.sqrt(head_dim)
        if cfg.sp_axis is not None and not decode:
            # sequence-parallel attention: this module runs inside shard_map
            # with [B, T_local, ...] shards; K/V cross shards via the ring or
            # Ulysses program (attention dropout unsupported there)
            if cfg.dropout != 0.0:
                raise ValueError("sequence parallelism requires dropout == 0")
            block_impl = "flash" if cfg.attention == "flash" else "dense"
            if cfg.sp_impl == "ring":
                from adapcc_tpu.parallel.ring_attention import ring_attention_shard

                out = ring_attention_shard(
                    q, k, v, axis_name=cfg.sp_axis, causal=True, scale=scale,
                    block_impl=block_impl,
                    block_q=cfg.flash_block, block_k=cfg.flash_block,
                )
            elif cfg.sp_impl == "ulysses":
                from adapcc_tpu.parallel.ulysses import ulysses_attention_shard

                out = ulysses_attention_shard(
                    q, k, v, axis_name=cfg.sp_axis, causal=True, scale=scale,
                    block_impl=block_impl,
                    block_q=cfg.flash_block, block_k=cfg.flash_block,
                )
            else:
                raise ValueError(f"unknown sp_impl {cfg.sp_impl!r} (ring|ulysses)")
            return self._project(out.reshape(B, T, cfg.d_model), deterministic)
        if decode:
            # single-token autoregressive step against a fixed-shape KV cache
            # (static [max_seq] slots — no dynamic shapes under jit)
            if T != 1:
                raise ValueError(f"decode mode feeds one token at a time, got T={T}")
            is_init = self.has_variable("cache", "cached_key")
            cached_k = self.variable(
                "cache", "cached_key",
                jnp.zeros, (B, cfg.max_seq, cfg.n_head, head_dim), cfg.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_value",
                jnp.zeros, (B, cfg.max_seq, cfg.n_head, head_dim), cfg.dtype,
            )
            cache_idx = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            if is_init:
                idx = cache_idx.value
                cached_k.value = jax.lax.dynamic_update_slice(
                    cached_k.value, k.astype(cfg.dtype), (0, idx, 0, 0)
                )
                cached_v.value = jax.lax.dynamic_update_slice(
                    cached_v.value, v.astype(cfg.dtype), (0, idx, 0, 0)
                )
                cache_idx.value = idx + 1
                k, v = cached_k.value, cached_v.value
                att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
                valid = jnp.arange(cfg.max_seq) <= idx
                att = jnp.where(valid[None, None, None], att, -1e30)
            else:
                att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        elif cfg.attention == "flash" and cfg.dropout == 0.0:
            from adapcc_tpu.ops import flash_attention

            out = flash_attention(
                q.astype(cfg.dtype), k.astype(cfg.dtype), v.astype(cfg.dtype),
                causal=True, scale=scale,
                block_q=cfg.flash_block, block_k=cfg.flash_block,
            )
            return self._project(out.reshape(B, T, cfg.d_model), deterministic)
        else:
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
            causal = jnp.tril(jnp.ones((T, T), dtype=bool))
            att = jnp.where(causal[None, None], att, -1e30)
        att = jax.nn.softmax(att, axis=-1).astype(cfg.dtype)
        att = nn.Dropout(cfg.dropout)(att, deterministic=deterministic)

        out = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, cfg.d_model)
        return self._project(out, deterministic)

    def _project(self, out: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
        cfg = self.cfg
        # scaled init on the residual projection (GPT-2 scheme)
        proj = nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02 / np.sqrt(2 * cfg.n_layer)),
            name="proj",
        )(out)
        return nn.Dropout(cfg.dropout)(proj, deterministic=deterministic)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, deterministic: bool = True, decode: bool = False
    ) -> jnp.ndarray:
        cfg = self.cfg
        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(dtype=jnp.float32, name="ln1")(x), deterministic, decode
        )
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        h = nn.Dense(4 * cfg.d_model, dtype=cfg.dtype, name="fc")(h)
        h = nn.gelu(h)
        h = nn.Dense(
            cfg.d_model,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02 / np.sqrt(2 * cfg.n_layer)),
            name="proj",
        )(h)
        return x + nn.Dropout(cfg.dropout)(h, deterministic=deterministic)


class GPT2(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        deterministic: bool = True,
        decode: bool = False,
        pos: Optional[jnp.ndarray] = None,
        return_hidden: bool = False,
    ) -> jnp.ndarray:
        """``tokens [B, T] int32`` → logits ``[B, T, vocab] float32``.

        ``decode=True`` runs one-token autoregressive steps against a mutable
        ``'cache'`` collection; ``pos`` (int32 scalar) is the absolute
        position of the fed token (required in decode mode).
        ``return_hidden=True`` skips the LM head and returns the post-ln_f
        ``[B, T, d_model]`` hiddens — for the chunked vocab loss
        (ops/chunked_ce.py), which fuses the head matmul into the loss and
        never materializes ``[B, T, vocab]``.
        """
        cfg = self.cfg
        B, T = tokens.shape

        wte = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            embedding_init=nn.initializers.normal(0.02),
            dtype=cfg.dtype,
            name="wte",
        )
        wpe = nn.Embed(
            cfg.max_seq,
            cfg.d_model,
            embedding_init=nn.initializers.normal(0.01),
            dtype=cfg.dtype,
            name="wpe",
        )
        if decode and pos is None:
            raise ValueError("decode=True needs pos (the fed token's absolute position)")
        if pos is not None:
            positions = jnp.asarray(pos).reshape((1,))
        elif cfg.sp_axis is not None:
            # sequence-sharded: this shard covers global positions
            # [me*T_local, (me+1)*T_local)
            positions = jax.lax.axis_index(cfg.sp_axis) * T + jnp.arange(T)
        else:
            positions = jnp.arange(T)
        x = wte(tokens) + wpe(positions)[None]
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        block = Block
        if cfg.remat:
            policies = {
                "full": None,  # recompute everything
                "dots": jax.checkpoint_policies.checkpoint_dots,
                "dots_no_batch": (
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                ),
            }
            if cfg.remat_policy not in policies:
                raise ValueError(
                    f"remat_policy {cfg.remat_policy!r} not in {sorted(policies)}"
                )
            block = nn.remat(
                Block, static_argnums=(2, 3), policy=policies[cfg.remat_policy]
            )
        for i in range(cfg.n_layer):
            x = block(cfg, name=f"h{i}")(x, deterministic, decode)

        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_hidden:
            return x
        # weight-tied LM head
        with jax.named_scope("lm_head"):
            logits = x.astype(cfg.dtype) @ wte.embedding.T.astype(cfg.dtype)
            return logits.astype(jnp.float32)


def lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Next-token cross-entropy over a ``[B, T]`` batch: the mean over rows of
    ``logsumexp(row) - row[target]``.

    Written as a log-sum-exp and a masked sum against an ``iota`` so that the
    compiled step sweeps the ``[B, T-1, vocab]`` logits once forward (both
    reductions in one fusion) and once backward (``(exp(x - lse) - onehot) / N``
    written straight in the head's dtype), and keeps ``lse [B, T-1]`` between
    them.  The textbook ``take_along_axis(log_softmax(x), targets)`` is the
    same arithmetic, and on a TPU it compiles to three sweeps: the whole fp32
    log-softmax is written to HBM (2.47 GB at 12 x 1,023 x 50,257) for a gather
    to pick ``B * (T-1)`` numbers out of it, and the gather's one-hot cotangent
    is summed over the vocabulary to a constant (at Trinity's 8,191 x 25,024 it
    is scattered by a loop of 195 trips instead, 14 ms a step).  Do not fold it
    back: ``tests/test_chip_compile.py`` holds the compiled program to this
    form, PERF.md section 6 (PR 28) has the chip's numbers."""
    with jax.named_scope("loss"):
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        lse = jax.nn.logsumexp(logits, axis=-1)
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        picked = jnp.sum(jnp.where(vocab_ids == targets[..., None], logits, 0), axis=-1)
        return jnp.mean(lse - picked)


def lm_loss_chunked(
    model: "GPT2", params, tokens: jnp.ndarray, block: int = 1024
) -> jnp.ndarray:
    """:func:`lm_loss` without the ``[B, T, vocab]`` logits tensor: the model
    returns post-ln_f hiddens and the weight-tied head matmul fuses into the
    chunked online-softmax loss (ops/chunked_ce.py).  Same math — head in
    ``cfg.dtype``, fp32 softmax — at 1/(vocab/block) of the logits HBM.
    Gradients flow to ``wte`` through both its embedding use and the head.
    """
    from adapcc_tpu.ops.chunked_ce import chunked_lm_loss

    hidden = model.apply(params, tokens, return_hidden=True)
    wte = params["params"]["wte"]["embedding"]
    return chunked_lm_loss(hidden, wte, tokens, block, model.cfg.dtype)


def _sp_targets_and_mask(tokens: jnp.ndarray, axis_name: str):
    """Shared SP boundary handling: each local position's target is the next
    token — the shard's last position's target lives on the *next* rank and
    arrives by one tiny ``[B]`` ppermute (rank r receives rank r+1's first
    token, the ring modules' shared convention); the last rank's final
    position has no target and is masked out."""
    from jax import lax

    from adapcc_tpu.parallel.ring_attention import _ring_perm

    B, Tl = tokens.shape
    world = lax.psum(1, axis_name)
    me = lax.axis_index(axis_name)
    next_first = lax.ppermute(tokens[:, 0], axis_name, _ring_perm(world))  # [B]
    targets = jnp.concatenate([tokens[:, 1:], next_first[:, None]], axis=1)
    valid = jnp.ones((B, Tl), jnp.float32)
    valid = valid.at[:, -1].set(jnp.where(me == world - 1, 0.0, 1.0))
    return targets, valid


def _sp_masked_mean(nll: jnp.ndarray, valid: jnp.ndarray, axis_name: str):
    """psum-weighted global mean over valid positions — replicated, and
    numerically identical to the unsharded mean."""
    from jax import lax

    total = lax.psum(jnp.sum(nll * valid.astype(nll.dtype)), axis_name)
    count = lax.psum(jnp.sum(valid), axis_name)
    return total / count


def lm_loss_sp_chunked(
    hidden: jnp.ndarray,
    wte: jnp.ndarray,
    tokens: jnp.ndarray,
    axis_name: str,
    block: int = 1024,
    compute_dtype=None,
) -> jnp.ndarray:
    """:func:`lm_loss_sp` without the ``[B, T_local, vocab]`` logits tensor:
    the long-context × long-vocab composition.  Same boundary handling and
    psum-weighted global mean (the shared helpers); the per-position NLL
    comes from the chunked online-softmax scan (ops/chunked_ce.py).
    """
    from adapcc_tpu.ops.chunked_ce import chunked_softmax_nll

    B, Tl, D = hidden.shape
    targets, valid = _sp_targets_and_mask(tokens, axis_name)
    nll = chunked_softmax_nll(
        hidden.reshape(B * Tl, D), wte, targets.reshape(B * Tl),
        block, compute_dtype or hidden.dtype,
    ).reshape(B, Tl)
    return _sp_masked_mean(nll, valid, axis_name)


def lm_loss_sp(logits: jnp.ndarray, tokens: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """:func:`lm_loss` under sequence sharding, for use inside ``shard_map``.

    ``logits/tokens`` are this shard's ``[B, T_local, V]`` / ``[B, T_local]``
    slices of the global sequence.  Each local position's target is the next
    token — for the shard's last position that token lives on the *next*
    rank, so it arrives by one tiny ``ppermute`` ([B] int32).  The last
    rank's final position has no target and is masked out; the result is the
    psum-weighted global mean, numerically identical to ``lm_loss`` on the
    unsharded batch (and replicated across ranks).
    """
    targets, valid = _sp_targets_and_mask(tokens, axis_name)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return _sp_masked_mean(nll, valid, axis_name)
