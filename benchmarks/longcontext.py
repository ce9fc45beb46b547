"""Long-context SP attention sweep: ring vs Ulysses vs single-device.

The sequence-parallel story in numbers (the reference has no SP at all,
SURVEY §5.7): per (scheme, T) this measures one forward+backward of the
attention program with the sequence sharded over the world axis, reporting
ms/call and the peak per-device *score memory* the dense single-device path
would need (``[B, H, T, T]`` fp32) versus what the SP schemes actually
materialize — the reason long context needs SP even before speed does.

Schemes:

* ``single``     — dense attention on one device (the memory wall baseline)
* ``ring``       — K/V blocks rotate over the axis; ``[Tl, Tl]`` scores
* ``ring-flash`` — ring with the Pallas blockwise kernel; O(Tl) memory
* ``ulysses``    — all-to-all head exchange; full-T scores on H/world heads

Usage::

    python -m benchmarks.longcontext --world 4 --seqs 1K,4K --heads 4 --json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from benchmarks.collectives import _time_op, parse_size


@dataclasses.dataclass
class LCResult:
    scheme: str
    seq: int
    world: int
    heads: int
    head_dim: int
    fwd_bwd_ms: float
    #: fp32 bytes of attention scores materialized per device at once
    score_bytes_per_device: int

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def _score_bytes(scheme: str, B: int, H: int, T: int, world: int, block: int) -> int:
    if scheme == "single":
        return 4 * B * H * T * T
    Tl = T // world
    if scheme == "ring":
        return 4 * B * H * Tl * Tl
    if scheme == "ring-flash":
        bq = min(block, Tl)
        return 4 * B * H * bq * bq  # one [bq, bq] tile in VMEM per head
    if scheme == "ulysses":
        return 4 * B * (H // world) * T * T
    raise ValueError(scheme)


def run_sweep(
    world: int,
    seqs: Sequence[int],
    heads: int = 4,
    head_dim: int = 32,
    batch: int = 1,
    iters: int = 3,
    warmup: int = 1,
    schemes: Optional[Sequence[str]] = None,
    block: int = 128,
):
    from adapcc_tpu.parallel import ring_attention, ulysses_attention
    from adapcc_tpu.parallel.ring_attention import reference_attention

    known = ("single", "ring", "ring-flash", "ulysses")
    if schemes:
        unknown = [s for s in schemes if s not in known]
        if unknown:
            raise ValueError(f"unknown schemes {unknown}; choose from {known}")
    if len(jax.devices()) < world:
        raise ValueError(f"need {world} devices, have {len(jax.devices())}")
    mesh = Mesh(np.array(jax.devices()[:world]), ("ranks",))
    results = []
    for T in seqs:
        if T % world:
            raise ValueError(f"seq {T} must divide by world {world}")
        rng = np.random.default_rng(T)
        q, k, v = (
            jnp.asarray(rng.normal(size=(batch, T, heads, head_dim)) * 0.5, jnp.float32)
            for _ in range(3)
        )

        progs = {
            "single": lambda q, k, v: reference_attention(q, k, v),
            "ring": lambda q, k, v: ring_attention(mesh, q, k, v, block_impl="dense"),
            "ring-flash": lambda q, k, v: ring_attention(
                mesh, q, k, v, block_impl="flash", block_q=block, block_k=block
            ),
            "ulysses": lambda q, k, v: ulysses_attention(mesh, q, k, v),
        }
        for scheme, prog in progs.items():
            if schemes and scheme not in schemes:
                continue

            def loss(q, k, v, prog=prog):
                return jnp.sum(prog(q, k, v).astype(jnp.float32) ** 2)

            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            sec = _time_op(lambda: step(q, k, v), iters, warmup)
            results.append(
                LCResult(
                    scheme=scheme,
                    seq=T,
                    world=world,
                    heads=heads,
                    head_dim=head_dim,
                    fwd_bwd_ms=round(sec * 1e3, 2),
                    score_bytes_per_device=_score_bytes(
                        scheme, batch, heads, T, world, block
                    ),
                )
            )
    return results


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--world", type=int, default=0)
    ap.add_argument("--seqs", default="1K,4K")
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--schemes", default="", help="comma subset")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    world = args.world or len(jax.devices())
    if not args.json:
        print(f"# world={world} platform={jax.devices()[0].platform}")
        print(f"{'scheme':<12}{'seq':>8}{'fwd+bwd(ms)':>14}{'score-bytes/dev':>18}")
    # one run_sweep per seq, rows flushed as they land: an OOM at a later
    # sequence length (the dense path's expected fate at 8K+) must not eat
    # the measurements already taken at the shorter ones
    for seq in (parse_size(s) for s in args.seqs.split(",") if s):
        results = run_sweep(
            world,
            [seq],
            heads=args.heads,
            head_dim=args.head_dim,
            batch=args.batch,
            iters=args.iters,
            schemes=[s for s in args.schemes.split(",") if s] or None,
        )
        for r in results:
            if args.json:
                print(r.to_json(), flush=True)
            else:
                print(
                    f"{r.scheme:<12}{r.seq:>8}{r.fwd_bwd_ms:>14.1f}"
                    f"{r.score_bytes_per_device:>18,}",
                    flush=True,
                )


if __name__ == "__main__":
    main()
