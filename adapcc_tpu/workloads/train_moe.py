"""Expert-parallel MoE workload (reference models/moe/train_moe.py).

The reference wraps fastmoe's ``FMoETransformerMLP`` in DDP and times an
*inference* loop — the all-to-all is fastmoe/NCCL's, not AdapCC's
(SURVEY §2.3: the ALLTOALL context is a stub there).  Here the all-to-all
IS the framework's (parallel/expert.py over the ``experts`` mesh axis), and
on top of the reference's timed inference mode this also *trains*: gradients
flow through the dispatch/combine all-to-alls (expert weights sharded, the
router replicated with its gradient summed by the shard_map transpose), with
the load-balancing auxiliary loss in the objective.

Usage::

    python -m adapcc_tpu.workloads.train_moe --steps 30            # train
    python -m adapcc_tpu.workloads.train_moe --mode inference      # ref loop
"""

from __future__ import annotations

import argparse
import functools
import sys
import time
from typing import Optional, Sequence, Tuple

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("train", "inference"), default="train")
    p.add_argument("--experts", type=int, default=8)
    p.add_argument("--dmodel", type=int, default=64)
    p.add_argument("--dhidden", type=int, default=128)
    p.add_argument("--top-k", type=int, default=2)
    p.add_argument("--batch", type=int, default=256, help="tokens per step")
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--aux-weight", type=float, default=0.01)
    p.add_argument("--world", type=int, default=None)
    p.add_argument(
        "--tune-every", type=int, default=10,
        help="steps between all-to-all tuner probes when a tuner is active "
        "(ADAPCC_TUNER=record|choose): the engine times real all_to_all "
        "dispatches at the MoE exchange geometry into the tuning database "
        "(the in-jit dispatch/combine shuffles cannot be walltimed "
        "individually)",
    )
    return p


def _cluster_data(n: int, d: int, classes: int, seed: int = 0):
    """Gaussian clusters: learnable by an expert MLP, and the clusters give
    the router something real to specialize on."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, d)) * 2.0
    labels = rng.integers(0, classes, size=(n,))
    x = centers[labels] + rng.normal(size=(n, d)) * 0.5
    return x.astype(np.float32), labels.astype(np.int32)


def run(args) -> Tuple[float, float]:
    """Train (or time inference); returns (first_loss, last_loss) — in
    inference mode both are the mean step milliseconds."""
    from adapcc_tpu.launch import maybe_initialize_distributed

    maybe_initialize_distributed()

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from adapcc_tpu.models.moe import MoEConfig, MoEMLP
    from adapcc_tpu.parallel import expert_parallel_moe

    world = args.world or len(jax.devices())
    if len(jax.devices()) < world:
        raise ValueError(f"need {world} devices, have {len(jax.devices())}")
    if args.batch % world:
        raise ValueError(f"--batch {args.batch} must divide by world {world}")
    mesh = Mesh(np.array(jax.devices()[:world]), ("experts",))

    cfg = MoEConfig(
        num_experts=args.experts, d_model=args.dmodel, d_hidden=args.dhidden,
        top_k=args.top_k, capacity_factor=2.0, dtype=jnp.float32,
        router_z_coef=0.1,
    )
    model = MoEMLP(cfg)
    x_np, y_np = _cluster_data(args.batch, cfg.d_model, args.classes)
    x, y = jnp.asarray(x_np), jnp.asarray(y_np)

    # expert traffic rides the engine when a tuner is active: the MoE
    # dispatch/combine all-to-alls route through engine.expert_a2a (traced
    # per compiled program) and periodic engine.all_to_all probes at the
    # SAME payload geometry feed the tuning database under the
    # `all_to_all` primitive (docs/LATENCY.md §5)
    from adapcc_tpu.tuner import tuner_mode

    engine = None
    a2a_probe = None
    if tuner_mode() != "off":
        from adapcc_tpu.comm.engine import CollectiveEngine
        from adapcc_tpu.strategy.ir import Strategy
        from adapcc_tpu.utils import CollectiveTrace

        from adapcc_tpu.parallel.expert import moe_capacity

        engine = CollectiveEngine(
            mesh, Strategy.ring(world), axis_name="experts",
            trace=CollectiveTrace(),
        )
        e_loc = cfg.num_experts // world
        cap = moe_capacity(cfg, args.batch // world)
        probe = jnp.zeros(
            (world, world, e_loc * cap * cfg.d_model), jnp.float32
        )

        def a2a_probe():
            engine.all_to_all(probe)

    import flax.linen as nn

    class Readout(nn.Module):
        classes: int

        @nn.compact
        def __call__(self, h):
            return nn.Dense(self.classes, name="out")(h)

    readout = Readout(args.classes)
    moe_params = model.init(jax.random.PRNGKey(0), x[None])
    head_params = readout.init(jax.random.PRNGKey(1), x)

    if args.mode == "inference":
        fwd = jax.jit(
            lambda p, x: expert_parallel_moe(p, x, cfg, mesh, engine=engine)[0]
        )
        jax.block_until_ready(fwd(moe_params, x))  # compile
        times = []
        for i in range(args.steps):
            if a2a_probe is not None and i % max(1, args.tune_every) == 0:
                a2a_probe()
            t0 = time.perf_counter()
            jax.block_until_ready(fwd(moe_params, x))
            times.append(time.perf_counter() - t0)
        ms = float(np.mean(times) * 1e3)
        _report_tuner(engine)
        # reference prints per-iteration computation time (train_moe.py)
        print(f"computation time: {ms:.3f} ms/step ({args.batch} tokens, world={world})")
        return ms, ms

    def loss_fn(params, x, y):
        h, aux = expert_parallel_moe(params["moe"], x, cfg, mesh, engine=engine)
        logits = readout.apply(params["head"], h.astype(jnp.float32))
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return ce + args.aux_weight * aux, (ce, aux)

    tx = optax.adam(args.lr)
    params = {"moe": moe_params, "head": head_params}
    opt_state = tx.init(params)

    # donate the loop-owned state: in-place updates, no second copy of the
    # params + optimizer state per step; x/y are static and never donated
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, ce, aux

    first = last = None
    for i in range(args.steps):
        if a2a_probe is not None and i % max(1, args.tune_every) == 0:
            a2a_probe()
        params, opt_state, loss, ce, aux = step(params, opt_state, x, y)
        if i == 0 or i == args.steps - 1 or (i + 1) % 10 == 0:
            loss_v = float(loss)
            print(f"step {i:4d}  loss {loss_v:.4f}  ce {float(ce):.4f}  aux {float(aux):.4f}")
            if first is None:
                first = loss_v
            last = loss_v
    _report_tuner(engine)
    return first, last


def _report_tuner(engine) -> None:
    """One summary line per tuned all_to_all cell — the run's evidence that
    expert traffic landed in the tuning database."""
    if engine is None or engine.tuner is None:
        return
    rows = [
        r for r in engine.tuner.db.snapshot() if r["primitive"] == "all_to_all"
    ]
    for r in rows:
        print(
            f"[tuner] all_to_all bucket={r['size_bucket']}B path={r['path']} "
            f"n={r['count']} median={r['median_s'] * 1e6:.1f}us"
        )


def main(argv: Optional[Sequence[str]] = None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    from adapcc_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
