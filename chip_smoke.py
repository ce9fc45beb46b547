#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process, on one
TPU chip, through the entry points a user would call:

1. ``AdapCC.init(entry_point=DETECT)`` → ``AdapCC.setup(ALLREDUCE)`` →
   ``AdapCC.allreduce`` (detect → profile → synthesize → execute), checked
   against the ``ones * i → i * world`` oracle;
2. ``adapcc_tpu.workloads.train_gpt2.run`` — ``DDPTrainer`` + gradient hook —
   for a few steps at GPT-2 small's published widths (12 layers, 12 heads,
   d_model 768, T=1,024, vocab 50,257, ``--attn flash``; random weights from
   a seed; the batch is what fits 16 GB beside fp32 activations), checked for
   finite, falling losses and for the flash kernel in the compiled step.

``python chip_smoke.py --chips 4`` runs the four-chip path instead, and no
other phase: the bootstrap on a 4-device mesh, one 64 MiB-per-rank payload
through every allreduce executor the DDP hook can choose — each against
``jax.lax.psum`` on the same data — one relay-masked subset allreduce, and
two ``DDPTrainer`` steps of the same GPT-2 configuration against a plain
``psum`` step.

The last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}``.  It is printed only when every phase passed on
a TPU; nothing catches a phase failure and goes on.  Without a TPU (under
``JAX_PLATFORMS=cpu``, or on a machine with none) the script exits non-zero
before any phase.  The phases are plain functions of their sizes so the
tests can run them small on the CPU pod (tests/test_chip_smoke.py); the
sizes are not options.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

#: GPT-2 small, the published widths (models/gpt2.GPT2Config.small()); depth
#: and widths are never cut here — only the batch is sized to the chip
GPT2_SMALL = dict(layers=12, heads=12, dmodel=768, seq=1024, vocab=50257)

#: per-chip batch of the train phases.  train_gpt2 builds fp32 activations:
#: the step's fp32 logits alone are batch × 1,024 × 50,257 × 4 B (206 MB a
#: row, held twice over through the loss and its gradient)
TRAIN_BATCH_PER_CHIP = 4

#: the one-chip train phase: epochs over ONE batch.  At vocab 50,257 a
#: corpus of a few thousand tokens barely repeats a token, so nothing a
#: handful of steps learns carries over to rows it has not stepped on (the
#: first chip run, two batches an epoch, showed exactly that: 10.37 on one
#: batch, 11.15 on the other a step later).  What a handful of steps can
#: show is the loss falling on the rows they step on.
TRAIN_EPOCHS = 4
TRAIN_STEPS_PER_EPOCH = 1

#: fp32 elements per rank of the four-chip payload: 64 MiB
PAYLOAD_ELEMS = 16 * 1024 * 1024


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------- #
# device + compile bookkeeping
# --------------------------------------------------------------------------- #


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it — or exit non-zero: this script has no
    CPU mode."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {device}")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX found {device}")
    return device


def compiles_since(mark: float) -> dict:
    """The backend compiles (persistent-cache reads included) that began at
    or after ``mark`` on ``time.perf_counter``: count, total seconds, the
    longest.  From the program's own watch of JAX's compile events
    (``adapcc_tpu/utils/compile_cache.py``, installed by the trainer's
    construction at the latest), so the step's compile time needs no timer
    inside the entry point; the registry keeps the newest 256 whole."""
    from adapcc_tpu.utils.compile_cache import compile_watch

    kept = compile_watch().registry.snapshot()["spans"].get("compile.backend", [])
    window = [(c["fun_name"], c["end_s"] - c["start_s"]) for c in kept if c["start_s"] >= mark]
    name, longest = max(window, key=lambda c: c[1], default=("-", 0.0))
    return {
        "programs": len(window),
        "seconds": round(sum(s for _, s in window), 2),
        "longest": name,
        "longest_seconds": round(longest, 2),
    }


def peak_hbm_bytes() -> dict:
    """``peak_bytes_in_use`` per local device, where the backend reports it."""
    import jax

    return {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    }


def assert_kernels_not_interpreted(sites) -> None:
    """Every Pallas call site on this path must have chosen Mosaic: the
    interpreter inlined where a kernel was expected is the silent fallback
    this script exists to rule out."""
    from adapcc_tpu.ops.kernel_mode import interpret_decisions

    decided = interpret_decisions()
    for site in sites:
        if site not in decided:
            raise AssertionError(f"no Pallas call was made at site {site!r}: {decided}")
        if decided[site] is not False:
            raise AssertionError(f"site {site!r} ran the Pallas interpreter: {decided}")
    say(f"kernel mode (interpret?) per site: {decided}")


def assert_sharded_over(out, mesh, what: str) -> None:
    """``world`` addressable shards on ``world`` distinct devices — code that
    has only ever seen world=1 may put everything on the first."""
    world = int(mesh.devices.size)
    shards = out.addressable_shards
    devices = {s.device for s in shards}
    if len(shards) != world or len(devices) != world:
        raise AssertionError(
            f"{what}: {len(shards)} shards on {len(devices)} devices, "
            f"expected {world} on {world}"
        )
    if devices != set(mesh.devices.flat):
        raise AssertionError(f"{what}: shards sit on {devices}, not on the mesh")


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #


def phase_bootstrap(mesh, workdir: str) -> None:
    """detect → profile → synthesize → execute through the ``AdapCC`` façade,
    against the reference's smoke oracle (``ones * i`` sums to ``i * world``
    on every rank) and, from two ranks up, a relay-masked subset."""
    import jax.numpy as jnp
    import numpy as np

    from adapcc_tpu import ALLREDUCE, DETECT, AdapCC, native
    from adapcc_tpu.config import CommArgs

    say(
        "schedule engine: "
        + ("native libadapcc_rt.so" if native.available()
           else "python (libadapcc_rt.so is not built here)")
    )
    world = int(mesh.devices.size)
    args = CommArgs(
        strategy_file=f"{workdir}/strategy.xml",
        logical_graph=f"{workdir}/logical_graph.xml",
        topology_dir=workdir,
        entry_point=DETECT,
        parallel_degree=2,
    )
    AdapCC.init(args, mesh=mesh)
    AdapCC.setup(ALLREDUCE)
    for i in (1, 2, 3):
        x = jnp.stack([jnp.ones(16) * i for _ in range(world)])
        out = np.asarray(AdapCC.allreduce(x, size=16, chunk_bytes=8))
        np.testing.assert_array_equal(out, np.full((world, 16), float(i * world)))
    say(f"AdapCC bootstrap + allreduce oracle: world {world}, ones*i -> i*{world} on every rank")
    if world >= 2:
        # the last rank straggles: it relays, the active ranks still sum
        x = jnp.stack([jnp.ones(16) * (r + 1) for r in range(world)])
        active = list(range(world - 1))
        out = np.asarray(AdapCC.allreduce(x, active_gpus=active))
        np.testing.assert_array_equal(
            out, np.full((world, 16), float(sum(r + 1 for r in active)))
        )
        say(f"AdapCC subset allreduce over active {active}: {int(out[0][0])} on every rank")
    AdapCC.clear(ALLREDUCE)


def train_args(
    world: int, batch_per_chip: int, epochs: int, steps: int, widths: dict
) -> argparse.Namespace:
    """The ``train_gpt2`` command line for ``epochs`` epochs of ``steps``
    steps: every flag at its parser default except the widths, ``--attn
    flash``, the batch, one warm-up step, and a corpus of exactly ``steps``
    training batches (the entry point holds out ``max(16, 10%)`` of the rows
    for validation), so every epoch steps on the same rows."""
    from adapcc_tpu.workloads import train_gpt2

    batch = batch_per_chip * world
    rows = steps * batch + 16
    while rows - max(16, rows // 10) < steps * batch:
        rows += 1
    return train_gpt2.build_parser().parse_args([
        "--epochs", str(epochs),
        "--batch", str(batch),
        "--world", str(world),
        "--layers", str(widths["layers"]),
        "--heads", str(widths["heads"]),
        "--dmodel", str(widths["dmodel"]),
        "--seq", str(widths["seq"]),
        "--vocab", str(widths["vocab"]),
        "--corpus-tokens", str(rows * widths["seq"]),
        "--warmup-steps", "1",
        "--attn", "flash",
    ])


def phase_train(
    world: int, batch_per_chip: int, epochs: int, steps: int, widths: dict
) -> dict:
    """A few ``train_gpt2`` steps through the normal entry point; returns
    what the run showed (losses, the step's compile seconds, the number of
    Pallas custom calls in the compiled step)."""
    import numpy as np

    from adapcc_tpu.workloads import train_gpt2

    args = train_args(world, batch_per_chip, epochs, steps, widths)
    say(
        f"train_gpt2: {widths['layers']}L/{widths['heads']}H/{widths['dmodel']}d "
        f"T={widths['seq']} vocab={widths['vocab']} attn=flash fp32, "
        f"batch {args.batch} ({batch_per_chip}/chip), world {world}"
    )
    report: dict = {}
    t0 = time.perf_counter()
    ppl0, ppl1 = train_gpt2.run(args, report)
    seconds = time.perf_counter() - t0
    losses = report["step_losses"]
    say(f"train_gpt2: {len(losses)} steps in {seconds:.1f}s (compiles included), losses {losses}")
    say(f"train_gpt2: val ppl {ppl0:.1f} -> {ppl1:.1f}")
    say(f"train_gpt2: compiles {compiles_since(t0)}")
    if len(losses) != epochs * steps:
        raise AssertionError(f"expected {epochs * steps} steps, the entry point took {len(losses)}")
    if not (np.all(np.isfinite(losses)) and np.isfinite(ppl0) and np.isfinite(ppl1)):
        raise AssertionError(f"a loss is not finite: {losses}, ppl {ppl0} -> {ppl1}")
    # every epoch steps on the same rows, so epoch means compare like with like
    epoch_means = np.asarray(losses).reshape(epochs, steps).mean(axis=1)
    say(f"train_gpt2: epoch mean losses {epoch_means.tolist()}")
    if not (epoch_means[-1] < epoch_means[0] and losses[-1] < losses[0]):
        raise AssertionError(f"losses do not fall: {losses}")

    # the compiled step itself: ask it, do not infer from the platform
    trainer, state, batch = report["trainer"], report["state"], report["batch"]
    compiled = trainer._compiled.lower(state, batch).compile()
    custom_calls = compiled.as_text().count("tpu_custom_call")
    say(f"train step: {custom_calls} tpu_custom_call mentions in the compiled program")
    return {
        "losses": losses,
        "ppl": (ppl0, ppl1),
        "custom_calls": custom_calls,
        "compile": compiles_since(t0),
    }


def phase_executors(mesh, nelems: int) -> dict:
    """One payload through every allreduce executor the DDP hook can choose,
    each against ``jax.lax.psum`` on the same data: exact on an integer-
    valued fp32 payload, within the codec's stated bound under a wire codec.
    Returns executor → the impl the dispatch trace recorded, plus the
    Pallas-ring dispatches whose trace says they ran the interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adapcc_tpu.comm.engine import CollectiveEngine
    from adapcc_tpu.comm.mesh import RANKS_AXIS
    from adapcc_tpu.quant import ring_error_bound
    from adapcc_tpu.strategy.ir import Strategy
    from adapcc_tpu.utils.observability import CollectiveTrace

    world = int(mesh.devices.size)
    sharded = NamedSharding(mesh, P(RANKS_AXIS))
    replicated = NamedSharding(mesh, P())
    rng = np.random.default_rng(0)
    ints_host = rng.integers(-8, 9, size=(world, nelems)).astype(np.float32)
    reals_host = rng.standard_normal(size=(world, nelems)).astype(np.float32)
    ints = jax.device_put(ints_host, sharded)
    reals = jax.device_put(reals_host, sharded)

    # the reference: jax.lax.psum over the ranks in `active` (a 0/1 mask row)
    masked_psum = jax.jit(jax.shard_map(
        lambda x, m: jax.lax.psum(x * m, RANKS_AXIS),
        mesh=mesh, in_specs=(P(RANKS_AXIS), P(RANKS_AXIS)),
        out_specs=P(RANKS_AXIS), check_vma=False,
    ))

    def psum_over(x, active):
        mask = np.zeros((world, 1), np.float32)
        mask[list(active)] = 1.0
        return masked_psum(x, jax.device_put(mask, sharded))

    ref_ints, ref_reals = psum_over(ints, range(world)), psum_over(reals, range(world))

    mass = np.abs(reals_host).sum(axis=0)
    bounds = {
        # every one of the <= world encodes rounds a partial no larger than
        # sum_r |x_r| to bf16's 8 significant bits
        "bf16": jax.device_put(world * 2.0**-8 * mass + 1e-6, replicated),
        "int8": jax.device_put(ring_error_bound(reals_host), replicated),
    }
    del ints_host, reals_host, mass

    trace = CollectiveTrace()
    strategy = Strategy.ring(world)
    engine = CollectiveEngine(mesh, strategy, trace=trace)
    # the strategy-shaped masked-ppermute schedule: what "ring" means once
    # the XLA fast path is off (a coordinator-driven hook runs it masked)
    scheduled = CollectiveEngine(mesh, strategy, use_xla_fastpath=False, trace=trace)

    @jax.jit
    def worst(out, ref, bound):
        err = jnp.abs(out - ref)
        return jnp.max(err), jnp.all(err <= bound[None, :])

    ran: dict = {}

    def check(name, out, ref, bound=None, impl_prefix=""):
        assert_sharded_over(out, mesh, name)
        if out.shape != ref.shape or out.dtype != ref.dtype:
            raise AssertionError(f"{name}: {out.shape} {out.dtype}, expected {ref.shape} {ref.dtype}")
        if bound is None:
            bound = jnp.zeros((out.shape[1],), jnp.float32)  # exact
        err, ok = worst(out, ref, bound)
        impl = trace.events()[-1].impl
        ran[name] = impl
        if not impl.startswith(impl_prefix):
            raise AssertionError(f"{name}: dispatched {impl!r}, expected {impl_prefix}*")
        say(f"  {name:<16} impl {impl:<28} max |out - psum| = {float(err):.3g}")
        if not bool(ok):
            raise AssertionError(f"{name} ({impl}) disagrees with psum: max error {float(err)}")

    say(f"allreduce executors vs psum, {nelems * 4 / 2**20:.0f} MiB fp32 per rank, world {world}:")
    check("xla", engine.all_reduce(ints), ref_ints)
    check("ring", scheduled.all_reduce(ints, algo="ring"), ref_ints)
    for algo in ("tree", "rd", "ir"):
        check(algo, engine.all_reduce(ints, algo=algo), ref_ints)
    check(
        "pallas_ring", engine.ring_allreduce(ints, wire_dtype="off"), ref_ints,
        impl_prefix="pallas_ring[",
    )
    for wire in ("bf16", "int8"):
        check(
            f"pallas_ring+{wire}",
            engine.ring_allreduce(reals, wire_dtype=wire), ref_reals, bounds[wire],
            impl_prefix="pallas_ring[",
        )
    active = list(range(world - 1))
    check(f"subset{active}", engine.all_reduce(ints, active_gpus=active), psum_over(ints, active))
    # what the dispatch trace says each Pallas ring ran as (Mosaic: False)
    ran["interpreted"] = sorted(
        e.impl for e in trace.events()
        if e.impl.startswith("pallas_ring") and e.extra["interpret"] is not False
    )
    return ran


def phase_ddp_vs_psum(mesh, batch_per_chip: int, steps: int, widths: dict) -> dict:
    """``steps`` ``DDPTrainer`` steps — built as ``train_gpt2`` builds them —
    against the same steps of a plain ``psum`` data-parallel program, from
    the same seed on the same tokens: the losses must match."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adapcc_tpu.comm.mesh import RANKS_AXIS
    from adapcc_tpu.ddp import DDPTrainer, TrainState
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from adapcc_tpu.strategy.ir import Strategy

    world = int(mesh.devices.size)
    cfg = GPT2Config(
        vocab_size=widths["vocab"], max_seq=widths["seq"], n_layer=widths["layers"],
        n_head=widths["heads"], d_model=widths["dmodel"], dtype=jnp.float32,
        attention="flash",
    )
    model = GPT2(cfg)
    host_tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch_per_chip * world, cfg.max_seq)
    ).astype(np.int32)
    tokens = jax.device_put(host_tokens, NamedSharding(mesh, P(RANKS_AXIS)))
    # init on one device from a host row, as train_gpt2 does: a flash kernel
    # outside shard_map cannot take a mesh-sharded operand (Mosaic kernels
    # are not auto-partitioned — the first four-chip run died right here)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(host_tokens[:1]))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(3e-4, weight_decay=0.01))

    def loss_fn(p, b):
        return lm_loss(model.apply(p, b), b)

    trainer = DDPTrainer(loss_fn, tx, mesh, Strategy.ring(world))
    state = trainer.init_state(params)
    ddp_losses = []
    for _ in range(steps):
        state, loss = trainer.step(state, tokens)
        assert_sharded_over(loss, mesh, "DDPTrainer per-rank losses")
        ddp_losses.append(float(jnp.mean(loss)))

    def plain_step(st, b):
        loss, grads = jax.value_and_grad(loss_fn)(st.params, b)
        grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, RANKS_AXIS), grads)
        updates, opt_state = tx.update(grads, st.opt_state, st.params)
        return (
            TrainState(optax.apply_updates(st.params, updates), opt_state, st.step + 1),
            loss[None],
        )

    plain = jax.jit(jax.shard_map(
        plain_step, mesh=mesh, in_specs=(P(), P(RANKS_AXIS)),
        out_specs=(P(), P(RANKS_AXIS)), check_vma=False,
    ))
    ref = TrainState.create(params, tx)
    ref_losses = []
    for _ in range(steps):
        ref, loss = plain(ref, tokens)
        ref_losses.append(float(jnp.mean(loss)))

    say(f"DDPTrainer losses {ddp_losses}")
    say(f"plain psum losses {ref_losses}")
    if not np.all(np.isfinite(ddp_losses)):
        raise AssertionError(f"a DDPTrainer loss is not finite: {ddp_losses}")
    np.testing.assert_allclose(ddp_losses, ref_losses, rtol=1e-3)
    return {"ddp": ddp_losses, "psum": ref_losses}


# --------------------------------------------------------------------------- #
# the two runs
# --------------------------------------------------------------------------- #


def run_one_chip() -> None:
    from adapcc_tpu.comm.mesh import build_world_mesh

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="adapcc_smoke_") as workdir:
        phase_bootstrap(build_world_mesh(1), workdir)
    say(f"phase bootstrap: {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    train = phase_train(
        1, TRAIN_BATCH_PER_CHIP, TRAIN_EPOCHS, TRAIN_STEPS_PER_EPOCH, GPT2_SMALL
    )
    say(f"phase train: {time.perf_counter() - t0:.1f}s")
    if train["custom_calls"] <= 0:
        raise AssertionError("the compiled train step holds no flash kernel (no tpu_custom_call)")
    assert_kernels_not_interpreted(["flash_attention"])


def run_four_chips(chips: int) -> None:
    from adapcc_tpu.comm.mesh import build_world_mesh

    mesh = build_world_mesh(chips)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="adapcc_smoke_") as workdir:
        phase_bootstrap(mesh, workdir)
    say(f"phase bootstrap: {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    ran = phase_executors(mesh, PAYLOAD_ELEMS)
    say(f"phase executors: {time.perf_counter() - t0:.1f}s")
    if ran["interpreted"]:
        raise AssertionError(f"ran the Pallas interpreter on the chip: {ran['interpreted']}")

    t0 = time.perf_counter()
    phase_ddp_vs_psum(mesh, TRAIN_BATCH_PER_CHIP, 2, GPT2_SMALL)
    say(f"phase ddp_vs_psum: {time.perf_counter() - t0:.1f}s")
    assert_kernels_not_interpreted(["flash_attention", "ring_allreduce"])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs the four-chip path (collectives + DDP vs psum) and no other phase",
    )
    chips = parser.parse_args(argv).chips

    t_start = time.perf_counter()
    import jax
    import jaxlib

    from adapcc_tpu.utils.compile_cache import compile_watch, enable_compile_cache

    cache_dir = enable_compile_cache()
    registry = compile_watch().registry  # from here on, not from the first trainer's construction
    device = require_tpu(chips)
    say(f"device {device}; jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {_libtpu_version()}")
    say(f"compile cache: {cache_dir}")

    if chips == 1:
        run_one_chip()
    else:
        run_four_chips(chips)

    snap = registry.snapshot()
    backend, counters = snap["timings"]["compile.backend"], snap["counters"]
    say(f"all compiles: {backend['count']} programs, {backend['total_s']:.2f} s, the longest "
        f"{backend['max_s']:.2f} s; persistent cache hits {counters.get('compile.cache_hits', 0):.0f}, "
        f"misses {counters.get('compile.cache_misses', 0):.0f}")
    say(f"peak HBM bytes in use per device: {peak_hbm_bytes()}")
    say(f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _libtpu_version() -> str:
    from importlib import metadata

    for dist in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            continue
    return "not installed"


if __name__ == "__main__":
    raise SystemExit(main())
