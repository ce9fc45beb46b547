"""The training runner: ``adapcc_tpu``'s GPT-2 under ``DDPTrainer.step``,
built the way ``adapcc_tpu/workloads/train_gpt2.run`` builds it, fed by
``adapcc_tpu.data.device_batches``, on as many chips as the cell asks for.

The runner passes to the program only what the configuration file states
(the fields of ``GPT2Config`` it names, the optimizer recipe); everything
else is the program's default, so a later PR that changes a default shows
in the cell with no change here.

Set-up builds ONE trainer with ONE state, drives it from the seed through
its first three steps by the window's own call and feed, and hands that same
object to the window.  After the window the program's state is freed and the
plain reference follows those three steps on the same rows (chipbench/
correct.py); its time is not in ``setup_s``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import time
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correct, trace_reduce, weights
from chipbench.reference import gpt2_ref
from chipbench.traffic import generator

#: the reference follows this many first steps
CHECK_STEPS = 3
#: steps driven after the checked ones and before the window, so that the
#: window starts on a device that has settled
SETTLE_STEPS = 2
#: a traced run measures this long at most: traces are large
TRACE_SECONDS = 4.0
SPAN_PREFIX = "chipbench."


class CompileLog:
    """Backend compiles (persistent-cache reads included), from JAX's own
    monitoring events; copied from ``chip_smoke.py``."""

    def __init__(self) -> None:
        self.compiles: List[Any] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((str(kwargs.get("fun_name", "?")), float(seconds)))

    def mark(self) -> int:
        return len(self.compiles)


class Spans:
    """Host spans around the calls into each layer, kept in memory.  In a
    traced run each is also a ``TraceAnnotation``, so that it lands on the
    profiler's clock beside the device's operations; in an untraced run
    ``span`` does nothing."""

    def __init__(self, on: bool) -> None:
        self.on = on
        self.seconds: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.seconds[name].append(time.perf_counter() - t)


def epochs_of_batches(rows: np.ndarray, global_batch: int, mesh, seed: int, prefetch: int) -> Iterator[Any]:
    """``device_batches`` cycled by epochs with a reshuffle, as the entry
    point's loop does (one pass is one epoch; reseed for the next)."""
    from adapcc_tpu.data import device_batches

    epoch = 0
    while True:
        yield from device_batches(rows, global_batch, mesh=mesh, seed=seed + epoch, prefetch=prefetch)
        epoch += 1


def build(config: Dict[str, Any], world: int):
    """The program under test, as ``train_gpt2.run`` puts it together."""
    import optax

    from adapcc_tpu.comm.mesh import build_world_mesh
    from adapcc_tpu.ddp import DDPTrainer
    from adapcc_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from adapcc_tpu.strategy.ir import Strategy

    stated = {f.name for f in dataclasses.fields(GPT2Config)} & set(config)
    model = GPT2(GPT2Config(**{k: config[k] for k in sorted(stated)}))
    opt = config["assumed"]["optimizer"]
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_norm"]),
        optax.adamw(
            opt["learning_rate"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"],
        ),
    )

    def loss_fn(p, b):
        return lm_loss(model.apply(p, b), b)

    mesh = build_world_mesh(world)
    return DDPTrainer(loss_fn, tx, mesh, Strategy.ring(world)), mesh


def fresh_state(trainer, mesh, config: Dict[str, Any], seed: int):
    """The trainer's state on weights made from the seed, every chip making
    its own copy."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return trainer.init_state(weights.make_params(seed, config, NamedSharding(mesh, P())))


def _first_moment(opt_state):
    found = [
        s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's, found {len(found)}")
    return found[0].mu


def drive_first_steps(trainer, state, batches, config, seed: int):
    """The checked steps, through ``trainer.step`` on ``next(batches)``.
    Returns the state after them, the rows they stepped on, and the
    program's side of the comparison."""

    b1 = config["assumed"]["optimizer"]["b1"]
    rows, losses, grad_norms = [], [], None
    for i in range(CHECK_STEPS):
        batch = next(batches)
        rows.append(np.asarray(batch))
        state, loss = trainer.step(state, batch)
        losses.append(float(jnp.mean(loss)))
        if i == 0:
            # Adam's first moment after one step is (1 - b1) x the gradient
            # the optimizer was given
            grad_norms = np.asarray(
                jax.jit(gpt2_ref.leaf_norms)(_first_moment(state.opt_state))
            ) / (1.0 - b1)
    init = weights.params_like(config)
    moved = jax.jit(
        lambda p, key: gpt2_ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, init(key)))
    )(state.params, weights.seed_key(seed))
    program = {"losses": losses, "grad_norms": grad_norms, "update_norms": np.asarray(moved)}
    return state, np.stack(rows), program


def reference_fn(config, rows_per_step: int, precision: str = "float32"):
    """The reference's three steps as one jitted program (build it once
    where several seeds go through it)."""

    cfg = dict(config, layer_norm_epsilon=config["assumed"]["layer_norm_epsilon"])
    opt = {k: float(v) for k, v in config["assumed"]["optimizer"].items()}
    block = min(gpt2_ref.ROW_BLOCK, rows_per_step)
    return jax.jit(lambda p, b: gpt2_ref.train_steps(p, b, cfg, opt, precision, block))


def reference_numbers(config, rows: np.ndarray, seed: int, fn=None):
    """The reference's side, on one device, from weights made anew by the
    seed."""

    fn = fn or reference_fn(config, rows.shape[1])
    out = fn(weights.make_params(seed, config), jnp.asarray(rows))
    return {k: np.asarray(v) for k, v in out.items()}


def replicas_differ(params, mesh) -> bool:
    """Whether any parameter differs between the chips' copies."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def per_chip(p):
        flags = [
            jnp.any(jax.lax.pmax(x, axis) != jax.lax.pmin(x, axis))
            for x in jax.tree_util.tree_leaves(p)
        ]
        return jnp.any(jnp.stack(flags))

    fn = jax.jit(jax.shard_map(per_chip, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    return bool(fn(params))


def peak_bytes(device) -> int:
    """A chip's peak: the allocator's ``peak_bytes_in_use`` plus, where the
    backend reports it, ``peak_bytes_reserved``: the TPU runtime reserves the
    loaded program's temporaries at the bottom of memory and does not count
    them as in use (a step with 12.2 GB of temporaries read 4.7 GB in use)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def measure(trainer, state, batches, seconds: float, spans: Spans, clock=time.perf_counter):
    """The window.  Completion of step *i* is observed by blocking on its
    loss after step *i+1* has been dispatched: one step is always in flight
    and the device never waits for the host's clock."""
    done: List[float] = []
    losses: List[Any] = []
    pending = None
    start = clock()
    deadline = start + seconds
    while True:
        with spans.span("input_wait"):
            batch = next(batches)
        with spans.span("step_dispatch"):
            state, loss = trainer.step(state, batch)
        if pending is not None:
            with spans.span("await_step"):
                pending.block_until_ready()
            done.append(clock())
        losses.append(loss)
        pending = loss
        if clock() >= deadline:
            break
    pending.block_until_ready()
    done.append(clock())
    return state, {"start": start, "done": done, "losses": losses}


def step_samples_ms(start: float, done: List[float], group: int) -> List[float]:
    """Time per step in ms, each sample the mean over ``group`` consecutive
    completions (a host clock is off by half a millisecond, so a sample
    spans a quarter of a second or more; the mix says how many steps that
    takes)."""
    marks = [start] + list(done)
    # the first interval holds the first step's dispatch on an idle device
    marks = marks[1:]
    return [
        (marks[i + group] - marks[i]) * 1e3 / group
        for i in range(0, len(marks) - group, group)
    ]


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(spec) -> Dict[str, Any]:
    config, mix, say = spec.config, spec.mix, spec.say
    world = int(spec.cell["chips"])
    seq_len, per_chip = int(mix["seq_len"]), int(mix["batch_per_chip"])
    global_batch = per_chip * world
    compiles = CompileLog()
    spans = Spans(on=spec.trace)

    def stamp(what: str) -> None:
        say(f"set-up: {what} at {time.perf_counter() - spec.t0:.1f} s")

    stamp("imports and device")
    rows = generator.make_rows(mix, config["vocab_size"], spec.seed)
    stamp("corpus")
    trainer, mesh = build(config, world)
    state = fresh_state(trainer, mesh, config, spec.seed)
    # the step's program reserves its temporaries when it is loaded, and
    # cannot while the programs that make the state still hold theirs
    jax.block_until_ready(state)
    stamp("trainer and state")
    batches = epochs_of_batches(rows, global_batch, mesh, spec.seed, int(mix.get("prefetch", 2)))
    try:
        state, checked_rows, program = drive_first_steps(trainer, state, batches, config, spec.seed)
        stamp(f"first {CHECK_STEPS} steps and the program's side of the check")
        for _ in range(SETTLE_STEPS):
            state, loss = trainer.step(state, next(batches))
        loss.block_until_ready()
        if spec.require_chip and config.get("attention") == "flash":
            from adapcc_tpu.ops.kernel_mode import interpret_decisions

            if interpret_decisions().get("flash_attention") is not False:
                raise SystemExit(
                    f"chipbench: the flash kernel did not run through Mosaic: {interpret_decisions()}"
                )

        seconds = min(spec.seconds, TRACE_SECONDS) if spec.trace else spec.seconds
        trace_dir = spec.out_dir / "trace"
        if spec.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the spans are TraceAnnotations; Python frames only slow the host
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        mark = compiles.mark()
        setup_s = time.perf_counter() - spec.t0
        state, win = measure(trainer, state, batches, seconds, spans)
        window_compiles = compiles.mark() - mark
        if spec.trace:
            jax.profiler.stop_trace()
    finally:
        batches.close()

    steps = len(win["done"])
    window_s = win["done"][-1] - win["start"]
    tokens_per_s = steps * global_batch * seq_len / window_s
    samples = step_samples_ms(win["start"], win["done"], int(mix.get("steps_per_sample", 1)))
    losses = np.asarray(jax.device_get([jnp.mean(x) for x in win["losses"]]))
    failed = int(np.sum(~np.isfinite(losses)))
    say(f"window: {steps} steps in {window_s:.3f} s, {len(samples)} step-time samples, "
        f"median {percentile(samples, 50):.3f} ms, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    differ = replicas_differ(state.params, mesh) if world > 1 else False
    peak = max(peak_bytes(d) for d in mesh.devices.flat)
    say(f"memory: {mesh.devices.flat[0].memory_stats()}")

    # free the program's state, then the reference on the checked rows
    del state, trainer, batches
    gc.collect()
    t_ref = time.perf_counter()
    reference = reference_numbers(config, checked_rows, spec.seed)
    rows_cmp = correct.compare(program, reference, config["limits"])
    correct.show(rows_cmp, say)
    say(f"correct: losses program {program['losses']} reference {reference['losses'].tolist()}")
    say(f"correct: non-finite losses in the window = {failed}  limit 0")
    if world > 1:
        say(f"correct: parameters differ between chips = {int(differ)}  limit 0")
    say(f"reference took {time.perf_counter() - t_ref:.1f} s (not in setup_s)")

    facts: Dict[str, Any] = {
        "config": config, "mix": mix, "world": world, "steps": steps,
        "window_s": window_s, "tokens_per_s": tokens_per_s,
        "window_compiles": window_compiles, "spans": dict(spans.seconds),
        "platform": jax.devices()[0].platform, "device_kind": jax.devices()[0].device_kind,
        "trace": None,
    }
    device_extra: Dict[str, Any] = {"memory_peak_bytes": int(peak)}
    breakdown: Optional[Dict[str, Any]] = None
    if spec.trace:
        path = trace_reduce.find_xplane(str(trace_dir))
        if path is None:
            raise SystemExit(f"chipbench: the profiler left no trace under {trace_dir}")
        reduced = trace_reduce.reduce_trace(trace_reduce.load_xplane(str(path)), SPAN_PREFIX)
        shutil.rmtree(trace_dir)  # 20 MB a chip for 4 s; the reduction is what is kept
        if reduced.get("devices"):
            facts["trace"] = reduced
            device_extra.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        elif spec.require_chip:
            raise SystemExit("chipbench: no operation ran on a device in the traced window")

    return {
        "correct": bool(correct.verdict(rows_cmp) and failed == 0 and not differ),
        "attempted": steps,
        "failed": failed,
        "end_to_end": {
            "train_tokens_per_s": tokens_per_s,
            "train_step_p95_ms": percentile(samples, 95),
            "setup_s": setup_s,
        },
        "facts": facts,
        "device": device_extra,
        "breakdown": breakdown,
    }
