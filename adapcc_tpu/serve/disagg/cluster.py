"""The disaggregated serving cluster: two pods, one router, traced KV streams.

Topology: a **prefill pod** and a **decode pod** of equal TP world (the
two-pod ``HierarchySketch`` layout — equal worlds keep the head-sharded
page layout ``[world, slots, max_seq, Hl, hd]`` migration-compatible
without a reshard).  Each pod owns its own mesh, its own
:class:`~adapcc_tpu.comm.engine.CollectiveEngine` (both recording into
ONE shared dispatch trace) and its own
:class:`~adapcc_tpu.serve.kv_cache.SlotKVCache`; one
:class:`~adapcc_tpu.serve.model.TPDecodeModel` serves both pods' compiled
step programs.

Request lifecycle (the bit-parity contract):

1. **admit → prefill**: FIFO admission into a free prefill slot, RNG
   reset to ``PRNGKey(seed)`` — exactly the colocated batcher's
   admission.  The lane force-feeds its prompt one token per step; the
   step that feeds position ``prompt_len − 1`` samples the **first
   generated token** (TTFT lands here, in the prefill pod).
2. **migrate**: the finished prefill's pages — only the filled prefix
   ``[:prompt_len]`` — ride :meth:`CollectiveEngine.kv_transfer` into a
   zeroed decode slot (one traced DCN stream per migration), together
   with the lane's RNG key.  No free decode slot → the lane **waits
   resident** in its prefill slot: frozen out of prefill compute, RNG
   untouched, never dropped.
3. **decode**: the decode pod streams the remaining tokens with the
   colocated step semantics (EOS latch included).

Why the streams are bit-identical to the colocated ``GPT2Server``: a
lane's tokens depend only on its prompt, its RNG **split count**, and
the (exact, re-association-free) layer math over its own pages — never
on the global clock or on its neighbors.  The router advances a lane's
RNG exactly once per step the lane actually computes (frozen lanes have
their keys restored after the fixed-shape pool step), migrates the key
with the pages, and the fp32 (``"off"``) wire moves pages bit-exactly —
so the k-th computed step of a request sees the same key and the same
pages wherever it runs.  The int8 wire deliberately breaks page
exactness; that is why it is gated behind the token-level KL probe
(:func:`measure_token_kl`) at construction time.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from adapcc_tpu.comm.engine import KV_TRANSFER_CHUNK_BYTES, CollectiveEngine
from adapcc_tpu.models.gpt2 import GPT2Config
from adapcc_tpu.serve import resolve_serve_slo_ms, resolve_serve_slots
from adapcc_tpu.serve.disagg import (
    KV_KL_BOUND_ENV,
    resolve_kv_kl_bound,
    resolve_kv_wire_dtype,
)
from adapcc_tpu.serve.kv_cache import SlotKVCache
from adapcc_tpu.serve.model import TPDecodeModel
from adapcc_tpu.serve.scheduler import Request, RequestResult
from adapcc_tpu.serve.trace import ArrivalTrace
from adapcc_tpu.strategy.ir import Strategy
from adapcc_tpu.utils.observability import (
    MetricsRegistry,
    nearest_rank_percentile,
)

#: pod ids stamped on every kv_transfer trace event (HierarchySketch order)
PREFILL_POD = 0
DECODE_POD = 1


@dataclass
class _ClusterLane:
    """One occupied slot's host state, in whichever pod currently owns it."""

    req: Request
    admitted_step: int
    tokens: np.ndarray = field(default=None)  # type: ignore[assignment]
    #: scan position: index of the token the NEXT step feeds
    pos: int = 0
    first_token_step: int = -1
    #: router step at which the lane entered the decode pod (−1 = not yet)
    migrated_step: int = -1
    wall_t0: float = 0.0

    @property
    def prompt_len(self) -> int:
        return len(self.req.prompt)


class _Pool:
    """One pod: a mesh, an engine, a slot cache, lanes, and RNG rows."""

    def __init__(
        self,
        name: str,
        pod_id: int,
        cfg: GPT2Config,
        mesh,
        slots: int,
        trace=None,
        engine: Optional[CollectiveEngine] = None,
    ) -> None:
        self.name = name
        self.pod_id = pod_id
        self.cfg = cfg
        self.mesh = mesh
        self.world = int(mesh.devices.size)
        self.slots = int(slots)
        if engine is None:
            engine = CollectiveEngine(
                mesh, Strategy.ring(self.world), trace=trace
            )
        self.engine = engine
        #: per-pod registry so the two pods' kv_cache.* ledgers stay split
        self.cache_metrics = MetricsRegistry()
        self.lanes: Dict[int, _ClusterLane] = {}
        self.reset()

    def reset(self) -> None:
        """(Re)build the pod's serving state from scratch — fresh pages,
        every slot free, RNG zeroed.  This is also the pod-death path:
        the cache-metrics registry survives, so eviction/reuse counters
        accumulate across a rebuild."""
        self.cache = SlotKVCache(
            self.cfg, self.world, self.slots, mesh=self.mesh,
            metrics=self.cache_metrics,
        )
        self.lanes = {}
        self.free: List[int] = list(range(self.slots))
        # committed to THIS pod's devices: the two pods' meshes are
        # disjoint device sets, and a stray default-device RNG array
        # would collide with the pod's committed pages inside the jitted
        # decode step
        self.rng = jax.device_put(
            jnp.zeros((self.slots, 2), jnp.uint32),
            NamedSharding(self.mesh, PartitionSpec()),
        )


def measure_token_kl(
    cfg: GPT2Config,
    params: Any,
    world: int,
    wire_dtype: str,
    prompt: Optional[List[int]] = None,
    block_size: Optional[int] = None,
) -> float:
    """Token-level KL (nats) a lossy KV wire would inflict on the first
    decode-pod step: prefill a deterministic probe prompt (exact fp32
    math, engine-free — the stacked partial's sum replaces the
    allreduce, which is the same concatenation), then compute the
    next-token distribution twice — over the exact pages and over
    ``codec.apply``'d pages (exactly what ``kv_transfer`` would move) —
    and return ``KL(p_exact ‖ p_codec)``.

    ``"off"`` returns exactly 0.0 (identity wire).  This is the
    acceptance probe the :class:`ClusterRouter` runs at construction:
    one measurement per (config, params, wire) — the EQuARX-style bar
    the colocated decode combine never needed because fp32 bought bit
    parity outright.
    """
    from adapcc_tpu.quant import get_codec
    from adapcc_tpu.quant.codec import DEFAULT_BLOCK_SIZE

    codec = get_codec(wire_dtype)
    if codec.name == "off":
        return 0.0
    block = int(block_size) if block_size is not None else DEFAULT_BLOCK_SIZE
    tp = TPDecodeModel(cfg, world)
    if prompt is None:
        plen = max(1, min(8, cfg.max_seq - 2))
        prompt = [1 + (i % (cfg.vocab_size - 1)) for i in range(plen)]
    plen = len(prompt)
    if plen + 1 >= cfg.max_seq:
        raise ValueError(
            f"KL probe prompt of {plen} tokens leaves no room for a "
            f"generated token under max_seq={cfg.max_seq}"
        )
    shape = (world, 1, cfg.max_seq, tp.heads_local, tp.head_dim)
    layers: List[Tuple[jnp.ndarray, jnp.ndarray]] = [
        (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
        for _ in range(cfg.n_layer)
    ]

    def step(cache_layers, tok: int, pos_i: int):
        x = tp.embed(
            params, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([pos_i], jnp.int32),
        )
        new_layers = []
        for layer in range(cfg.n_layer):
            lp = params[f"h{layer}"]
            k_pages, v_pages = cache_layers[layer]
            partial, k_pages, v_pages = tp.attn_partial(
                lp, x, k_pages, v_pages, jnp.asarray([pos_i], jnp.int32)
            )
            new_layers.append((k_pages, v_pages))
            # the allreduce's sum, without an engine: exact concatenation
            x = tp.post_attn(lp, x, partial.sum(axis=0))
        return new_layers, tp.logits(params, x)

    logits = None
    for i, tok in enumerate(prompt):
        layers, logits = step(layers, int(tok), i)
    first_token = int(jnp.argmax(logits[0, 0]))

    def distorted(cache_layers):
        out = []
        for k_pages, v_pages in cache_layers:
            kq = k_pages.at[:, :, :plen].set(
                codec.apply(k_pages[:, :, :plen], block).astype(k_pages.dtype)
            )
            vq = v_pages.at[:, :, :plen].set(
                codec.apply(v_pages[:, :, :plen], block).astype(v_pages.dtype)
            )
            out.append((kq, vq))
        return out

    _, exact = step(layers, first_token, plen)
    _, lossy = step(distorted(layers), first_token, plen)
    # float64 on the host: at small widths the KL (quadratic in the logit
    # shift) sits below fp32's resolution, where the fp32 sum comes out 0 or
    # negative and the probe would report a lossy wire as lossless
    def log_softmax64(logits):
        z = np.asarray(logits[0, 0], np.float64)
        z = z - z.max()
        return z - np.log(np.exp(z).sum())

    lp_exact, lp_lossy = log_softmax64(exact), log_softmax64(lossy)
    kl = np.sum(np.exp(lp_exact) * (lp_exact - lp_lossy))
    return max(float(kl), 0.0)


class ClusterRouter:
    """Routes requests through the two-pod disaggregated cluster.

    The public surface mirrors :class:`~adapcc_tpu.serve.scheduler.
    GPT2Server` (``submit`` / ``submit_trace`` / ``step`` / ``run`` /
    ``results`` / ``summary``) so the two serving planes are drop-in
    alternatives for the same arrival trace; ``summary`` additionally
    splits latency per pool and carries the KV-stream ledger.
    """

    def __init__(
        self,
        cfg: GPT2Config,
        params: Any,
        prefill_mesh,
        decode_mesh,
        prefill_slots: Optional[int] = None,
        decode_slots: Optional[int] = None,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_id: Optional[int] = None,
        algo: Optional[str] = "auto",
        trace=None,
        metrics: Optional[MetricsRegistry] = None,
        slo_ms: Optional[float] = None,
        kv_wire_dtype: Optional[str] = None,
        kv_kl_bound: Optional[float] = None,
        kv_block_size: Optional[int] = None,
        kv_chunk_bytes: int = KV_TRANSFER_CHUNK_BYTES,
    ) -> None:
        pw = int(prefill_mesh.devices.size)
        dw = int(decode_mesh.devices.size)
        if pw != dw:
            raise ValueError(
                f"prefill pod world={pw} != decode pod world={dw}: equal "
                "TP worlds are what keep the head-sharded KV page layout "
                "migration-compatible without a reshard"
            )
        self.cfg = cfg
        self.params = params
        self.pool_world = pw
        #: total chips across both pods — the budget the colocated
        #: baseline gets in an equal-chip-count comparison
        self.world = 2 * pw
        self.eos_id = eos_id
        self.algo = algo
        self.slo_ms = resolve_serve_slo_ms(slo_ms)
        self.trace = trace
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.kv_wire_dtype = resolve_kv_wire_dtype(kv_wire_dtype)
        self.kv_block_size = kv_block_size
        self.kv_chunk_bytes = int(kv_chunk_bytes)
        self.kv_kl: Optional[float] = None
        if self.kv_wire_dtype != "off":
            bound = resolve_kv_kl_bound(kv_kl_bound)
            self.kv_kl = measure_token_kl(
                cfg, params, pw, self.kv_wire_dtype,
                block_size=kv_block_size,
            )
            if self.kv_kl > bound:
                raise ValueError(
                    f"KV wire dtype {self.kv_wire_dtype!r} rejected: "
                    f"measured token-level KL {self.kv_kl:.3e} nats exceeds "
                    f"the acceptance bound {bound:.3e} ({KV_KL_BOUND_ENV}); "
                    "serve the bit-exact fp32 wire ('off') or raise the "
                    "bound deliberately"
                )
            self.kv_kl_bound = bound
        self.tp = TPDecodeModel(
            cfg, pw, temperature=temperature, top_k=top_k, top_p=top_p
        )
        self.prefill = _Pool(
            "prefill", PREFILL_POD, cfg, prefill_mesh,
            resolve_serve_slots(prefill_slots), trace=trace,
        )
        self.decode = _Pool(
            "decode", DECODE_POD, cfg, decode_mesh,
            resolve_serve_slots(decode_slots), trace=trace,
        )
        self.clock = 0
        self._pending: Deque[Request] = deque()
        #: prefill slots whose lane finished prefill and awaits a decode
        #: slot (FIFO by readiness; frozen out of prefill compute)
        self._ready: Deque[int] = deque()
        self._results: Dict[int, RequestResult] = {}
        self._arrival_wall: Dict[int, float] = {}
        #: req_id → router step the request entered the decode pod
        self._migrated: Dict[int, int] = {}
        self._kv_transfers = 0
        self._kv_payload_bytes = 0
        self._kv_wire_bytes = 0

    # -- admission -------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Same loud validations as the colocated server's ``submit`` —
        the two planes must reject exactly the same traffic."""
        if req.total > self.cfg.max_seq:
            raise ValueError(
                f"request {req.req_id}: {req.total} tokens > "
                f"max_seq={self.cfg.max_seq} cache slots"
            )
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.req_id}: max_new_tokens must be >= 1"
            )
        if not req.prompt:
            raise ValueError(f"request {req.req_id}: empty prompt")
        bad = [t for t in req.prompt if not 0 <= t < self.cfg.vocab_size]
        if bad:
            raise ValueError(
                f"request {req.req_id}: prompt token(s) {bad[:3]} outside "
                f"vocab_size={self.cfg.vocab_size}"
            )
        self._pending.append(req)

    def submit_trace(self, trace: ArrivalTrace) -> None:
        if trace.world != self.world:
            raise ValueError(
                f"arrival trace was authored for world={trace.world} but "
                f"this cluster runs world={self.world} "
                f"(2 pods x {self.pool_world})"
            )
        for spec in trace.requests:
            self.submit(Request.from_spec(spec))

    def _admit(self) -> None:
        pool = self.prefill
        while pool.free and self._pending and (
            self._pending[0].arrival_step <= self.clock
        ):
            req = self._pending.popleft()
            slot = pool.free.pop(0)
            lane = _ClusterLane(req=req, admitted_step=self.clock)
            lane.tokens = np.zeros((req.total,), np.int32)
            lane.tokens[: len(req.prompt)] = np.asarray(req.prompt, np.int32)
            lane.wall_t0 = time.perf_counter()
            pool.lanes[slot] = lane
            pool.cache.clear_slot(slot)
            pool.rng = pool.rng.at[slot].set(jax.random.PRNGKey(req.seed))
            self.metrics.incr("serve.admitted")

    # -- the cluster step ------------------------------------------------------

    def step(self) -> int:
        """One router tick: admit into prefill, advance both pods by one
        token (the cluster's two compiled steps run per tick — the wall
        cost of a tick is their max, which is what the sim twin prices),
        then migrate every finished prefill a decode slot can take.
        Returns the number of lanes that computed."""
        now = time.perf_counter()
        for req in self._pending:
            if req.arrival_step > self.clock:
                break  # arrival-sorted FIFO (the discipline _admit assumes)
            self._arrival_wall.setdefault(req.req_id, now)
        self._admit()
        frozen = set(self._ready)
        n = self._step_pool(self.prefill, frozen)
        n += self._step_pool(self.decode, set())
        self._migrate_ready()
        self.clock += 1
        return n

    def _step_pool(self, pool: _Pool, frozen: set) -> int:
        """Advance one pod's occupied, non-frozen lanes by one token.

        Frozen lanes (finished prefills awaiting a decode slot) stay
        resident but out of the computation: their RNG rows are restored
        after the fixed-shape step (the vmapped sampler splits every
        row), and their position is pointed at the first *unmigrated*
        row so the step's unconditional cache write for their slot can
        only touch a row the migration never copies.
        """
        active = sorted(s for s in pool.lanes if s not in frozen)
        if not active:
            return 0
        t0 = time.perf_counter()
        tok = np.zeros((pool.slots, 1), np.int32)
        pos = np.zeros((pool.slots,), np.int32)
        for s in active:
            lane = pool.lanes[s]
            tok[s, 0] = lane.tokens[lane.pos]
            pos[s] = lane.pos
        saved_rng = {}
        for s in frozen:
            if s in pool.lanes:
                pos[s] = pool.lanes[s].pos  # a row beyond the migrated prefix
                saved_rng[s] = pool.rng[s]
        pool.rng, sampled, new_layers = self.tp.decode_step(
            self.params,
            pool.engine,
            pool.cache.layers,
            jnp.asarray(tok),
            jnp.asarray(pos),
            pool.rng,
            algo=self.algo,
        )
        for layer, (k_pages, v_pages) in enumerate(new_layers):
            pool.cache.update(layer, k_pages, v_pages)
        for s, key in saved_rng.items():
            pool.rng = pool.rng.at[s].set(key)
        sampled_host = np.asarray(sampled)
        self.metrics.observe(f"serve.{pool.name}.step_s",
                             time.perf_counter() - t0)
        self.metrics.gauge(f"serve.{pool.name}.slots_busy", len(active))
        self.metrics.gauge("serve.queue_depth", len(self._pending))
        for s in active:
            self._advance(pool, s, int(sampled_host[s]))
        return len(active)

    def _advance(self, pool: _Pool, slot: int, sampled: int) -> None:
        """The colocated ``_advance_lane`` body, with one extra outcome:
        a prefill lane that just wrote its first generated token (and
        neither completed nor latched EOS) becomes *ready* and queues
        for migration instead of decoding in place."""
        lane = pool.lanes[slot]
        req = lane.req
        t = lane.pos
        prompt_len = lane.prompt_len
        if t + 1 >= prompt_len:
            lane.tokens[t + 1] = sampled
            if t + 1 == prompt_len:
                lane.first_token_step = self.clock + 1
        lane.pos = t + 1
        wrote_eos = (
            self.eos_id is not None
            and t + 1 >= prompt_len
            and int(lane.tokens[t + 1]) == self.eos_id
        )
        if wrote_eos and lane.pos < req.total - 1:
            lane.tokens[lane.pos + 1:] = self.eos_id
            self.metrics.incr("serve.evicted_eos")
            self._complete(pool, slot, eos_evicted=True)
            return
        if lane.pos == req.total - 1:
            # max_new_tokens == 1 completes inside the prefill pod: there
            # is nothing left to decode, so no migration is owed
            self._complete(pool, slot, eos_evicted=False)
            return
        if pool is self.prefill and lane.pos >= prompt_len:
            self._ready.append(slot)

    def _migrate_ready(self) -> None:
        """Move finished prefills into free decode slots, FIFO: pages
        (filled prefix only) through the traced ``kv_transfer`` stream,
        RNG key by copy.  Runs at end of step — a migrated lane decodes
        its next token on the next tick.  Lanes the decode pod cannot
        take yet stay queued; nothing is ever dropped."""
        while self._ready and self.decode.free:
            slot = self._ready.popleft()
            lane = self.prefill.lanes.pop(slot)
            p = lane.pos  # == prompt_len: rows [0, p) are the filled prefix
            pages = [
                (k[:, slot, :p], v[:, slot, :p])
                for k, v in self.prefill.cache.layers
            ]
            moved = self.prefill.engine.kv_transfer(
                pages,
                src_pod=PREFILL_POD,
                dst_pod=DECODE_POD,
                wire_dtype=self.kv_wire_dtype,
                block_size=self.kv_block_size,
                chunk_bytes=self.kv_chunk_bytes,
                dst_sharding=self.decode.cache.sharding,
            )
            dslot = self.decode.free.pop(0)
            self.decode.cache.clear_slot(dslot)
            self.decode.cache.layers = [
                (k.at[:, dslot, :p].set(mk), v.at[:, dslot, :p].set(mv))
                for (k, v), (mk, mv) in zip(self.decode.cache.layers, moved)
            ]
            # the RNG key migrates with the pages; hop through the host
            # so the prefill-committed key cannot drag the decode pod's
            # RNG array onto the wrong devices
            self.decode.rng = self.decode.rng.at[dslot].set(
                np.asarray(jax.device_get(self.prefill.rng[slot]))
            )
            self.prefill.cache.release_slot(slot, used_tokens=p, evicted=False)
            self.prefill.free.append(slot)
            self.prefill.free.sort()
            lane.migrated_step = self.clock + 1
            self._migrated[lane.req.req_id] = lane.migrated_step
            self.decode.lanes[dslot] = lane
            payload = sum(int(k.nbytes) + int(v.nbytes) for k, v in pages)
            self._kv_transfers += 1
            self._kv_payload_bytes += payload
            self._kv_wire_bytes += self._wire_bytes(pages)
            self.metrics.incr("serve.migrated")

    def _wire_bytes(self, pages) -> int:
        if self.kv_wire_dtype == "off":
            return sum(int(k.nbytes) + int(v.nbytes) for k, v in pages)
        from adapcc_tpu.quant.codec import DEFAULT_BLOCK_SIZE
        from adapcc_tpu.sim.cost_model import wire_bytes_per_element

        block = (
            int(self.kv_block_size)
            if self.kv_block_size is not None else DEFAULT_BLOCK_SIZE
        )
        per_elem = wire_bytes_per_element(self.kv_wire_dtype, block)
        return int(sum(
            (int(k.size) + int(v.size)) * per_elem for k, v in pages
        ))

    def _complete(self, pool: _Pool, slot: int, eos_evicted: bool) -> None:
        lane = pool.lanes.pop(slot)
        pool.free.append(slot)
        pool.free.sort()
        req = lane.req
        pool.cache.release_slot(
            slot, used_tokens=lane.pos + 1, evicted=eos_evicted
        )
        if slot in self._ready and pool is self.prefill:
            self._ready.remove(slot)  # defensive; a ready lane never computes
        wall = time.perf_counter() - self._arrival_wall.pop(
            req.req_id, lane.wall_t0
        )
        result = RequestResult(
            req_id=req.req_id,
            tokens=[int(x) for x in lane.tokens],
            prompt_len=len(req.prompt),
            arrival_step=req.arrival_step,
            admitted_step=lane.admitted_step,
            first_token_step=lane.first_token_step,
            completed_step=self.clock + 1,
            eos_evicted=eos_evicted,
            wall_s=wall,
        )
        self._results[req.req_id] = result
        self.metrics.incr("serve.completed")
        self.metrics.observe("serve.sojourn_steps", result.sojourn_steps)
        if result.first_token_step >= 0:
            self.metrics.observe("serve.ttft_steps", result.ttft_steps)
        self.metrics.observe("serve.sojourn_s", wall)

    # -- failure injection -----------------------------------------------------

    def kill_decode_pool(self) -> List[int]:
        """Decode-pod death, mid-stream: every in-flight decode lane's
        request re-enters the *front* of the prefill queue with its
        original arrival step (FIFO order among the victims preserved),
        and the pod is rebuilt from scratch.  Nothing is dropped; the
        re-prefill recomputes the same RNG stream from ``PRNGKey(seed)``,
        so the victims' token streams are unchanged — the pinned casualty
        is exactly those requests' TTFT (first_token_step is re-earned
        after the death)."""
        victims = [
            self.decode.lanes[s].req.req_id
            for s in sorted(self.decode.lanes)
        ]
        for s in sorted(self.decode.lanes, reverse=True):
            lane = self.decode.lanes[s]
            self._pending.appendleft(lane.req)
            self._migrated.pop(lane.req.req_id, None)
        self.decode.reset()
        self.metrics.incr("serve.decode_pod_deaths")
        self.metrics.incr("serve.re_prefilled", len(victims))
        return victims

    # -- fabric integration ----------------------------------------------------

    def kv_stream_fabric_job(self, fabric, name: str = "kv_stream",
                             priority: Optional[str] = "high"):
        """Register the router's cumulative KV-stream traffic with a
        :class:`~adapcc_tpu.adapt.fabric.SharedFabric`, so congestion
        triage prices serving migrations against training DCN traffic.
        Serving is latency-critical, hence priority ``"high"`` by
        default.  Uses wire bytes (what the DCN actually carries), with
        a 1-byte floor so a cold router still registers."""
        return fabric.add_job(
            name,
            priority=priority,
            nbytes=max(1, int(self._kv_wire_bytes)),
            degree=1,
        )

    # -- the drive loop --------------------------------------------------------

    def run(self, max_steps: Optional[int] = None) -> List[RequestResult]:
        """Step until every submitted request completes (or ``max_steps``
        elapses — loudly, same policy as the colocated server)."""
        budget = max_steps if max_steps is not None else 1_000_000
        steps = 0
        while self._pending or self.prefill.lanes or self.decode.lanes:
            if steps >= budget:
                raise RuntimeError(
                    f"serve run exceeded max_steps={budget} with "
                    f"{len(self._pending)} queued / "
                    f"{len(self.prefill.lanes)} prefill / "
                    f"{len(self.decode.lanes)} decode in-flight requests"
                )
            self.step()
            steps += 1
        return self.results()

    def results(self) -> List[RequestResult]:
        return [self._results[k] for k in sorted(self._results)]

    def summary(self) -> dict:
        """The disaggregated serving ledger: the colocated summary's
        step-clock percentiles, split per pool (TTFT is prefill-pod
        latency by construction; decode residency runs migration →
        completion), plus the KV-stream ledger and per-pod cache stats."""
        res = self.results()
        out: dict = {
            "requests": len(res),
            "world": self.world,
            "steps": self.clock,
            "disagg": True,
            "pools": {
                "prefill": {
                    "world": self.prefill.world,
                    "slots": self.prefill.slots,
                },
                "decode": {
                    "world": self.decode.world,
                    "slots": self.decode.slots,
                },
            },
            "kv_cache": self.decode.cache.layout(),
            "kv_cache_stats": {
                "prefill": self.prefill.cache.stats(),
                "decode": self.decode.cache.stats(),
            },
            "kv_stream": {
                "wire_dtype": self.kv_wire_dtype,
                "transfers": self._kv_transfers,
                "payload_bytes": self._kv_payload_bytes,
                "wire_bytes": self._kv_wire_bytes,
                "chunk_bytes": self.kv_chunk_bytes,
            },
        }
        if self.kv_kl is not None:
            out["kv_stream"]["token_kl"] = self.kv_kl
            out["kv_stream"]["kl_bound"] = self.kv_kl_bound
        if res:
            def pct(xs, q):
                return int(nearest_rank_percentile(xs, q))

            sojourns = sorted(r.sojourn_steps for r in res)
            ttfts = sorted(
                r.ttft_steps for r in res if r.first_token_step >= 0
            )
            out["p50_sojourn_steps"] = pct(sojourns, 0.50)
            out["p99_sojourn_steps"] = pct(sojourns, 0.99)
            if ttfts:
                # arrival → first token: queue wait + prefill-pod service
                out["p50_ttft_steps"] = pct(ttfts, 0.50)
                out["p99_ttft_steps"] = pct(ttfts, 0.99)
                out["pools"]["prefill"]["p50_sojourn_steps"] = pct(ttfts, 0.50)
                out["pools"]["prefill"]["p99_sojourn_steps"] = pct(ttfts, 0.99)
            decode_res = sorted(
                r.completed_step - self._migrated[r.req_id]
                for r in res if r.req_id in self._migrated
            )
            if decode_res:
                # migration → completion: decode-pod residency
                out["pools"]["decode"]["p50_sojourn_steps"] = pct(
                    decode_res, 0.50
                )
                out["pools"]["decode"]["p99_sojourn_steps"] = pct(
                    decode_res, 0.99
                )
        snap = self.metrics.snapshot()
        for pool in ("prefill", "decode"):
            step_t = snap["timings"].get(f"serve.{pool}.step_s")
            if step_t:
                out["pools"][pool]["p50_step_ms"] = step_t["p50_s"] * 1e3
                out["pools"][pool]["p99_step_ms"] = step_t["p99_s"] * 1e3
        if self.slo_ms is not None and res:
            within = sum(1 for r in res if r.wall_s * 1e3 <= self.slo_ms)
            out["slo_ms"] = self.slo_ms
            out["slo_attainment"] = within / len(res)
        return out
