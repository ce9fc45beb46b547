# Native runtime build (the analog of the reference's single-rule Makefile
# building communicator.so; here g++ instead of nvcc, no MPI/ibverbs).
CXX ?= g++
CXXFLAGS ?= -O2 -std=c++17 -fPIC -Wall -Wextra

LIB := libadapcc_rt.so
SRCS := csrc/schedule_engine.cpp

.PHONY: all native test sim-bench ring-sweep quant-bench fused-bench tune-bench overlap-bench latency-bench compiler-bench hier-bench elastic-bench adapt-bench chaos-bench fabric-bench recovery-bench serve-bench disagg-bench simscale-bench pipe-bench trace-export clean

all: native

native: $(LIB)

$(LIB): $(SRCS)
	$(CXX) $(CXXFLAGS) -shared -o $@ $(SRCS)

test: native
	python -m pytest tests/ -q

# Hardware-free collective sweep on the calibrated α-β simulator
# (docs/SIMULATION.md).  Deterministic: same calibration artifact →
# byte-identical rows, so it runs in CI alongside the tier-1 tests.
sim-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 4K,1M,16M --json

# Chunk-size sweep for the staged HBM-streaming Pallas ring on the same
# simulator (docs/RING.md): deterministic "mode": "simulated" rows over a
# chunk_bytes grid, so ring chunk tuning has a hardware-free regression
# artifact.  Path/tile per row come from the kernel's own planner.
ring-sweep:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 16M,128M --ring-sweep --chunks 256K,1M,4M,16M --json

# Wire-codec sweep for the quantized ring allreduce on the same simulator
# (docs/QUANT.md): deterministic "mode": "simulated" rows over the codec
# grid, priced by the sim-rank cost-model term (reduced wire bytes vs
# per-hop codec overhead), with the chosen dtype flagged per size.
quant-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M,128M --wire-dtype off,bf16,int8 --json

# Fused-vs-unfused codec sweep for the quantized STREAMING ring on the
# same simulator (docs/RING.md §5): deterministic "mode": "simulated"
# rows over (size x wire_dtype x chunk_bytes) comparing the fused staged
# kernel's overlapped pricing against the ppermute reroute's serial
# pricing, with the crossover size flagged per row.
fused-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M,128M --fused-sweep --chunks 256K,1M,4M --json

# Autotuner convergence replay on a deterministic synthetic cost surface
# (docs/TUNER.md): "mode": "simulated" rows over the (chunk x codec) grid
# with the policy's chosen plan flagged per size — the hardware-free
# regression artifact for the measurement-driven plan tuner.
tune-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M,128M --tune-replay --json

# Overlapped-gradient-sync sweep on the same simulator (docs/OVERLAP.md):
# deterministic "mode": "simulated" rows over (accum x bucket cap x
# overlap schedule), priced by overlapped_step_time — exposed comm for
# the bucket-rolling schedule is strictly below the non-overlapped
# baseline on every comm-bound configuration.
overlap-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 16M,128M --overlap-sweep --accums 1,2,4 \
		--bucket-caps-mb 1,4 --json

# Latency-bound allreduce algorithm sweep on the same simulator
# (docs/LATENCY.md): deterministic "mode": "simulated" rows over a size
# grid spanning the ring <-> recursive-doubling crossover, pricing ring vs
# recursive halving/doubling vs binomial tree per size, with the chosen
# algorithm and the crossover size flagged per row — the sized decision
# ADAPCC_COLL_ALGO=auto executes, as a regression artifact.
latency-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1K,16K,64K,256K,1M,16M --latency-sweep --json

# Schedule-compiler sweep on the same simulator (docs/COMPILER.md):
# deterministic "mode": "simulated" rows over a size grid pricing the
# IR-lowered programs — ring / recursive-doubling / binomial tree
# re-emitted as compiler.ScheduleProgram, plus the pipelined
# bidirectional schedule no hand-written plane expresses — each verified
# by compiler.verify_program then priced by schedule_program_time next
# to its legacy plane's own term, with the pipelined program's
# beats-lockstep-ring acceptance flag stamped per row.
compiler-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 64K,1M,16M,128M --schedule-sweep --json

# Hierarchical (DCN x ICI) two-level-vs-flat sweep on the same simulator
# (docs/HIERARCHY.md): deterministic "mode": "simulated" rows over the
# (pods x pod_size x size) grid pricing the composed RS-within-pod ->
# AR-across-leaders -> AG-within-pod plan against the flat ring on the
# DCN bottleneck, with the per-row decision and the pod-count crossover
# flagged — the wire-time half of the hierarchical synthesis story.
hier-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--sizes 1M,16M,128M --hier-sweep --pods 2,4,8 --pod-sizes 4,8 --json

# Elastic failover sweep on the same simulator (docs/ELASTIC.md):
# deterministic "mode": "simulated" rows pricing each injected fault's
# detection -> swap -> steady-state timeline (standby-cached vs cold swap
# stall both priced), plus a canonical fault plan's per-step replay.
elastic-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M --fault-sweep --hosts 2 --json

# Closed-adaptation-loop replay on the same simulator (docs/ADAPT.md):
# deterministic "mode": "simulated" rows driving the REAL drift detector
# through an injected DCN degradation — per-step detection timeline
# (drift onset, detection lag) plus a summary pricing stale-vs-adapted
# steady state and the hot-swap stall vs the full-rebuild stall (probe
# traffic + re-synthesis + cold compile) the closed loop avoids.
adapt-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M --adapt-sweep --hosts 2 --json

# Supervised-failover pricing on the same simulator (docs/SUPERVISOR.md):
# deterministic "mode": "simulated" rows over the (heartbeat period x
# grace) grid — out-of-band detection latency vs the false-positive
# headroom the confirmation window buys — next to the standby-cached vs
# cold swap stall, plus the canonical fault plan compiled into its
# deterministic cross-process chaos schedule (SIGKILL / SIGSTOP duty
# cycle), the spelling the multi-process drill delivers to real ranks.
chaos-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 16M,128M --chaos-sweep --json

# Multi-tenant fabric sweep on the same simulator (docs/FABRIC.md):
# deterministic "mode": "simulated" rows over (congestion intensity x
# priority mix) on a two-pod split of --world — the coordinated high-low
# fabric (the low-priority job's synthesizer constrained off the high
# job's occupied links) priced against the uncoordinated high-high
# pile-up, with per-job steady states, Jain's fairness index, and the
# high-beats-uncoordinated acceptance flag stamped per row.
fabric-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --sizes 1M,16M --fabric-sweep --intensities 1,2,4 --json

# Durable-recovery pricing on the same simulator (docs/RECOVERY.md):
# deterministic "mode": "simulated" rows over the (world x payload) grid
# — the per-step wire overhead of k-replicated ZeRO-1 shards against the
# baseline step comm (the < 5% acceptance bound stamped per row), and the
# in-fabric shard repair (one hop + warm swap, zero lost steps) priced
# against a checkpoint reload (full-state read + save_interval/2 steps of
# re-done work).
recovery-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--sizes 1M,64M --recovery-sweep --json

# Latency-SLO serving frontier on the same simulator (docs/SERVING.md):
# deterministic "mode": "simulated" rows over (arrival rate x decode
# slots) — one seeded Poisson trace per rate replayed through the
# continuous batcher's queueing twin, each cell priced by the decode-step
# service time (per-layer small-message allreduce on the calibrated
# coefficients + compute), with p50/p99 sojourn, throughput, utilization,
# and SLO attainment stamped per row.  The frontier an admission policy
# trades along, as a regression artifact.
serve-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --serve-sweep --rates 0.05,0.1,0.25 \
		--serve-slots 1,2,4,8 --slo-ms 2 --json

# Colocated-vs-disaggregated serving frontier (docs/SERVING.md §7):
# deterministic "mode": "simulated" rows over (request mix x pool split
# x d_model) at equal chip count — prefill priced by pool-world decode
# steps, the KV migration on the calibrated DCN α-β coefficients, decode
# by decode_step_time — each row carrying both the two-pool tandem
# percentiles (simulate_disagg_queue) and the colocated baseline, with
# disagg_beats_colocated_p99_ttft stamping the frontier cell.
disagg-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--world 8 --disagg-sweep --json

# Replay-scaling grid on the vectorized engine (docs/SIMULATION.md §7):
# deterministic "mode": "simulated" rows over (world x size) at pod
# scale, each priced on its own uniform synthetic topology and stamped
# with its certified optimality_gap against the α-β collective lower
# bound.  Byte-identical across runs — measured replay wall-clock rows
# live in benchmarks.synthesis_scale instead.
simscale-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--scale-sweep --scale-worlds 1024,4096,16384,65536 \
		--sizes 1M,16M,256M --json

# GPipe-vs-1F1B pipeline frontier on the same simulator
# (docs/PIPELINE.md): deterministic "mode": "simulated" rows over the
# (stages x microbatches x hop bytes) grid, each cell's verified hop
# program replayed next to the closed-form step time and stash bound,
# the 1F1B memory win flagged per row.  Byte-identical across runs; the
# gpipe-vs-1f1b A/B is not measured on a chip yet.
pipe-bench:
	JAX_PLATFORMS=cpu python -m benchmarks.sim_collectives \
		--pipe-sweep --pipe-stages 2,4 --pipe-microbatches 2,4,8 \
		--sizes 1M,16M --json

# Perfetto/chrome://tracing export of a recorded dispatch trace: run a
# short virtual-pod collective session under ADAPCC_TUNER=record and emit
# benchmarks/results/trace_export.json (open in ui.perfetto.dev).
trace-export:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -m scripts.trace_export

clean:
	rm -f $(LIB)
