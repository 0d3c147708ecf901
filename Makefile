# Repro of "On the Discrepancy between the Theoretical Analysis and
# Practical Implementations of Compressed Communication for Distributed
# Deep Learning" (AAAI'20). See README.md / ROADMAP.md.

# Tier-1 verification — the command the tier-1 gate runs: every test,
# six xdist workers taking whole files, on the CPU, within 1470 s.
verify:
	timeout -k 10 1470 env PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} \
	  JAX_PLATFORMS=cpu python -m pytest -q \
	  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
	  --dist loadfile -p no:randomly

# Tier-1 minus the long-running tests (`slow`) and the sweeps past their
# unmarked smoke cases (`sched` / `wire` / `obs` / `stream` / `scenario`
# / `fault` markers) — the inner-loop command. Full `make verify` before
# shipping.
verify-fast:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH} python -m pytest -x -q -m "not slow and not sched and not wire and not obs and not stream and not scenario and not fault"

# Full microbenchmarks (operators x granularity, Pallas kernels, UnitPlan
# dispatches, adaptive controller). Writes BENCH_unitplan.json and
# BENCH_controller.json, so it refuses to run on a dirty tree: committed
# BENCH files must be attributable to a commit (BENCH_FORCE=1 overrides).
bench: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -m benchmarks.run --only micro

bench-guard:
	@if [ -z "$$BENCH_FORCE" ] && [ -n "$$(git status --porcelain 2>/dev/null)" ]; then \
	  echo "refusing to overwrite BENCH_*.json on a dirty tree (untracked files count);"; \
	  echo "commit first, or override with BENCH_FORCE=1 make bench"; \
	  exit 1; fi

# Just the per-leaf-vs-planned dispatch benchmark -> BENCH_unitplan.json.
bench-unitplan:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import unitplan; unitplan()"

# Just the controller benchmark -> BENCH_controller.json.
bench-controller:
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import controller; controller()"

# Just the comm-schedule benchmark (message fusion counts + modeled
# exposed comm) -> BENCH_schedule.json. Same clean-tree guard as `bench`:
# committed BENCH files must be attributable to a commit.
bench-schedule: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import schedule; schedule()"

# Just the wire benchmark (accounted vs measured packed bits per model
# config x codec x fusion threshold) -> BENCH_wire.json. Clean-tree
# guarded like every BENCH artifact.
bench-wire: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import wire; wire()"

# Just the fused-kernel traffic benchmark (per-codec encode/decode bytes
# moved + measured pallas dispatch counts, jnp vs fused single-launch)
# -> BENCH_kernels.json. Deterministic counts, no wall clocks; clean-tree
# guarded like every BENCH artifact.
bench-kernels: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import kernels_bench; kernels_bench()"

# Just the observability calibration benchmark (TraceRecorder-measured
# exposed comm vs the alpha-beta model, default + per-host fitted
# parameters) -> BENCH_obs.json. Wall-clock based by nature — trust the
# counts/bytes and the RELATIVE ratio shape, not absolute us (the report
# embeds the caveat). Clean-tree guarded like every BENCH artifact.
bench-obs: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.microbench import obs_bench; obs_bench()"

# Just the streaming-collective benchmark (ring vs serialized allgather
# stream on an 8-virtual-device host ring: hop counts, bytes per hop,
# measured exposed comm) -> BENCH_stream.json. The hop/byte COUNTS are
# the gate — deterministic; the ring-vs-serialized wall clocks carry the
# container-noise caveat the report embeds. XLA_FLAGS must be set before
# jax initializes, hence on the recipe line. Clean-tree guarded like
# every BENCH artifact.
bench-stream: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. \
	  XLA_FLAGS=--xla_force_host_platform_device_count=8 python -c \
	  "from benchmarks.microbench import stream; stream()"

# The fault-injected scenario campaign (SimCluster): registry configs x
# scenarios x top-k ratios x granularities -> convergence + exposed-comm
# telemetry + the per-cell layerwise-vs-entire-model verdict ->
# BENCH_scenarios.json. Deterministic model numbers (no wall clocks).
# SCENARIO_STEPS=n shrinks the per-cell step count for quick looks.
# Clean-tree guarded like every BENCH artifact.
bench-scenarios: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. python -c \
	  "from benchmarks.scenarios import scenarios; scenarios()"

# The resilience-plane benchmark: the Fletcher-32 detection matrix (six
# codecs x granularities x {serialized, ring} collectives: detection and
# false-positive rates under single-bit flips), per-message integrity
# overhead in bytes, the faulted-campaign recovery verdict (corrupted
# cell + resend recovers the clean cell's layerwise-vs-entire_model
# verdict), and the kill-and-resume bitwise gate -> BENCH_faults.json.
# Deterministic (seeded corruption, no wall clocks); the gates are
# ASSERTED, not just recorded. The ring leg needs virtual devices, so
# XLA_FLAGS rides the recipe line. Clean-tree guarded like every BENCH
# artifact. FAULT_STEPS=n shrinks the training legs for quick looks.
bench-faults: bench-guard
	PYTHONPATH=src$${PYTHONPATH:+:$$PYTHONPATH}:. \
	  XLA_FLAGS=--xla_force_host_platform_device_count=8 python -c \
	  "from benchmarks.faults import faults; faults()"

.PHONY: verify verify-fast bench bench-guard bench-unitplan \
	bench-controller bench-schedule bench-wire bench-kernels bench-obs \
	bench-stream bench-scenarios bench-faults
