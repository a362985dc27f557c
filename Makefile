# celestia_tpu build/test surface (the reference's Makefile test tiers,
# /root/reference/Makefile:124-131, mapped to this repo).

PY ?= python

.PHONY: test test-all test-slow chaos bench bench-transfers dryrun native \
	trace-smoke bench-gate obs-smoke sdc-smoke storm-smoke storm-bench \
	ragged-smoke \
	store-smoke crash-smoke gateway-bench fleet-smoke \
	scenario-smoke scenario-pfb-storm scenario-rolling-outage \
	scenario-sdc-under-storm scenario-rejoin-under-load \
	scenario-gateway-fleet scenario-scale-out-under-load \
	scenario-disk-pressure scenarios \
	soak-smoke scenario-soak scenario-das-sweep \
	kernel-smoke bench-fused analyze san multichip-smoke multichip-bench \
	devledger-smoke

# Static analysis gate (specs/analysis.md, ADR-020): AST-level
# concurrency lint (lock ordering vs the specs/serving.md partial
# order, locks held across device transfers, torn reads),
# consensus-determinism lint over the DAH-critical modules, and
# registry-drift lint (fault sites / metrics / spans / SLO objectives
# vs their specs). Crypto-free, accelerator-free, stdlib-only —
# imports nothing from the package under analysis; seconds. Fails
# only on NEW findings (config/lint_baseline.json + inline
# `# lint: allow(...)` waivers, every one with a written reason).
analyze:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.tools.analysis

# Runtime sanitizer gate (celestia-san, specs/analysis.md §Runtime
# sanitizer): lock-order & device-boundary hammer over the whole
# serving lock surface, run twice on one seed (zero new T-findings +
# run-to-run determinism), cross-validated against celestia-lint
# (every static C001/C002/C003 site must be runtime-instrumentable;
# a statically waived hazard that fires live fails), then the
# lock-heavy tier-1 subset under `pytest --san`. CPU-only,
# crypto-free, <120 s budget enforced by the script itself.
san:
	JAX_PLATFORMS=cpu $(PY) scripts/san_smoke.py

# Fast developer loop: the default tier skips the slow multi-process
# suites (devnet, gRPC, multihost, network, race storms). Two FRESH
# pytest processes: accumulated XLA executables/tracing state slows
# jit-heavy tests 3-5x late in a long single process (measured on the
# 1-core CI box), so the device-path files run first in their own
# interpreter. ~2-3 min with a warm .jax_cache; the first run compiles
# and is slower.
JIT_A = tests/test_extend_tpu.py tests/test_nmt_semantics.py \
	tests/test_repair.py
JIT_B = tests/test_device_resident.py tests/test_blob_pool.py \
	tests/test_parallel.py tests/test_graft_entry.py
JIT_HEAVY = $(JIT_A) $(JIT_B)
# analyze first: the static gate costs ~3 s and fails fast on lint;
# san next: the runtime sanitizer gate is ~30 s and catches what the
# AST cannot (observed inversions, spec drift) before the long tiers;
# crash-smoke last of the gates: the powercut sweep + ENOSPC drill is
# ~2 s and guards the durability contract the store tests assume
test: analyze san crash-smoke
	$(PY) -m pytest $(JIT_HEAVY) -q
	$(PY) -m pytest tests/ -q $(addprefix --ignore=,$(JIT_HEAVY))

# Everything, including the slow tier (3-OS-process devnet, live gRPC,
# multi-host DCN backend, RPC race storms). ~8-15 min warm. Run as
# SHORT-LIVED processes: XLA:CPU on this box segfaults intermittently
# (in compile/serialize/deserialize, upstream jaxlib) once a single
# interpreter has compiled enough device-path programs — bounding
# compiles per process sidesteps it, and also avoids the measured
# late-process XLA slowdown (see ops/enable_compile_cache).
test-all:
	$(PY) -m pytest $(JIT_A) --all -q
	$(PY) -m pytest $(JIT_B) --all -q
	$(PY) -m pytest tests/ --all -q $(addprefix --ignore=,$(JIT_HEAVY))

# Only the slow tier.
test-slow:
	$(PY) -m pytest tests/ --all -m slow -q

# Deterministic chaos suite (specs/faults.md): fault injection across
# the transport/codec/device boundaries, slow cases included, pinned
# seed so every run replays the identical fault schedule.
chaos:
	CELESTIA_CHAOS_SEED=$${CELESTIA_CHAOS_SEED:-1337} \
		$(PY) -m pytest tests/test_chaos.py --all -q

# The BASELINE benchmark suite on the real TPU chip (one JSON line).
bench:
	$(PY) bench.py

# Transfer-path acceptance run (specs/transfers.md): sliced-sample +
# k=64 node-path + chunked-repair configs with the fault injector armed
# at device.extend/device.repair — pins byte-identical DAH/proof output
# under the async chunked transfer paths. Exits non-zero on any parity
# failure; never writes the bench cache (fault delays poison walls).
bench-transfers:
	$(PY) bench.py --transfers

# Tracing acceptance gate (specs/observability.md, ADR-022). Device
# phase: one k=32 extend under a recording (fenced profiling sampled),
# validates the Chrome trace-event JSON and requires root spans to
# cover >=90% of the traced wall. Fleet phase: two backend PROCESSES
# behind a gateway, primary drained + gateway.route fault-armed, one
# hedged /sample; gates that trace_merge yields ONE valid trace id
# spanning gateway route+hedge and both backends, stage sums within
# 10% of the handler span, and rpc_stage_ms exemplars resolving to
# real spans. CPU-only, under a minute.
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/trace_smoke.py \
		--trace-out /tmp/trace_smoke.json

# Perf-regression gate (specs/slo.md, ADR-014): judge the tracked series
# vs their median±MAD baselines; exits non-zero with a readable table on
# a regression. The bench walls (extend, repair, node-path, transfer,
# fused) come from BENCH_r*.json rounds, none of which is committed
# since PR 21: they are inert until the benchmark PR feeds them from the
# driver's PERF_LEDGER.jsonl. The storm/scenario/soak series still gate.
# Pure ledger math, never touches the accelerator.
bench-gate:
	$(PY) bench.py --check-regressions

# Observability smoke gate (specs/slo.md): boot a devnet node, pin the
# /readyz 503→200 flip across startup, run the DAS prober for a few
# verified cycles, check /healthz + /debug/slo contracts, then prove
# the bench gate passes on committed history and catches a synthetic
# 2x regression. CPU-only, seconds.
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/obs_smoke.py

# Longitudinal-telemetry smoke gate (specs/observability.md
# §Longitudinal telemetry): live .ctts recording over the real
# /metrics wire, a mid-recording node kill/restart absorbed by the
# counter-reset rebase, the drift detector flagging a synthetic leak
# while clearing a flat control, and CRC refusal of a flipped byte.
# CPU-only, crypto-free, seconds warm.
soak-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/soak_smoke.py

# Device runtime ledger smoke (ADR-025): compile/retrace watchdog
# semantics (strict raise before the build, lru eviction is not a
# retrace), the HBM owner attribution flip, busy-ratio sanity, and the
# /debug/device route + device_ledger_* exposition over the real RPC
# handler. Crypto-free, CPU jax, seconds.
devledger-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/devledger_smoke.py

# SDC defense drill (ADR-015): arm a seeded bitflip at every integrity
# injection point (extend output, repair output, transfer chunk), prove
# detection fires before any DAH commit, the host recompute restores
# byte parity, /readyz reflects quarantine, and audits-off is a single
# boolean check. CPU-only, crypto-free, seconds.
sdc-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/sdc_smoke.py

# Overload-resilience drill (specs/serving.md, ADR-016): saturate the
# bounded admission queue through the real RPC stack, pin well-formed
# 503+Retry-After sheds with zero 500s, 504 client deadlines, the
# /readyz not_overloaded flip, graceful mid-storm drain, and a short
# end-to-end `bench.py --das-storm-lite` run with every accepted
# sample proof-verified. CPU-only, crypto-free, seconds.
storm-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/storm_smoke.py

# Ragged cross-height batching gate (specs/serving.md, ISSUE 14):
# mixed-height mixed-k page-table gathers byte-identical to the
# per-height path (one compiled program per page geometry), ragged
# sample documents byte-identical + NMT-verified, and a concurrent
# cross-height burst through the real RPC stack coalescing into a
# single ("sample",) micro-batch that spans multiple heights. CPU-only,
# crypto-free, seconds.
ragged-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/ragged_smoke.py

# Block-store durability drill (specs/store.md, ADR-021): persist a
# chain into the CRC32C-guarded on-disk store through the real node,
# restart over the same directory, and require re-index + serving of
# every persisted height with byte-identical DAHs, NMT-verified
# shares, and disk-backed page reads; a CRC-corrupted page must be
# REFUSED (IntegrityError + SDC detection, never torn bytes) and
# truncated/garbage files quarantined at re-index. CPU-only,
# crypto-free, seconds.
store-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/store_smoke.py

# Crash-consistency gate (specs/store.md §Durability contract,
# ADR-026): the powercut explorer replays a power loss at EVERY prefix
# of the put/compact/re-put/reindex effect trace under a simulated
# page cache (un-fsynced bytes volatile, renames need the parent-dir
# fsync) across lost/applied/torn variants — zero recovery-invariant
# violations allowed — then proves the harness has teeth (the
# no-dirsync world MUST lose acknowledged heights) and drills ENOSPC
# graceful degradation + recovery over the real RPC stack. CPU-only,
# crypto-free, seconds. `--inject-no-dirsync` is the red-path
# self-test: it must FAIL with the missing-height report.
crash-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/crash_smoke.py

# Continuous-batching throughput gate (specs/serving.md, ADR-017): the
# full das-storm — 32 concurrent light clients through the real RPC
# stack, unbatched phase then batched phase on identical config with
# the paged device EDS cache armed under a churn-forcing budget. Every
# accepted sample NMT-verified; fails if the batched phase is not >=2x
# unbatched samples/sec. --ledger feeds storm_ledger.json so `make
# bench-gate` judges the storm_ms_per_accepted_sample trajectory.
# CPU-only, ~15 s.
storm-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --das-storm \
		--seconds 4 --threads 32 --k 8 --paged-budget 98304 \
		--require-speedup 2.0 --ledger storm_ledger.json

# Horizontal-scaling gate (ADR-021): one backend vs a 3-backend fleet
# behind the consistent-hash gateway on identical client load, every
# accepted sample NMT-verified. The require-scaling floor only asserts
# the fleet does not COLLAPSE (the CI box is 1-core, so the phases tie
# there; real scaling headroom needs cores). --ledger feeds the
# lower-is-better gateway_ms_per_accepted_sample series `make
# bench-gate` judges. CPU-only, ~8 s.
gateway-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --gateway-fleet \
		--seconds 3 --threads 16 --k 8 --fleet 3 \
		--require-scaling 0.7 --ledger storm_ledger.json
	JAX_PLATFORMS=cpu $(PY) bench.py --gateway-fleet --processes 3 \
		--seconds 6 --threads 16 --k 8 --heights 2 \
		--require-scaling 0.4 --ledger storm_ledger.json

# Process-fleet smoke gate (ADR-023): two real supervised backend
# subprocesses behind the gateway, SIGKILL one mid-storm — the
# supervisor must reap/backoff/respawn/warm/re-attach it while the
# gateway keeps serving NMT-verified samples (no client ever sees a
# 500), with ONE merged Chrome trace spanning the gateway plus both
# backend PIDs; then a 1000-height chain is compacted to a byte budget
# through the `store compact` CLI with every retained DAH
# byte-identical. Runs under celestia-san: any new runtime finding
# fails the gate. CPU-only, crypto-free, <120 s.
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/fleet_smoke.py --san \
		--trace-out /tmp/fleet_smoke.json

# Fused-kernel smoke gate (ADR-019): fused extend+hash DAH byte-parity
# vs the host oracle at k ∈ {32, 64} (production dispatch + the
# kernels' eager reference math), `auto` picking TPU at every k with an
# accelerator and degrading off a dead one, and vmappable batched-roots chunking
# at k=128. CPU-only, crypto-free, <120 s (repeat runs much faster via
# the persistent XLA compile cache).
kernel-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/kernel_smoke.py

# The ADR-019 step-change configs alone on the real chip: fused
# roots-only vs the XLA roots path vs native at k ∈ {64, 32}; prints
# one JSON line (nothing persists it since PR 21).
bench-fused:
	$(PY) bench.py --fused-kernels

# Scenario-engine smoke gate (specs/scenarios.md, ADR-018): run the
# condensed `smoke` scenario twice on one seed, pin an identical fault
# timeline across runs, the two required SLO breaches (the drill's
# flip and strike MUST surface on the board), all invariant probes,
# the report schema, and the ledger fold. CPU-only, crypto-free,
# well under 120 s.
scenario-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/scenario_smoke.py

# The shipped production-emulation suites (specs/scenarios.md): each
# runs a declarative load+fault timeline through the real RPC stack
# and is judged by the node's own SLO engine plus teardown invariant
# probes — non-zero exit when the breaching-objective set departs the
# scenario's contract or any invariant fails. --ledger feeds
# scenario_ledger.json so `make bench-gate` judges the
# scenario_slo_pass trajectory. CPU-only, crypto-free.
scenario-pfb-storm:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios pfb-storm \
		--ledger scenario_ledger.json

scenario-rolling-outage:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios rolling-outage \
		--ledger scenario_ledger.json

scenario-sdc-under-storm:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios sdc-under-storm \
		--ledger scenario_ledger.json

scenario-rejoin-under-load:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios rejoin-under-load \
		--ledger scenario_ledger.json

# Fleet campaign (ADR-021): a DAS flash crowd through the consistent-
# hash gateway over a 3-node fleet with rolling backend restarts; each
# restarted backend must re-index its on-disk block store and serve
# byte-identical DAHs from disk.
scenario-gateway-fleet:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios gateway-fleet \
		--ledger scenario_ledger.json

scenario-scale-out-under-load:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios \
		scale-out-under-load --ledger scenario_ledger.json

# Disk-pressure campaign (ADR-026): open-loop DAS storm with ENOSPC
# injected at store.write mid-storm — the store must degrade to sticky
# read-only (visible on /readyz and as the REQUIRED store_writable
# breach) while reads keep serving with zero verification failures,
# then recover to writable once space is freed.
scenario-disk-pressure:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios disk-pressure \
		--ledger scenario_ledger.json

# Longitudinal soak (specs/observability.md §Longitudinal telemetry):
# thousands of heights under store compaction churn with the whole run
# recorded to a durable .ctts; judged by Theil-Sen drift detectors
# over the RECORDED series (RSS, fds, store bytes, probe p99) plus
# byte-identity re-verification of samples served `soak_sample_lag`
# heights apart. --soak-ledger feeds soak_ledger.json so `make
# bench-gate` judges the drift-breach trajectory.
scenario-soak:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios soak \
		--ledger scenario_ledger.json --soak-ledger soak_ledger.json \
		--record soak.ctts

# Open-loop offered-load sweep: stepped seeded-Poisson arrival rates
# against /sample with latency measured from the INTENDED send time
# (no coordinated omission) — emits the latency-vs-offered-load curve
# and the knee estimate into the report + soak ledger.
scenario-das-sweep:
	JAX_PLATFORMS=cpu $(PY) -m celestia_tpu.scenarios das-sweep \
		--ledger scenario_ledger.json --soak-ledger soak_ledger.json

# All the suites back to back.
scenarios: scenario-pfb-storm scenario-rolling-outage \
	scenario-sdc-under-storm scenario-rejoin-under-load \
	scenario-gateway-fleet scenario-scale-out-under-load \
	scenario-disk-pressure scenario-soak scenario-das-sweep

# Multi-chip block-pipeline smoke gate (specs/parallel.md §Block
# pipeline): stream blocks through the 3-deep H2D/compute/D2H pipeline
# on a virtual 8-device mesh and gate host-oracle DAH byte-parity for
# every retired block, device-seeded prover parity, per-stage overlap
# (pipelined wall < sum of fenced serial stage walls), and graceful
# mid-stream drain. CPU-only, crypto-free, <120 s warm.
multichip-smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) scripts/multichip_smoke.py

# Scale-out throughput gate: 1 device vs a (1, 8) virtual host mesh
# streaming the same block sequence through the pipeline in scrubbed
# child processes. Gates DAH + device-seeded prover byte-parity across
# phases and a no-collapse scaling floor. k=32 so per-block arithmetic
# dominates the mesh's fixed dispatch/collective overhead (at k=8 that
# overhead is most of the wall and the ratio says nothing); the fused
# int8-psum program holds >= 0.7 even on the 1-core CI box — real
# headroom needs chips. --ledger feeds the higher-is-better
# multichip_blocks_per_sec series `make bench-gate` judges.
multichip-bench:
	JAX_PLATFORMS=cpu $(PY) bench.py --multichip-pipeline \
		--devices 8 --blocks 12 --k 32 \
		--require-scaling 0.7 --ledger storm_ledger.json

# The driver's multichip compile/execute check on a virtual CPU mesh.
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Build the native C++ runtime (CPU codec baseline + sidecar).
# (auto-compiles on first import; this just forces it eagerly)
native:
	$(PY) -c "from celestia_tpu import native; assert native.available(); print('native runtime ready')"
