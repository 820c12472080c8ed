# Single entry point for CI / local development.
#
#   make test         tier-1 verify: the full suite (what the roadmap gates on)
#   make test-fast    quick lane: skips tests marked `slow`
#   make test-4dev    test-fast on a forced 4-device host platform (the sweep
#                     partition layer shards every grid over a 4-wide mesh,
#                     and the serving tests multiplex tenants over slot-
#                     sharded resident programs)
#   make test-faults  the fault-injection suite (tests/test_faults.py) on the
#                     default platform AND the forced 4-device platform —
#                     tenant quarantine/rollback isolation, crash-safe
#                     checkpoint durability (kill-resume), shrink-devices
#   make test-fleet   the fleet-scale suite (tests/test_fleet.py): 2-D mesh
#                     bit-identity across shapes (forced 4-device subprocess),
#                     seed-share on/off equivalence, shard packing, and the
#                     2-local-process jax.distributed scaffolding
#   make test-pallas  the Pallas kernel on the chip path (order_fixed_dense,
#                     the agent's Q-network contractions) in interpret mode
#                     on CPU, pinned bit-identical against `dqn.dense`
#   make bench-smoke  smallest benchmark slice (fig5 + the engine perf record
#                     + the continual warm-vs-cold record + the multi-tenant
#                     serving record + the fault-tolerance record + the
#                     topology-axis record + the fleet-scale record: writes
#                     bench_out/BENCH_engine.json, BENCH_continual.json,
#                     BENCH_serving.json, BENCH_faults.json,
#                     BENCH_topology.json and BENCH_fleet.json)
#   make bench-continual  just the continual-stream warm-vs-cold benchmark
#   make bench-serving    just the multi-tenant serving benchmark (64 tenant
#                         streams through 16 resident slot programs)
#   make bench-faults     just the fault-tolerance benchmark (recovery drills
#                         + the divergence guard's no-fault overhead)
#   make bench-topology   just the topology-axis benchmark (per-interconnect
#                         learned-AIMM vs baseline + mesh warm-grid guard)
#   make bench        every benchmark figure (BENCH_FULL=1 for paper scale)
#   make profile      JAX profiler trace of one batched grid -> bench_out/profile

PY ?= python
# src for the repro package, repo root for the benchmarks package
PYTHONPATH := src:.$(if $(PYTHONPATH),:$(PYTHONPATH),)
export PYTHONPATH

.PHONY: test test-fast test-4dev test-faults test-fleet test-pallas \
	bench-smoke bench-continual bench-serving bench-faults bench-topology \
	bench-fleet bench profile

test:
	$(PY) -m pytest -x -q

test-fast:
	$(PY) -m pytest -x -q -m "not slow"

# Forced 4-device host platform: the whole fast lane sharded, including the
# topology equivalence tests (tests/test_topology.py runs the mixed-topology
# grid against serial per-lane runs on the 4-wide lane mesh).
test-4dev:
	XLA_FLAGS="--xla_force_host_platform_device_count=4 $$XLA_FLAGS" \
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q -m "not slow"

# The fault-injection suite on both platforms: single-device and a forced
# 4-device host (the quarantine/rollback isolation and the shrink-devices
# re-mesh path are only fully exercised when lanes are device-sharded).
test-faults:
	$(PY) -m pytest -x -q tests/test_faults.py
	XLA_FLAGS="--xla_force_host_platform_device_count=4 $$XLA_FLAGS" \
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q tests/test_faults.py

# Fleet-scale suite: includes the slow forced-4-device and 2-process
# subprocess tests regardless of the parent platform.
test-fleet:
	$(PY) -m pytest -x -q tests/test_fleet.py

# Pallas lane: the order-fixed dense kernel (the one Pallas kernel a chip
# program runs) in interpret mode on CPU, pinned against `dqn.dense`.
test-pallas:
	$(PY) -m pytest -x -q tests/test_dqn.py -k order_fixed

bench-smoke:
	BENCH_ONLY=fig5,engine,continual,serving,faults,topology,fleet $(PY) benchmarks/run.py

bench-continual:
	BENCH_ONLY=continual $(PY) benchmarks/run.py

bench-serving:
	BENCH_ONLY=serving $(PY) benchmarks/run.py

bench-faults:
	BENCH_ONLY=faults $(PY) benchmarks/run.py

bench-topology:
	BENCH_ONLY=topology $(PY) benchmarks/run.py

bench-fleet:
	BENCH_ONLY=fleet $(PY) benchmarks/run.py

bench:
	$(PY) benchmarks/run.py

profile:
	$(PY) benchmarks/profile_grid.py
