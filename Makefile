# Test/bench entry points (the reference pins quality with Makefile:3-7 —
# fmt + clippy + `cargo test` under a quickcheck budget; here the suite +
# dryrun + bench are the equivalent gates).
.PHONY: test test-fast test-chaos test-recovery test-restart test-overload test-fuzz test-devicefault test-device-stripped dryrun bench chip-smoke bench-smoke trace-smoke critpath-smoke overload-smoke fuzz-smoke failover-smoke telemetry-smoke scenario-smoke

test:
	python -m pytest tests/ -x -q

# the CI-shrunk load (tests/harness.py COMMANDS_PER_CLIENT, hypothesis
# max_examples both scale down under CI=true)
test-fast:
	CI=true python -m pytest tests/ -x -q -m "not slow"

# the full fault-injection matrix (crash x loss x protocol, including the
# `slow`-marked sweep rows tier-1 skips)
test-chaos:
	python -m pytest tests/test_faults.py -x -q -m chaos

# the recovery slice: per-dot MPrepare/MPromise recovery (EPaxos/Atlas/
# Newt AND Caesar's (clock, preds) synod), noop commits, FPaxos leader
# failover (sim + TCP), and the crashed-coordinator model checker rows
# (Caesar included, n=3/f=1 exhaustive)
test-recovery:
	python -m pytest tests/ -x -q -m recovery

# the restart-and-rejoin slice: WAL durability edges, snapshot/restore,
# crash-restart chaos rows (restored tolerance, all five protocols —
# Caesar MSync records + FPaxos MSlotSync slot catch-up included), WAL
# tail-replay rows, TCP WAL recovery + on_peer_up revival, the FPaxos
# leader-kill 3-phase TCP row
test-restart:
	python -m pytest tests/ -x -q -m restart

# the overload-control slice: bounded queues + watermark backpressure,
# admission sheds + client backoff/deadlines, open-loop bursts, the
# SlowProcess nemesis, and the queue-gauge metrics export
test-overload:
	python -m pytest tests/ -x -q -m overload

# the chaos-fuzzing + consistency-audit slice: auditor verdicts on
# hand-built histories, digest divergence detection (incl. the TCP
# forked-replica row), fuzzer determinism, shrinker minimality, and the
# GC-straggler mutation self-test
test-fuzz:
	python -m pytest tests/ -x -q -m fuzz

# close the tier-1 coverage hole on the pinned jax: run every
# jax-version-guarded device test module (discovered by guard scan —
# tests/test_device_runner.py today; new guarded device suites ride
# along automatically) from guard-stripped copies (the guard exists
# because jaxlib 0.4.x segfaults flakily while tracing the drivers' scan
# bodies) in their own pytest processes, the way PR 6 validated its
# changes.  On jax >= 0.5 the regular suite already covers the modules
# and this is a no-op
test-device-stripped:
	python scripts/run_device_stripped.py

dryrun:
	python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

# full mode needs the chip: it exits non-zero unless jax finds the TPU
# (hostenv.py's platform rule) or when any row raised.  `make chip-smoke`
# is the quick proof that the served path starts there; both run through
# the chip tool, one process per chip
bench:
	python bench.py

chip-smoke:
	python chip_smoke.py

# tiny CPU-sized bench rows (table + Newt serving), in-process: catches
# import breaks and order-of-magnitude regressions in the bench seams
# without a chip — the per-push CI slice runs this
bench-smoke:
	python bench.py --smoke

# observability gate: tiny traced sim, byte-identical same-seed span
# logs, Perfetto conversion + stage-latency report all validate — the
# per-push CI slice runs this next to bench-smoke
trace-smoke:
	python scripts/trace_smoke.py

# critical-path gate: localhost 3-process EPaxos with tracing — >= 99%
# of sampled spans stitch across processes, every attribution vector
# telescopes exactly to reply-submit, a SlowProcess nemesis is named
# the dominant quorum-wait contributor, and a forced
# StalledExecutionError dumps flight-recorder black boxes from every
# live process that the same correlator stitches — the per-push CI
# slice runs this next to trace-smoke
critpath-smoke:
	python scripts/critpath_smoke.py

# overload gate: tiny CPU open-loop burst at ~2x saturation against a
# tight admission limit — bounded queue depths, typed sheds reaching
# clients, nonzero goodput while shedding, post-burst latency back to
# baseline — the per-push CI slice runs this next to bench/trace-smoke
overload-smoke:
	python scripts/overload_smoke.py

# live-telemetry gate: localhost EPaxos cluster with the /metrics
# exposition endpoints live — scrape twice mid-run (well-formed, required
# key set, monotonic counters), windowed series files parse, `obs watch`
# renders, and the perf-regression gate trips on an injected 2x latency
# (plus a report-only `bench.py --regress` over the smoke row when
# bench-smoke left one behind) — the per-push CI slice runs this
telemetry-smoke:
	python scripts/telemetry_smoke.py

# chaos-fuzz gate: seeded fault-schedule sweep with composed nemeses
# over EVERY protocol x EVERY nemesis class (fixed seed set + targeted
# Caesar-crash and FPaxos-restart rows, budget-checked), auditor-clean +
# byte-identical determinism (same seed => same plan/trace/verdict).
# Set FANTOCH_FUZZ_BUDGET_S for a longer soak (nightly — the soak
# samples all five protocols with crash AND restart nemeses un-gated) —
# the per-push CI slice runs the fixed set next to
# bench/trace/overload-smoke
fuzz-smoke:
	python scripts/fuzz_smoke.py

# the accelerator fault-tolerance slice: DeviceFault nemesis (hang /
# raise / corrupt) against all three device planes, dispatch deadlines,
# shadow-check corruption attribution, host-twin failover bit-for-bit
# parity, exactly-once pipeline replay, and online rebuild + cutback
test-devicefault:
	python -m pytest tests/ -x -q -m devicefault

# accelerator failover gate: a seeded device hang against a live plane
# — the typed DeviceFailedError is observed, host-twin goodput stays
# nonzero while degraded, cutback costs exactly one counted re-upload,
# and the faulted run's output is bit-for-bit the fault-free run's —
# the per-push CI slice runs this next to fuzz-smoke
failover-smoke:
	python scripts/failover_smoke.py

# scenario-observatory gate (r20): a declarative spec expands
# byte-identically, a 3-point offered-rate ladder (sim timeline, EPaxos
# n=3) runs to a DETECTED saturation knee with p50/p95/p99 + goodput
# per point, curves.json round-trips through plot/db, the PNG renders
# headless, and `obs curves` passes the spec's SLO verdicts
scenario-smoke:
	python scripts/scenario_smoke.py
