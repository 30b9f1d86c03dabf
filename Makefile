# Test matrix (reference parity: test_local.sh / test.sh /
# test_kubernetes.sh run one suite against three backend tiers).

PYTEST ?= python -m pytest tests/ -q

.PHONY: test stest test-all lint bench bench-store bench-telemetry \
	bench-sched bench-transport bench-cluster bench-recovery \
	bench-accounting bench-check bench-scale bench-ici \
	bench-autonomy bench-stream bench-serve bench-slo weakscale docs \
	chaos

# Tier 1: local backend (subprocess jobs)
test:
	$(PYTEST)

# Tier 2: simulated multi-host pod slice (host agents on localhost —
# the reference's Docker-backend role). Runs under pytest's DEFAULT
# fd capture: the round-4 SIGABRT that forced a --capture=sys
# mitigation stopped reproducing after the poison-chunk crash-loop
# fix and the stray-agent cleanup (3 green full-suite runs recorded).
stest:
	FIBER_BACKEND=tpu FIBER_TPU_HOSTS=sim:2 $(PYTEST)

# Tier 3 runs on a real pod slice: start agents with `fiber-tpu up`,
# then FIBER_BACKEND=tpu FIBER_TPU_HOSTS=host1,host2 make test

test-all: test stest

# Chaos tier (docs/robustness.md): the seeded fault-injection suite —
# health-plane unit tests once, then the injection scenarios (including
# the slow soaks) under fixed seeds, plus the streaming-data-plane
# drills re-run under a fresh seed with a deliberately tiny default
# admission window (docs/streaming.md). The fast scenarios also run
# un-marked in tier 1; this target is the full deterministic sweep.
chaos:
	python -m pytest tests/test_health.py -q
	FIBER_CHAOS_SEED=101 python -m pytest tests/test_chaos.py -q
	FIBER_CHAOS_SEED=202 python -m pytest tests/test_chaos.py -q
	FIBER_CHAOS_SEED=303 python -m pytest tests/test_chaos.py -q
	FIBER_CHAOS_SEED=404 FIBER_TRANSPORT_IO=shm \
		python -m pytest tests/test_chaos.py -q
	FIBER_CHAOS_SEED=505 FIBER_POLICY_VERIFY_S=0.2 \
		FIBER_POLICY_COOLDOWN_S=0 \
		python -m pytest tests/test_chaos.py -q
	FIBER_CHAOS_SEED=606 FIBER_STREAM_WINDOW=4 \
		python -m pytest tests/test_stream.py -q
	FIBER_CHAOS_SEED=707 python -m pytest tests/test_serve_daemon.py \
		-q -m slow

# FIBER_BENCH_ENFORCE: fail loudly when the 1 ms host-pool point
# drifts past its budget (the driver's plain `python bench.py` only
# records it). Device mode: needs the chip, exits non-zero without one
# (`python bench.py --platform cpu` rehearses on the CPU mesh).
bench:
	FIBER_BENCH_ENFORCE=1 python bench.py

# Object-store data-plane microbench (docs/objectstore.md): local
# put/get + wire fetch throughput, and broadcast bytes-per-task with
# the by-reference pool path on vs off. Pure host plane — runs on the
# CPU platform; JSON-lines record lands next to the driver's BENCH
# files.
bench-store:
	JAX_PLATFORMS=cpu python bench.py --store --record | tee BENCH_store.json

# Telemetry-plane overhead gate (docs/observability.md): small-task pool
# throughput with telemetry off / metrics-only / full tracing / +flight
# recorder / +continuous monitor / +device telemetry plane / +sampling
# profiler; FAILS when the tracing, flightrec, monitor, device or
# profiler arm exceeds 5% overhead on the microbench. The record lands
# in BENCH_telemetry.json either way.
bench-telemetry:
	JAX_PLATFORMS=cpu python bench.py --telemetry --record > BENCH_telemetry.json; \
	rc=$$?; cat BENCH_telemetry.json; exit $$rc

# Accounting-plane gate (docs/observability.md "Resource accounting"):
# small-task pool throughput with the cost ledger fully on (billing
# keys on every envelope, per-frame wire attribution, worker cost
# frames) vs telemetry off; FAILS past 5% overhead. The focused record
# lands in BENCH_accounting.json (the full bench-telemetry run also
# carries an accounting arm in BENCH_telemetry.json); --record appends
# the trajectory to BENCH_history.jsonl for bench-check.
bench-accounting:
	JAX_PLATFORMS=cpu python bench.py --accounting --record > BENCH_accounting.json; \
	rc=$$?; cat BENCH_accounting.json; exit $$rc

# Policy-plane (autonomous operations) gate (docs/observability.md
# "Autonomous operations"): per-fault-class anomaly -> action ->
# outcome chain drills (every class must leave a complete
# cause_id-linked flight chain), a policy-enabled chaos soak that must
# lose zero tasks, and the engine's on-but-idle pool overhead (must
# stay <= 5%). The record lands in BENCH_autonomy.json either way.
bench-autonomy:
	JAX_PLATFORMS=cpu python bench.py --autonomy --record > BENCH_autonomy.json; \
	rc=$$?; cat BENCH_autonomy.json; exit $$rc

# Bench-trajectory regression check: compares the latest recorded value
# of every gated metric in BENCH_history.jsonl (written by --record)
# against the best ever recorded; fails on a >10% regression.
bench-check:
	python scripts/bench_check.py

# Scheduler-plane gate (docs/scheduling.md): uniform-workload overhead
# of the adaptive scheduler vs fifo (must stay within 5%) and straggler
# speculation on vs off under one chaos-slowed worker (must be >= 1.3x
# faster). The record lands in BENCH_sched.json either way.
bench-sched:
	JAX_PLATFORMS=cpu python bench.py --sched --record > BENCH_sched.json; \
	rc=$$?; cat BENCH_sched.json; exit $$rc

# Transport I/O-core gate (docs/transport.md): selector event loop vs
# thread-per-connection on small-frame frames/sec (must be >= 1.5x),
# large-frame throughput (must stay >= 0.95x) and a 64-worker fan-in
# (CPU seconds + transport thread count). The record lands in
# BENCH_transport.json either way.
bench-transport:
	JAX_PLATFORMS=cpu python bench.py --transport --record > BENCH_transport.json; \
	rc=$$?; cat BENCH_transport.json; exit $$rc

# Master scale-out gate (docs/transport.md, docs/architecture.md):
# a million tiny tasks through hierarchical per-host dispatch + shm
# transport vs the recorded single-master selector baseline. FAILS
# when master dispatch capacity (tasks per master-CPU-second) falls
# under 3x the baseline or master CPU-seconds-per-task exceeds 0.5x.
# The record lands in BENCH_scale.json either way.
bench-scale:
	JAX_PLATFORMS=cpu python bench.py --scale --record > BENCH_scale.json; \
	rc=$$?; cat BENCH_scale.json; exit $$rc

# Serving-tier gate (docs/serving.md): one long-lived daemon, N
# tenants x M concurrent jobs over the authenticated channel. FAILS
# when the WDRR fairness ratio across equal tenants exceeds 1.6x, when
# the over-budget tenant is not throttled-then-PREEMPTED (parked
# resumable, chunks reclaimed), when a SIGKILL'd client's or SIGKILL'd
# daemon's jobs lose a task or double-bill one (exactly-once
# tasks + tasks_restored reconciliation per disjoint tenant record),
# or when a job on standby warm workers takes more than 0.5x the cold
# Pool-spawn wall. The record lands in BENCH_serve.json either way.
bench-serve:
	JAX_PLATFORMS=cpu python bench.py --serve --record > BENCH_serve.json; \
	rc=$$?; cat BENCH_serve.json; exit $$rc

# SLO plane + observability archive gate (docs/observability.md "SLOs
# and the archive"): FAILS when running the serve workload with the
# archive + SLO plane armed costs more than 1.05x the plain daemon,
# when injected slow-worker chaos does not breach `slo_burn` with a
# complete cause_id-linked anomaly -> policy action -> outcome chain
# in the archive, when a SIGKILL'd + restarted daemon loses its burn-
# window state (archive replay), or when `history` queries return any
# torn record. The record lands in BENCH_slo.json either way.
bench-slo:
	JAX_PLATFORMS=cpu python bench.py --slo --record > BENCH_slo.json; \
	rc=$$?; cat BENCH_slo.json; exit $$rc

# Streaming data plane gate (docs/streaming.md): a million tiny tasks
# through a windowed imap_unordered over a generator — nothing
# materialized anywhere. FAILS when the run completes < 1M tasks, when
# master peak RSS grows > 1.5x across a 100x task-count increase
# (retention must be O(stream_window)), or when streamed throughput
# falls under 0.9x a materialized `map` of the same workload (best-of-2
# subprocess arms — the window must keep the cluster fed). The record
# lands in BENCH_stream.json either way.
bench-stream:
	JAX_PLATFORMS=cpu python bench.py --stream --record > BENCH_stream.json; \
	rc=$$?; cat BENCH_stream.json; exit $$rc

# Full-stack macro bench (docs/observability.md, ROADMAP item 5): the
# whole stack at once — simulated multi-host pod, 8MB per-generation
# store broadcasts, straggler + worker-kill chaos, full tracing +
# flight recorder. FAILS on an evals/s or bytes-per-task regression,
# on an explain misattribution of the injected straggler, or on a
# missing postmortem bundle after the chaos kill; archives a Perfetto
# trace + flight-event artifact per run into RUNS/. The record lands
# in BENCH_cluster.json either way.
bench-cluster:
	JAX_PLATFORMS=cpu python bench.py --cluster --record > BENCH_cluster.json; \
	rc=$$?; cat BENCH_cluster.json; exit $$rc

# Device-tier data plane gate (docs/objectstore.md "Device tier"):
# repeat-generation param resolutions must come out of the
# device-resident store with ~zero wire bytes, and the collective
# broadcast path (one mesh replication, accounted under the `ici`
# transfer site) must beat the tier-off baseline that re-pays the
# host->mesh transfer every call by >= 1.3x wall. Runs on the
# forced-host-device CPU mesh; the record lands in BENCH_ici.json
# either way.
bench-ici:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	JAX_PLATFORMS=cpu python bench.py --ici --record > BENCH_ici.json; \
	rc=$$?; cat BENCH_ici.json; exit $$rc

# Durable-map recovery gate (docs/robustness.md): write-ahead ledger
# overhead on the no-crash path (must stay <= 5%) and resume wall-time
# proportional to the REMAINING tasks of a partially-journaled job,
# with an exactly-once restored/executed reconciliation. The record
# lands in BENCH_recovery.json either way.
bench-recovery:
	JAX_PLATFORMS=cpu python bench.py --recovery --record > BENCH_recovery.json; \
	rc=$$?; cat BENCH_recovery.json; exit $$rc

# Weak-scaling record over 1/2/4/8-device sim meshes (fused ES,
# population scaled with devices) + strong curve (constant total pop)
# -> RUNS/weak_scaling_r05.json. On chip the same entry records real scaling.
weakscale:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	JAX_PLATFORMS=cpu python __graft_entry__.py --weak-scaling

lint:
	python -m compileall -q fiber_tpu examples bench.py __graft_entry__.py \
		chip_smoke.py
	python scripts/check_pycache.py fiber_tpu examples tests scripts
	python scripts/check_docs_nav.py

# Docs site (reference parity: built mkdocs site). Prefers mkdocs when
# installed; otherwise the zero-dependency renderer (same mkdocs.yml nav).
docs:
	@if command -v mkdocs >/dev/null 2>&1; then mkdocs build; \
	else python scripts/build_docs.py; fi
