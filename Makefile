# Test matrix (reference parity: test_local.sh / test.sh /
# test_kubernetes.sh run one suite against three backend tiers).

PYTEST ?= python -m pytest tests/ -q

.PHONY: test stest test-all lint docs chaos

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

lint:
	python -m compileall -q fiber_tpu examples __graft_entry__.py \
		chip_smoke.py
	python scripts/check_pycache.py fiber_tpu examples tests scripts
	python scripts/check_docs_nav.py

# Docs site (reference parity: built mkdocs site). Prefers mkdocs when
# installed; otherwise the zero-dependency renderer (same mkdocs.yml nav).
docs:
	@if command -v mkdocs >/dev/null 2>&1; then mkdocs build; \
	else python scripts/build_docs.py; fi
