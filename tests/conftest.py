"""Shared test setup.

* Forces the CPU platform with 8 virtual devices so mesh/sharding tests run
  anywhere (the driver separately dry-runs the multi-chip path).
* Leak-check fixture (reference parity: the autouse fixture asserting
  ``fiber.active_children() == []`` before/after every test —
  tests/test_pool.py:75-84 etc. in the reference): every test must clean up
  every process it started.
"""

import os
import time

# Hard-set (not setdefault): tests must run on the virtual 8-device CPU
# mesh whatever the shell exported. Nothing preloads jax into the
# interpreter, so setting the environment before the first jax import
# is enough — for this process and for every child it starts.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# XLA CPU's in-process collective rendezvous abort()s the whole process
# when a starved participant thread misses its terminate deadline (the
# core-dump-verified root cause of an earlier sim-tier SIGABRT) — on a
# ONE-core box a loaded suite can starve any of the 8 virtual devices'
# threads. The shared policy makes a starved collective a slow test,
# never a dead interpreter.
from fiber_tpu.utils.misc import (  # noqa: E402
    ensure_cpu_collective_timeout_flags,
)

ensure_cpu_collective_timeout_flags()
os.environ.setdefault("FIBER_BACKEND", "local")
os.environ.setdefault("FIBER_LOG_FILE", "/tmp/fiber_tpu_test.log")

# Agent file staging (code distribution) must never write the operator's
# real ~/.fiber_tpu from tests.
import tempfile  # noqa: E402

os.environ.setdefault(
    "FIBER_AGENT_STAGING", tempfile.mkdtemp(prefix="fiber-test-staging-")
)
# The suite's compiled programs go to a directory of this run's own, not
# to the checkout's .jax_cache: XLA:CPU entries are specific to the CPU
# they were compiled on, and a checkout outlives the machine it ran on.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    tempfile.mkdtemp(prefix="fiber-test-jaxcache-"),
)

import pytest  # noqa: E402

import fiber_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/soak tests (excluded from tier 1; "
        "run via `make chaos`)",
    )


@pytest.fixture(autouse=True)
def _policy_restore():
    """The policy engine's remediations mutate process-wide knobs (TX
    high-water, speculation quantiles, WDRR weights, compile-cache
    pins). ``WATCHDOG.clear()`` bypasses the clear-edge reverts, so
    every test ends with an explicit engine reset — a leaked
    remediation must not outlive the test that provoked it."""
    yield
    from fiber_tpu.telemetry.policy import POLICY

    POLICY.reset()


@pytest.fixture(autouse=True)
def leak_check():
    assert fiber_tpu.active_children() == [], "leaked processes from earlier test"
    yield
    deadline = time.time() + 15
    while fiber_tpu.active_children() and time.time() < deadline:
        time.sleep(0.05)
    leftover = fiber_tpu.active_children()
    for proc in leftover:
        try:
            proc.terminate()
            proc.join(5)
        except Exception:
            pass
    assert leftover == [], f"test leaked processes: {leftover}"
