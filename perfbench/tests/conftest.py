"""The harness's own tests run on the CPU, on four virtual devices (the ring
cell's rehearsal needs them). Both are set before JAX starts a backend."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()


import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def stop_the_programs_sampler_thread():
    """The program's daemon sampler thread can abort the interpreter as it
    exits (PERF.md, Open questions); the harness stops it before it returns,
    and so does a test session that imported the program."""
    yield
    import sys

    if "fiber_tpu.telemetry" in sys.modules:
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        import run as harness

        harness.stop_program_threads()
