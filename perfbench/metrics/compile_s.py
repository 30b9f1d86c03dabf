"""compile_s: seconds JAX spent tracing, lowering and compiling up to the end
of set-up, as the program's own counter has them
(``fiber_tpu.telemetry.device.DEVICE.snapshot()["compile_seconds"]``)."""


def read(run):
    return run["compile_s"]
