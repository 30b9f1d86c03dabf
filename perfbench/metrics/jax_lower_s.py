"""jax_lower_s: seconds of set-up that JAX spent in lowering jaxprs to MLIR modules.
The sum of the program's ``jax.lower`` spans (one per
``/jax/core/compile/*`` event, stored by ``fiber_tpu.telemetry.device`` with
JAX's own start, end and ``fun_name``) that ended before the window's first
call span began. The three phases together are what ``compile_s`` lumps. No
such span: nothing."""
import program_spans


def read(run):
    return program_spans.setup_seconds(run, "jax.lower")
