"""jax_trace_s: seconds of set-up that JAX spent in tracing jitted functions to jaxprs in Python.
The sum of the program's ``jax.trace`` spans (one per
``/jax/core/compile/*`` event, stored by ``fiber_tpu.telemetry.device`` with
JAX's own start, end and ``fun_name``) that ended before the window's first
call span began. The three phases together are what ``compile_s`` lumps. No
such span: nothing."""
import program_spans


def read(run):
    return program_spans.setup_seconds(run, "jax.trace")
