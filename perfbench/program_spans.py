"""What the readers of the program's own spans share.

The program keeps finished spans in ``fiber_tpu.telemetry.tracing.SPANS``
(dicts with ``name``, ``start_ns``, ``end_ns`` on the epoch's clock,
``parent``, and what the span records). The store is read where it lives:
the harness's record carries none of it. A hand-built record (the tests)
brings its spans under ``"program_spans"``. A program without such spans,
as the commit before PR 25, gives every reader nothing to read.
"""
import sys

#: the span around one call of the program's step function, by runner kind
CALL_SPANS = ("lm.train_step", "es.run_fused")
#: JAX's compile phases as the program's listener stores them
COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.backend_compile")


def spans_of(run):
    """Every finished span with epoch nanoseconds, oldest first."""
    if "program_spans" in run:
        spans = run["program_spans"]
    else:
        tracing = sys.modules.get("fiber_tpu.telemetry.tracing")
        if tracing is None or not hasattr(tracing, "SPANS"):
            return []
        spans = tracing.SPANS.snapshot()
    return [s for s in spans if "start_ns" in s and "end_ns" in s]


def window_calls(run, spans):
    """The window's call spans: the last ``len(call_times)`` of that name
    (the reference, which runs after the window, calls nothing of the
    program). None where the store holds fewer."""
    calls = [s for s in spans if s["name"] in CALL_SPANS]
    n = len(run["call_times"])
    if n == 0 or len(calls) < n:
        return None
    return calls[-n:]


def setup_spans(run, *names):
    """The spans of these names that ended before the window's first call
    began; None where the program left no call span."""
    spans = spans_of(run)
    calls = window_calls(run, spans)
    if calls is None:
        return None
    start = calls[0]["start_ns"]
    return [s for s in spans if s["name"] in names and s["end_ns"] <= start]


def setup_seconds(run, name):
    """Summed seconds of set-up's spans called ``name``, as ``compile_s``
    counts them. The program folds a trace that ran inside another phase
    (a jitted function met while another is traced; what lowering a Pallas
    kernel traces) into that phase's span, as ``nested_s``; JAX's duration
    events, which ``compile_s`` adds up, count such a trace for itself and
    again inside the outer phase's duration. So ``jax.trace`` takes the
    ``nested_s`` of every phase's spans besides its own durations. None
    where set-up left no compile span at all (the program has no such
    listener)."""
    phases = setup_spans(run, *COMPILE_SPANS)
    if not phases:
        return None
    seconds = sum(s["end_ns"] - s["start_ns"]
                  for s in phases if s["name"] == name) / 1e9
    if name == "jax.trace":
        seconds += sum(s.get("nested_s", 0.0) for s in phases)
    return seconds
