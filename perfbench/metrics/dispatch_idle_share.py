"""dispatch_idle_share: the share of the traced window in which chip 0 runs
no op (``trace_reduce.busy_intervals``) *and* the host is inside one of the
program's ``lm.train_step`` spans: the device idle time that the step's own
call is charged with, as distinct from the harness's feed and wait.

The device trace counts from the start of the profiler's session and the
program's spans are on the epoch's clock, and ``trace_reduce.load_xplane``
keeps only the harness's three spans of the host plane. So the offset
between the two clocks is taken from the calls both sides hold: traced call
k's ``dispatch`` event (the harness's, on the trace's clock) encloses the
program's ``lm.train_step`` span k (on the epoch's), and the median of the
differences of their starts is the offset: microseconds of error against
gaps of 5-15 ms. When ``load_xplane`` keeps the program's spans (they are
in the capture too, under the same names) the offset goes. No such span, or
no trace: nothing."""
import statistics

import program_spans
import trace_reduce

CALL_SPAN = "lm.train_step"
HARNESS_SPAN = "dispatch"


def read(run):
    trace = run["trace"]
    if trace is None or not trace.device:
        return None
    calls = program_spans.window_calls(run, program_spans.spans_of(run))
    if calls is None or calls[0]["name"] != CALL_SPAN:
        return None
    theirs = sorted((e for e in trace.host if e.name == HARNESS_SPAN),
                    key=lambda e: e.start)
    pairs = list(zip(calls, theirs))     # the traced calls are the first
    if not pairs:
        return None
    offset = int(statistics.median(
        span["start_ns"] - event.start for span, event in pairs))
    window = trace_reduce.window_of(trace)
    inside = trace_reduce.merge(trace_reduce.clip(
        ((span["start_ns"] - offset, span["end_ns"] - offset)
         for span, _ in pairs), window))
    idle = trace_reduce.subtract(
        [window], trace_reduce.busy_intervals(trace, min(trace.device)))
    charged = trace_reduce.subtract(idle, trace_reduce.subtract(idle, inside))
    return 100.0 * trace_reduce.measure(charged) / (window[1] - window[0])
