"""collective_exposed_share: on chip 0, the time in collective ops (the
ring's collective-permutes, the gradient all-reduce) during which no
compute op ran, over the traced window. No collective: nothing."""
import trace_reduce


def read(run):
    trace = run["trace"]
    if trace is None or not trace.device:
        return None
    exposed = trace_reduce.exposed_collective_seconds(trace, min(trace.device))
    if exposed is None:
        return None
    return 100.0 * exposed / trace_reduce.window_seconds(trace)
