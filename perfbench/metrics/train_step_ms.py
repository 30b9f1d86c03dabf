"""train_step_ms: median host-clock time of one optimizer step, from its
dispatch to its loss being ready (the next tokens are drawn meanwhile)."""
import statistics


def read(run):
    return 1e3 * statistics.median(run["call_times"])
