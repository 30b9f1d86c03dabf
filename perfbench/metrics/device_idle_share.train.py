"""device_idle_share.train: 1 - (union of the device's op intervals) / traced
window, on the idlest chip."""
import trace_reduce


def read(run):
    share = trace_reduce.idle_share(run["trace"])
    return None if share is None else 100.0 * share
