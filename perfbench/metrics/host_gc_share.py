"""host_gc_share: the share of the window's periods (start of one
``lm.train_step`` span to the next one's) that the process's collector ran:
the sum of the spans' ``gc_ns`` and ``since_gc_ns`` over the sum of the
periods."""
import host_account


def read(run):
    return host_account.gc_share(run)
