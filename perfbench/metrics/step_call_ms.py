"""step_call_ms: median length of the window's ``lm.train_step`` spans: the
step's own call (flattening, the launch, whatever the runtime makes the
caller wait for), timed inside the program; the feed and the wait for the
loss are outside it. All the window's calls. No account on the spans, or
under 8 calls: nothing."""
import host_account


def read(run):
    return host_account.call_ms(run)
