"""step_call_blocked_ms: the part of the step's call in which the calling
thread was off the CPU: ``step_call_ms`` times 1 - sum(``cpu_ns``) /
sum(length) over the window's ``lm.train_step`` spans (summed, because the
chip's machine keeps thread CPU time by the 10 ms tick). Where it is most of
``step_call_ms`` the call waits (the allocator, the runtime); where it is
near 0 the call computes. Listed for the cells whose calls sum to tens of
ticks a window (the three ``sc2_3b_*``, about 0.45 s): where they sum to a
few, the reading is the ticks' noise, and may read below 0. No account on
the spans, or under 8 calls: nothing."""
import host_account


def read(run):
    return host_account.call_blocked_ms(run)
