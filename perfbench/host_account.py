"""What the readers of the host's account on the step's call spans share.

Since PR 35 every ``lm.train_step`` span says what its calling thread did
inside the call (``cpu_ns``, ``gc_ns``, ``gc_runs``) and between the end of
its last call and this one's start (the same names under ``since_``;
``fiber_tpu/telemetry/device.py`` ``StepCalls``). The readers take all the
window's call spans, the whole 15 s and not the traced calls alone, and give
nothing where the spans lack the fields (the commit before PR 35) or the
window holds under ``MIN_CALLS`` calls.

A period runs from one call's start to the next one's. In a traced run the
harness stops the profiler after call ``trace_calls`` and leaves the seconds
that writing the capture took out of its window (``run.py``
``timed_window``); so do the readers: the period that begins with that call,
and the next call's ``since_*``, are left out.
"""
import statistics

import program_spans

CALL_SPAN = "lm.train_step"
MIN_CALLS = 8
#: a period is stalled by what it has over this many medians
STALL_OVER = 1.25


def calls_of(run):
    """The window's ``lm.train_step`` spans if every one carries the
    account, else None."""
    calls = program_spans.window_calls(run, program_spans.spans_of(run))
    if calls is None or len(calls) < MIN_CALLS:
        return None
    if any(s["name"] != CALL_SPAN or "cpu_ns" not in s for s in calls):
        return None
    return calls


def periods_of(run, calls):
    """[(the call that begins the period, the call that ends it, its
    nanoseconds)], without the period in which the capture was written."""
    written = (int(run["traffic"]["trace_calls"]) - 1
               if run.get("trace") is not None else None)
    return [(a, b, b["start_ns"] - a["start_ns"])
            for k, (a, b) in enumerate(zip(calls, calls[1:]))
            if k != written]


def call_ms(run):
    """Median length of the call span."""
    calls = calls_of(run)
    if calls is None:
        return None
    return statistics.median(s["end_ns"] - s["start_ns"] for s in calls) / 1e6


def call_blocked_ms(run):
    """The median call's length times the share of the calls' time in which
    the thread was off the CPU, 1 - sum(cpu_ns) / sum(length). Summed, not
    call by call: a kernel that keeps a thread's CPU time by the tick (10 ms
    on the chip's machine) reads 0 or 10 ms for a call of 6, and only the sum
    over the window's calls is an unbiased reading of it. Where the calls sum
    to a few ticks the reading is the ticks' noise, and ticks that overcount
    read below 0: the metric lists the cells whose calls sum to tens of
    ticks a window."""
    calls = calls_of(run)
    if calls is None:
        return None
    lengths = [s["end_ns"] - s["start_ns"] for s in calls]
    on_cpu = sum(s["cpu_ns"] for s in calls) / sum(lengths)
    return statistics.median(lengths) * (1.0 - on_cpu) / 1e6


def stall_share(run):
    """100 x what the periods have over ``STALL_OVER`` medians / their sum."""
    calls = calls_of(run)
    if calls is None:
        return None
    periods = [p for _, _, p in periods_of(run, calls)]
    limit = STALL_OVER * statistics.median(periods)
    return 100.0 * sum(max(0.0, p - limit) for p in periods) / sum(periods)


def gc_share(run):
    """100 x the collector's time in the periods (inside the call that begins
    each, and since it ended) / their sum."""
    calls = calls_of(run)
    if calls is None:
        return None
    periods = periods_of(run, calls)
    spent = sum(a["gc_ns"] + b.get("since_gc_ns", 0) for a, b, _ in periods)
    return 100.0 * spent / sum(p for _, _, p in periods)
