"""The five readers of the program's own spans on a hand-built record:
every number below can be checked on paper. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_program_spans.py -q

A record is what ``run.py`` hands a reader, plus ``"program_spans"``: the
program's span store as a list (in a run of the harness the readers take it
from ``fiber_tpu.telemetry.tracing.SPANS`` itself). The program's spans are
on the epoch's clock, the trace on the profiler session's: here the session
began at ``EPOCH`` nanoseconds.
"""
import importlib.util
import os
import sys

PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, PERFBENCH)

import pytest

from trace_reduce import Event, Trace

MS = 1_000_000  # ns
EPOCH = 1_790_000_000 * 1_000_000_000
#: the renamed kernels, as a compiled v5e program calls them (PR 25)
FWD = ("%flash_attn_fwd.4 = (f32[24,8192,128]{2,1,0:T(8,128)}, "
       "f32[24,8192,1]{2,1,0:T(8,128)}) custom-call(%a, %b, %c), "
       "custom_call_target=\"tpu_custom_call\"")
DQ = ("%flash_attn_dq.8 = f32[24,8192,128]{2,1,0:T(8,128)S(1)} "
      "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
DKV = ("%flash_attn_dkv.8 = (f32[2,8192,128]{2,1,0:T(8,128)}, "
       "f32[2,8192,128]{2,1,0:T(8,128)}) custom-call(%a, %b), "
       "custom_call_target=\"tpu_custom_call\"")


def reader(name):
    path = os.path.join(PERFBENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, start_ms, dur_ms, **attrs):
    """A program span, ``start_ms`` after the session began."""
    start = EPOCH + int(start_ms * MS)
    return {"name": name, "start_ns": start,
            "end_ns": start + int(dur_ms * MS), "parent": None, **attrs}


def ev(name, start_ms, dur_ms):
    return Event(name, int(start_ms * MS), int(dur_ms * MS))


@pytest.fixture
def run():
    """Set-up 0..1000 ms: the step compiles under the first checked call
    (trace 300 ms with 25 ms of nested traces folded in, lower 200 ms
    with 4 ms of traces inside it, backend 40 ms, a cache hit), the weight
    init
    before it (trace 50, lower 30, backend 20 ms, a miss) and a tiny
    helper whose compile went uncached. The window is three calls of
    100 ms from 1000 ms on; the first two are traced (window 1000..1200).
    The reference afterwards compiles too (5000 ms on) and must not count.

    Traced call k: the harness's ``dispatch`` event starts 0.02 ms before
    the program's ``lm.train_step`` span and ends 0.01 ms after it; the
    chip idles for the first 8 ms of each call (the span covers the first
    6 of them) and for 4 ms in the middle of the step, while the host is
    in ``wait``."""
    spans = [
        span("jax.trace", 100, 50, fun_name="init"),
        span("jax.lower", 150, 30, fun_name="jit(init)"),
        span("jax.backend_compile", 180, 20, fun_name="jit(init)",
             cache="miss"),
        span("jax.trace", 210, 1, fun_name="helper"),
        span("jax.backend_compile", 212, 2, fun_name="jit(helper)",
             cache=None),
        span("jax.trace", 400, 300, fun_name="step", nested=900,
             nested_s=0.025),
        span("jax.lower", 700, 200, fun_name="jit(step)", nested=300,
             nested_s=0.004),
        span("jax.backend_compile", 900, 40, fun_name="jit(step)",
             cache="hit"),
        span("lm.train_step", 400, 545, tokens=8192),   # checked step 1
        span("lm.train_step", 950, 6, tokens=8192),     # checked step 2
        span("monitor.tick", 999, 1),
    ]
    for k in range(3):
        spans.append(span("lm.train_step", 1000 + 100 * k + 0.02, 6,
                          tokens=8192))
    spans += [span("jax.trace", 5000, 700, fun_name="reference"),
              span("jax.backend_compile", 5700, 900,
                   fun_name="jit(reference)", cache="miss")]
    chip0, host = [], []
    for k in range(2):
        t = 1000 + 100 * k
        host += [ev("dispatch", t, 6.03), ev("make_batch", t + 6.03, 1),
                 ev("wait", t + 7.03, 92.97)]
        chip0 += [ev(FWD, t + 8, 20), ev(DQ, t + 28, 12),
                  ev(DKV, t + 44, 16),
                  ev("%fusion.1 = f32[8]{0} fusion(%x), kind=kLoop",
                     t + 60, 40)]
    trace = Trace(device={0: chip0}, host=host,
                  window=(1000 * MS, 1200 * MS))
    return {"program_spans": spans, "call_times": [0.1, 0.1, 0.1],
            "trace": trace, "compile_s": 0.672}


def test_compile_phases_of_set_up(run):
    # nested traces count as compile_s counts them: for themselves and
    # again inside the outer trace's duration
    assert reader("jax_trace_s").read(run) == pytest.approx(0.380)
    assert reader("jax_lower_s").read(run) == pytest.approx(0.230)
    assert reader("jax_backend_compile_s").read(run) == pytest.approx(0.062)
    # the three together are what compile_s lumps
    assert 0.380 + 0.230 + 0.062 == pytest.approx(run["compile_s"])


def test_cache_hit_share_counts_spans_that_say(run):
    # init missed, step hit, the helper says nothing: one of two
    assert reader("compile_cache_hit_share").read(run) == pytest.approx(50.0)


def test_dispatch_idle_share(run):
    # idle on chip 0: 8 ms at the head of each call, 4 ms in the middle;
    # the program's span covers 0.02..6.02 ms of each call: 2 x 6 ms of
    # the 200 ms window
    assert reader("dispatch_idle_share").read(run) == pytest.approx(
        100.0 * 12 / 200, abs=1e-3)
    # the whole idle share is larger: (8 + 4) x 2 of 200
    import trace_reduce

    assert 100 * trace_reduce.idle_share(run["trace"]) == pytest.approx(12.0)


def test_clocks_are_aligned_on_the_calls_both_sides_hold(run):
    """Shift the program's clock by an hour: nothing moves."""
    hour = 3600 * 1_000_000_000
    for s in run["program_spans"]:
        s["start_ns"] += hour
        s["end_ns"] += hour
    assert reader("dispatch_idle_share").read(run) == pytest.approx(
        6.0, abs=1e-3)


@pytest.mark.parametrize("name", [
    "jax_trace_s", "jax_lower_s", "jax_backend_compile_s",
    "compile_cache_hit_share", "dispatch_idle_share"])
def test_a_program_without_such_spans_gives_nothing(run, name):
    """The commit before PR 25: spans with ``ts`` and ``dur`` only, none of
    these names. A reader returns None (never 0) and does not raise."""
    run["program_spans"] = [{"name": "pool.serialize", "ts": 1.0, "dur": 0.1}]
    assert reader(name).read(run) is None
    run["program_spans"] = []
    assert reader(name).read(run) is None


def test_nothing_without_a_trace_or_with_an_es_call(run):
    assert reader("dispatch_idle_share").read(dict(run, trace=None)) is None
    for s in run["program_spans"]:
        if s["name"] == "lm.train_step":
            s["name"] = "es.run_fused"
    assert reader("dispatch_idle_share").read(run) is None
    # the set-up readers serve both runner kinds
    assert reader("jax_trace_s").read(run) == pytest.approx(0.380)


def test_renamed_kernels_still_match_the_roofline_readers(run):
    import re

    fwd, bwd = reader("flash_fwd_roofline"), reader("flash_bwd_roofline")
    assert re.search(fwd.KERNEL, FWD) and not re.search(bwd.KERNEL, FWD)
    for text in (DQ, DKV):
        assert re.search(bwd.KERNEL, text) and not re.search(fwd.KERNEL, text)
