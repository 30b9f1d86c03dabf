"""The trace reducer on a hand-built trace: every number below can be
checked on paper. Run by hand: ``JAX_PLATFORMS=cpu python3 -m pytest
perfbench/tests/test_trace_reduce.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

import trace_reduce as tr
from trace_reduce import Event, Trace

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return Event(name, int(start_ms * MS), int(dur_ms * MS))


FWD = ("%jvp_jit_attn__.4 = (f32[24,8192,128]{2,1,0}, f32[24,8192,1]{2,1,0}) "
       "custom-call(f32[24,8192,128]{2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
BWD = ("%transpose_jvp_jit_attn___.2 = f32[24,8192,128]{2,1,0} "
       "custom-call(f32[24,8192,128]{2,1,0} %x), custom_call_target=\"tpu_custom_call\"")


@pytest.fixture
def trace():
    # window 0..100 ms. chip 0: a while op spanning 10..60 whose body ops
    # cover 10..30 and 40..60; a matmul 70..80 under an all-reduce 65..90.
    chip0 = [
        ev("%while.13 = (s32[], f32[8]) while(%t), body=%b", 10, 50),
        ev(FWD, 10, 20),
        ev(BWD, 40, 20),
        ev("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g)", 65, 25),
        ev("%fusion.7 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kOutput", 70, 10),
    ]
    chip1 = [ev(FWD, 0, 50), ev("%collective-permute.2 = f32[8] collective-permute(%k)", 50, 10)]
    host = [ev("dispatch", 0, 10), ev("wait", 10, 85), ev("make_batch", 95, 5)]
    return Trace(device={0: chip0, 1: chip1}, host=host, window=(0, 100 * MS))


def test_merge_clip_subtract():
    assert tr.merge([(5, 9), (1, 3), (2, 6)]) == [(1, 9)]
    assert tr.clip([(0, 10), (20, 30)], (5, 25)) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [(0, 2), (4, 8), (22, 30)]
    assert tr.measure([(0, 2), (4, 8)]) == 6


def test_busy_leaves_the_container_out(trace):
    busy = tr.busy_seconds(trace)
    # chip 0: 10..30, 40..60, 65..90 -> 65 ms; the while's 30..40 is idle
    assert busy[0] == pytest.approx(0.065)
    assert busy[1] == pytest.approx(0.060)
    assert tr.window_seconds(trace) == pytest.approx(0.100)


def test_kernel_seconds_by_name(trace):
    fwd = tr.kernel_seconds(trace, r"^%jvp_jit_attn[\w.]* = .*custom-call\(")
    bwd = tr.kernel_seconds(trace, r"^%transpose_jvp_jit_attn[\w.]* = .*custom-call\(")
    assert fwd == {0: pytest.approx(0.020), 1: pytest.approx(0.050)}
    assert bwd == {0: pytest.approx(0.020)}          # chip 1 ran none: left out
    assert tr.kernel_seconds(trace, r"^%no_such_kernel") == {}


RING_FWD = ("%attn_lse.13 = (f32[24,4096,128]{2,1,0:T(8,128)}, "
            "f32[24,4096,1]{2,1,0:T(8,128)}) custom-call(f32[24,4096,128]{2,1,0} %q)")
RING_DQ = "%attn_lse.15 = f32[24,4096,128]{2,1,0:T(8,128)S(1)} custom-call(f32[24,4096,128]{2,1,0} %q)"
RING_DKV = ("%attn_lse.16 = (f32[2,4096,128]{2,1,0:T(8,128)}, "
            "f32[2,4096,128]{2,1,0:T(8,128)}) custom-call(f32[24,4096,128]{2,1,0} %q)")
ONE_DKV = ("%transpose_jvp_jit_attn___.1 = (f32[2,8192,128]{2,1,0:T(8,128)}, "
           "f32[2,8192,128]{2,1,0:T(8,128)}) custom-call(f32[24,8192,128]{2,1,0} %q)")
VMAP_FWD = ("%jvp_vmap_jit_attn___.4 = (f32[4,24,2048,128]{3,2,1,0:T(8,128)}, "
            "f32[4,24,2048,1]{3,2,1,0:T(8,128)}) custom-call(f32[4,24,2048,128]{3,2,1,0} %q)")
VMAP_DQ = "%transpose_jvp_vmap_jit_attn____.2 = f32[4,24,2048,128]{3,2,1,0} custom-call(f32[4,24,2048,128]{3,2,1,0} %q)"
VMAP_DKV = ("%transpose_jvp_vmap_jit_attn____.1 = (f32[4,2,2048,128]{3,2,1,0}, "
            "f32[4,2,2048,128]{3,2,1,0}) custom-call(f32[4,24,2048,128]{3,2,1,0} %q)")
NOT_KERNELS = [
    "%fusion.95 = (f32[3072,12288]{1,0}, f32[3072,12288]{1,0}) fusion(f32[3072,12288]{1,0} %p)",
    "%custom-call.11 = f32[4096,24,128]{2,1,0} custom-call(f32[4096,24,128]{2,1,0} %x)",
]


@pytest.mark.parametrize("reader,hits,misses", [
    ("flash_fwd_roofline", [FWD, RING_FWD, VMAP_FWD],
     [BWD, RING_DQ, RING_DKV, ONE_DKV, VMAP_DQ, VMAP_DKV]),
    ("flash_bwd_roofline", [BWD, RING_DQ, RING_DKV, ONE_DKV, VMAP_DQ, VMAP_DKV],
     [FWD, RING_FWD, VMAP_FWD]),
])
def test_the_roofline_readers_find_their_kernels_by_what_the_trace_shows(
        reader, hits, misses):
    """One chip names them ``jvp_jit_attn`` / ``transpose_jvp_jit_attn``
    (``..._vmap_...`` under a batch), the ring ``attn_lse`` for all three:
    the outputs tell them apart."""
    import importlib.util
    import re

    spec = importlib.util.spec_from_file_location(reader, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "metrics", reader + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert all(re.search(module.KERNEL, name) for name in hits)
    assert not any(re.search(module.KERNEL, name)
                   for name in misses + NOT_KERNELS)


def test_exposed_collective_time(trace):
    # all-reduce 65..90 with a fusion under 70..80: 15 ms exposed
    assert tr.exposed_collective_seconds(trace, 0) == pytest.approx(0.015)
    assert tr.exposed_collective_seconds(trace, 1) == pytest.approx(0.010)
    none = Trace(device={0: [ev("%fusion.1 = f32[] fusion()", 0, 5)]})
    assert tr.exposed_collective_seconds(none, 0) is None


def test_top_ops_and_gap_attribution(trace):
    ops = dict((name, s) for name, s in tr.top_ops(trace, 0))
    assert "%while.13 while" not in ops
    assert ops["%all-reduce.1 all-reduce"] == pytest.approx(0.025)
    assert ops["%jvp_jit_attn__.4 custom-call"] == pytest.approx(0.020)
    gaps = tr.idle_gaps(trace, 0)
    # gaps: 0..10 (dispatch), 30..40 (wait), 60..65 (wait), 90..100 (half wait, half make_batch)
    assert [round(s, 4) for _, s in gaps] == [0.01, 0.01, 0.01, 0.005]
    assert {(n, round(s, 4)) for n, s in gaps} >= {("dispatch", 0.01), ("wait", 0.005)}


def test_window_falls_back_to_the_device_events():
    t = Trace(device={0: [ev("%a = f32[] add()", 5, 5), ev("%b = f32[] add()", 20, 10)]})
    assert tr.window_seconds(t) == pytest.approx(0.025)
    assert tr.busy_seconds(t)[0] == pytest.approx(0.015)


def test_short_name():
    assert tr.short_name(FWD) == "%jvp_jit_attn__.4 custom-call"
    assert tr.short_name("wrapped_tanh") == "wrapped_tanh"
