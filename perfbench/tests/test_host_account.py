"""The four readers of the host's account on the step's call spans, on
hand-built spans (every number below can be checked on paper), and one
traced rehearsal of an LM cell on the CPU that shows the four names in the
run's ``metrics``. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_host_account.py -q

A record is what ``run.py`` hands a reader, plus ``"program_spans"`` (in a
run of the harness the readers take the spans from
``fiber_tpu.telemetry.tracing.SPANS`` itself).
"""
import importlib.util
import json
import os
import subprocess
import sys

PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)

import pytest

MS = 1_000_000  # ns
EPOCH = 1_790_000_000 * 1_000_000_000
FOUR = ("step_call_ms", "step_call_blocked_ms", "step_stall_share",
        "host_gc_share")


def reader(name):
    path = os.path.join(PERFBENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def call(start_ms, dur_ms=6.0, cpu_ms=2.0, gc_ms=0.0, since=None):
    """An ``lm.train_step`` span ``start_ms`` after the epoch of the test:
    ``dur_ms`` long, of which the thread computed ``cpu_ms`` and the
    collector ran ``gc_ms``; ``since`` is the collector's time since the
    last call ended, in ms."""
    start = EPOCH + int(start_ms * MS)
    sp = {"name": "lm.train_step", "start_ns": start,
          "end_ns": start + int(dur_ms * MS), "parent": None, "tokens": 8192,
          "cpu_ns": int(cpu_ms * MS), "gc_ns": int(gc_ms * MS),
          "gc_runs": int(gc_ms > 0)}
    if since is not None:
        sp.update(since_ns=1, since_cpu_ns=0, since_gc_ns=int(since * MS),
                  since_gc_runs=int(since > 0))
    return sp


def record(calls, traced=None):
    """Two checked steps of set-up, then the window's ``calls``."""
    setup = [call(-2000, 900), call(-1000, 6, since=0),
             {"name": "monitor.tick", "start_ns": EPOCH - 5 * MS,
              "end_ns": EPOCH - 4 * MS, "parent": None}]
    return {"program_spans": setup + list(calls),
            "call_times": [0.1] * len(calls),
            "traffic": {"trace_calls": traced or 8},
            "trace": object() if traced else None}


@pytest.fixture
def twenty():
    """Twenty calls 100 ms apart, but the eleventh period is 600 ms: the
    collector took 60 ms of it (10 inside the call that began it, 50 after).
    Every call is 6 ms long and computes 2 ms of it; call 3 is 10 ms long
    and computes 9, call 4 is 20 ms long and computes 1."""
    starts = [100 * k for k in range(11)] + [
        1000 + 600 + 100 * k for k in range(9)]
    calls = [call(t, since=0) for t in starts]
    calls[3] = call(starts[3], 10, 9, since=0)
    calls[4] = call(starts[4], 20, 1, since=0)
    calls[10] = call(starts[10], gc_ms=10, since=0)
    calls[11] = call(starts[11], since=50)
    return record(calls)


def test_the_calls_length_and_the_part_off_the_cpu(twenty):
    assert reader("step_call_ms").read(twenty) == pytest.approx(6.0)
    # 138 ms of calls, 46 of them on the CPU: two thirds of the median
    # call's 6 ms are off it
    assert reader("step_call_blocked_ms").read(twenty) == pytest.approx(4.0)
    # a kernel that keeps CPU time by the 10 ms tick: 7 calls in 20 read a
    # whole tick and 13 none; the sum says 70 of 138 ms
    window = [sp for sp in twenty["program_spans"]
              if sp["name"] == "lm.train_step"][-20:]
    for k, sp in enumerate(window):
        sp["cpu_ns"] = 10 * MS if k % 3 == 0 else 0
    assert reader("step_call_blocked_ms").read(twenty) == pytest.approx(
        6.0 * (1 - 70 / 138))
    # ticks that overcount show as a reading below 0, not as 0
    for sp in window:
        sp["cpu_ns"] = 10 * MS
    assert reader("step_call_blocked_ms").read(twenty) == pytest.approx(
        6.0 * (1 - 200 / 138))


def test_one_stalled_period_in_twenty_calls(twenty):
    # 18 periods of 100 ms and one of 600: the median is 100, the stalled
    # one has 475 over 1.25 medians, of 2,400 ms in all
    assert reader("step_stall_share").read(twenty) == pytest.approx(
        100 * 475 / 2400)
    assert reader("host_gc_share").read(twenty) == pytest.approx(
        100 * 60 / 2400)


def test_a_sound_window_reads_nothing_stalled(twenty):
    sound = record([call(100 * k, since=0) for k in range(20)])
    assert reader("step_stall_share").read(sound) == 0.0
    assert reader("host_gc_share").read(sound) == 0.0


@pytest.mark.parametrize("name", FOUR)
def test_spans_without_the_account_give_nothing(twenty, name):
    """The commit before PR 35: the call spans are there, the fields are
    not. None, never 0, and nothing raised."""
    for sp in twenty["program_spans"]:
        for key in [k for k in sp if k.endswith(("_ns", "gc_runs"))
                    and k not in ("start_ns", "end_ns")]:
            del sp[key]
    assert reader(name).read(twenty) is None
    assert reader(name).read(dict(twenty, program_spans=[])) is None
    es = [dict(sp, name="es.run_fused") if sp["name"] == "lm.train_step"
          else sp for sp in twenty["program_spans"]]
    assert reader(name).read(dict(twenty, program_spans=es)) is None


@pytest.mark.parametrize("name", FOUR)
def test_a_window_of_five_calls_gives_nothing(name):
    few = record([call(100 * k, since=0) for k in range(5)])
    assert reader(name).read(few) is None
    eight = record([call(100 * k, since=0) for k in range(8)])
    assert reader(name).read(eight) is not None


def test_the_period_in_which_the_capture_was_written_is_left_out(twenty):
    """The same twenty calls as a traced run whose profiler stopped after
    call 11: the long period is the harness writing its capture, and what
    the thread did in it is the harness's too."""
    traced = dict(twenty, traffic={"trace_calls": 11}, trace=object())
    assert reader("step_stall_share").read(traced) == 0.0
    assert reader("host_gc_share").read(traced) == 0.0
    assert reader("step_call_ms").read(traced) == pytest.approx(6.0)
    assert reader("step_call_blocked_ms").read(traced) == pytest.approx(4.0)
    # a capture that ended elsewhere leaves the long period in
    other = dict(traced, traffic={"trace_calls": 8})
    assert reader("step_stall_share").read(other) > 19


def test_a_traced_rehearsal_reports_the_four():
    """One traced run of the tiny LM cell on the CPU, from the rehearsal
    file that lists the four beside the cell's old metrics."""
    bench = os.path.join(PERFBENCH, "tests", "rehearsal", "BENCH_host.json")
    env = {k: v for k, v in os.environ.items()
           if k not in ("FIBER_POLICY_DTYPE", "FIBER_ROLLOUT_UNROLL")}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(PERFBENCH, "run.py"), "--workload",
         "tiny_lm_train", "--seed", str(2**31 + 35), "--seconds", "1.5",
         "--trace", "1", "--bench", bench],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(FOUR) | {"compile_s", "train_step_ms", "train_step_mfu",
                        "device_idle_share.train"} == set(metrics)
    assert line["correct"] is True and line["attempted"] >= 8
    assert (0 <= metrics["step_call_blocked_ms"] <= metrics["step_call_ms"]
            <= metrics["train_step_ms"])  # this kernel's clock is fine
    for name in FOUR[2:]:
        assert 0 <= metrics[name] < 100
