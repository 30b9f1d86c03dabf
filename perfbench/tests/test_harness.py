"""The harness end to end on the CPU, at tiny sizes (the rehearsal set in
``rehearsal/``: its own configurations and traffic files, Pallas kernels in
the interpreter, never in BENCHMARK.json). Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_harness.py -q

Each run is a process of its own, as the driver's are.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(TESTS))
REHEARSAL = os.path.join(TESTS, "rehearsal")
BENCH = os.path.join(REHEARSAL, "BENCH.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(workload, *, trace=0, bench=BENCH, env=None, devices=1, seed=7,
        seconds=0.5):
    full = {k: v for k, v in os.environ.items()
            if k not in ("FIBER_POLICY_DTYPE", "FIBER_ROLLOUT_UNROLL")}
    full.update(JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    full.update(env or {})
    cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    if bench is not None:
        cmd += ["--bench", bench]
    return subprocess.run(cmd, capture_output=True, text=True, cwd=ROOT,
                          env=full, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,devices,rate", [
    ("tiny_es_fused", 1, "es_evals_per_s"),
    ("tiny_lm_train", 1, "train_tokens_per_s"),
    ("tiny_lm_b2", 1, "train_tokens_per_s"),
    ("tiny_lm_ring_x4", 4, "train_tokens_per_s"),
])
def test_run_end_to_end(workload, devices, rate):
    proc = run(workload, devices=devices, seed=2**31 + 11)
    line = last_line(proc)
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", rate}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == devices
    assert line["device"]["platform"] == "cpu"
    # each number compared, beside its limit, ends standard error
    tail = proc.stderr.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert all(name in proc.stderr for name in line["compared"])


@pytest.mark.parametrize("workload,devices,expect", [
    ("tiny_es_fused", 1, {"compile_s", "es_gen_ms", "es_gen_mfu",
                          "device_idle_share.es"}),
    ("tiny_lm_ring_x4", 4, {"compile_s", "train_step_ms", "train_step_mfu",
                            "device_idle_share.train"}),
])
def test_traced_run_reports_per_layer_metrics(workload, devices, expect):
    line = last_line(run(workload, trace=1, devices=devices))
    # the interpreter runs no Mosaic kernel, so the rooflines find nothing
    # to read and are left out: never reported as 0
    # (the CPU's thunk threads stand in for chip 0 only loosely, so the
    # collectives of four virtual devices may or may not land on them)
    assert expect <= set(line["metrics"]) <= expect | {"collective_exposed_share"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new traffic mix and a new cell: the mix's file, the cell's limits
    and one entry; no file that is there is edited."""
    work = tmp_path / "rehearsal"
    shutil.copytree(REHEARSAL, work)
    mix = json.load(open(work / "traffic" / "lm_128_b2.json"))
    mix["seq"], mix["batch"] = 64, 3
    json.dump(mix, open(work / "traffic" / "lm_64_b3.json", "w"))
    bench = json.load(open(work / "BENCH.json"))
    shutil.copy(work / "limits" / "tiny_lm_b2.json",
                work / "limits" / "tiny_lm_b3.json")
    bench["traffic_dir"] = os.path.relpath(work / "traffic", ROOT)
    bench["limits_dir"] = os.path.relpath(work / "limits", ROOT)
    bench["workloads"].append({"name": "tiny_lm_b3", "config": "tiny_lm",
                               "traffic": "lm_64_b3", "chips": 1,
                               "why": "added by files alone"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny_lm_b2" in metric.get("workloads", ()):
            metric["workloads"].append("tiny_lm_b3")
    path = work / "BENCH.json"
    json.dump(bench, open(path, "w"))
    line = last_line(run("tiny_lm_b3", bench=str(path)))
    assert line["correct"] is True
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("name", ["FIBER_POLICY_DTYPE", "FIBER_ROLLOUT_UNROLL"])
def test_the_programs_trace_time_knobs_are_refused(name):
    proc = run("tiny_es_fused", env={name: "bfloat16" if "DTYPE" in name else "4"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert name in proc.stderr


def test_a_benchmark_cell_never_falls_back_to_the_cpu():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    proc = run(bench["workloads"][0]["name"], bench=None)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "nothing falls back" in proc.stderr


def test_fewer_chips_than_the_cell_asks_for():
    proc = run("tiny_lm_ring_x4", devices=2)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_an_unknown_workload_is_refused():
    proc = run("no_such_cell")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
