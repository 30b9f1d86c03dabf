"""What decides ``correct``, shown to fail where it must, at sizes a test run
can hold (the rehearsal set; the readings at the cells' own sizes are in
PERF.md). Run by hand:

    python3 -m pytest perfbench/tests/test_correct.py -q

* the control: the nearest precision below the configuration's (bfloat16)
  comes out as not correct under the rehearsal's limits, in both runner kinds;
* the timed path broken underneath a whole run of the harness (the look for a
  chip skipped, everything else driven): a step that returns its state
  unchanged; half of the batch left out and the mean taken over the rest; the
  exchange between chips left out; an answer altered where it is produced.
"""
import argparse
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)
sys.path.insert(1, ROOT)

import run as harness  # noqa: E402

BENCH = os.path.join(TESTS, "rehearsal", "BENCH.json")


def run_cell(workload, sabotage=None, seed=11):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.2, trace=0)
    return harness.run_cell(args, harness.read_json(BENCH), sabotage=sabotage)


def over(result):
    return {name for name, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


# -- sound runs ------------------------------------------------------------
@pytest.mark.parametrize("workload", ["tiny_es_fused", "tiny_lm_train",
                                      "tiny_lm_b2", "tiny_lm_ring_x4"])
def test_the_sound_program_is_correct(workload):
    result = run_cell(workload)
    assert result["correct"] is True and not over(result)


# -- the control -------------------------------------------------------------
def readings(workload, seed):
    """(runner, program's side, reference's side) at the rehearsal size."""
    bench = harness.read_json(BENCH)
    cell, cfg, traffic, limits = harness.cell_files(bench, workload)
    mod = harness.load_runner(cfg)
    devices = harness.pick_devices(int(cell["chips"]), "cpu")

    def fresh():
        return mod.Runner(cfg, traffic, harness.seed_key(seed), seed, devices,
                          harness.Spans(), rehearsal=True)
    return fresh, limits


# (seeds 5 and 6 are left out: at this toy size their first policy drops every
# walker backwards at once, all 256 fitnesses are 0 and nothing is left to rank)
@pytest.mark.parametrize("seed", [3, 4, 7, 8, 9])
def test_control_es_bfloat16_policy_is_not_correct(seed):
    """The program's own lower-precision path (``compute_dtype``, what
    FIBER_POLICY_DTYPE=bfloat16 switches on) is the control."""
    fresh, limits = readings("tiny_es_fused", seed)
    control = fresh()
    control.build(policy_dtype="bfloat16")
    control.checked_steps()
    numbers = dict(control.compare(control.program, control.reference()))
    assert any(numbers[n] > limits[n] for n in limits), numbers


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_lm_bfloat16_is_not_correct(seed):
    """The reference put in the program's place, stored and computed in
    bfloat16."""
    import jax.numpy as jnp

    fresh, limits = readings("tiny_lm_train", seed)
    runner = fresh()
    runner.build()
    runner.checked_steps()
    control = runner.reference(dtype=jnp.bfloat16)
    numbers = dict(runner.compare(control, runner.reference()))
    assert any(numbers[n] > limits[n] for n in limits), numbers


# -- the timed path broken underneath a whole run ------------------------------
def es_state_unchanged(runner):
    real = runner.es.run_fused

    def run_fused(params, key, generations):
        _, stats = real(params, key, generations)
        return params, stats
    runner.es.run_fused = run_fused


def es_half_population(runner):
    from runner_es_fused import make_es

    cfg = dict(runner.cfg, population=runner.cfg["population"] // 2)
    runner.es, _ = make_es(cfg, runner.devices)


def es_fitness_altered(runner, monkeypatch):
    from fiber_tpu.models import ParamBipedWalker
    from runner_es_fused import make_es

    real = ParamBipedWalker.rollout_p.__func__
    monkeypatch.setattr(
        ParamBipedWalker, "rollout_p",
        classmethod(lambda cls, *a, **kw: -real(cls, *a, **kw)))
    runner.es, _ = make_es(runner.cfg, runner.devices)


def lm_state_unchanged(runner):
    real = runner.step

    def step(params, opt_state, tokens):
        _, _, loss = real(params, opt_state, tokens)
        return params, opt_state, loss
    runner.step = step


def lm_half_batch(runner):
    real = runner.step
    runner.step = lambda p, s, tokens: real(p, s, tokens[:tokens.shape[0] // 2])


def lm_token_altered(runner):
    real = runner.step
    runner.step = lambda p, s, tokens: real(p, s, tokens.at[..., 5].add(1) % 512)


def lm_no_exchange(runner, monkeypatch):
    import importlib

    from runner_lm_train import make_step

    ring_attention = importlib.import_module("fiber_tpu.ops.ring_attention")

    monkeypatch.setattr(ring_attention, "_kv_rotate",
                        lambda k, v, **kw: (k, v))
    _, _, runner.step, _ = make_step(runner.cfg, runner.traffic,
                                     runner.devices, rehearsal=True)


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("tiny_es_fused", es_state_unchanged, {"update"}),
    ("tiny_es_fused", es_half_population, {"grad_dir"}),
    ("tiny_es_fused", es_fitness_altered, {"grad_dir"}),
    ("tiny_lm_train", lm_state_unchanged, {"update"}),
    ("tiny_lm_b2", lm_half_batch, {"grad"}),
    ("tiny_lm_train", lm_token_altered, {"loss1"}),
    ("tiny_lm_ring_x4", lm_no_exchange, {"loss1"}),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, caught_by,
                                            monkeypatch):
    def sabotage(runner):
        if "monkeypatch" in fault.__code__.co_varnames[:fault.__code__.co_argcount]:
            fault(runner, monkeypatch)
        else:
            fault(runner)

    result = run_cell(workload, sabotage=sabotage)
    assert result["correct"] is False, json.dumps(result["compared"])
    assert caught_by <= over(result), json.dumps(result["compared"])
