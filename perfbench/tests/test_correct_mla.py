"""What decides ``correct`` for the runner kind ``lm_mla_train``, shown to
fail where it must, at a size a test run can hold (the rehearsal set
``rehearsal/BENCH_mla.json``: two latent layers, dense + experts, and
an MTP module with experts; queries and keys 24 wide, values
16; 4 of 16 experts held as share 1 of 4; the readings at the cell's own
size are in PERF.md). Run by hand:

    python3 -m pytest perfbench/tests/test_correct_mla.py -q

* the control: the reference stored and computed in bfloat16 comes out as not
  correct under the rehearsal's limits;
* the timed path broken underneath a whole run of the harness: the MTP term
  left out of the loss; the key-value latent entering W_kvb without its
  norm; the rope turning the halves of q_pe and k_pe instead of adjacent
  pairs; a step that returns its state unchanged.
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

BENCH = os.path.join(TESTS, "rehearsal", "BENCH_mla.json")
CELL = "tiny_mla_train"


def run_cell(sabotage=None, seed=11):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.2, trace=0)
    return harness.run_cell(args, harness.read_json(BENCH), sabotage=sabotage)


def over(result):
    return {name for name, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_the_sound_program_is_correct(seed):
    result = run_cell(seed=seed)
    assert result["correct"] is True and not over(result), json.dumps(
        result["compared"])
    assert {"loss1", "loss2", "loss3", "grad", "update", "update_routed",
            "routing", "window_compiles"} == set(result["compared"])


@pytest.mark.parametrize("seed", [3, 4])
def test_control_bfloat16_is_not_correct(seed):
    import jax.numpy as jnp

    bench = harness.read_json(BENCH)
    cell, cfg, traffic, limits = harness.cell_files(bench, CELL)
    mod = harness.load_runner(cfg)
    runner = mod.Runner(cfg, traffic, harness.seed_key(seed), seed,
                        harness.pick_devices(1, "cpu"), harness.Spans(),
                        rehearsal=True)
    runner.draw_checked_batches()
    control = runner.reference(dtype=jnp.bfloat16)
    numbers = dict(runner.compare(control, runner.reference()))
    assert any(numbers[n] > limits[n] for n in limits), numbers


# -- the timed path broken underneath a whole run ------------------------------
def rebuilt(runner):
    """The runner's model and step built again, from the program as it is
    now (patched)."""
    from runner_lm_mla_train import make_step

    runner.model, _, runner.step, _ = make_step(
        runner.cfg, runner.traffic, runner.devices, rehearsal=True)


def no_mtp(runner, monkeypatch):
    # the program's configuration alone: the reference keeps its own spec
    runner.cfg = dict(runner.cfg, mtp_loss_weight=0.0)
    rebuilt(runner)


def no_kv_norm(runner, monkeypatch):
    from fiber_tpu.models import BlockLM

    real = BlockLM._rms

    def rms(self, x, g):
        # the key-value latent's gain is the one of its width
        if g.shape[-1] == runner.spec["kv_rank"]:
            return x
        return real(self, x, g)
    monkeypatch.setattr(BlockLM, "_rms", rms)
    rebuilt(runner)


def half_rope(runner, monkeypatch):
    from fiber_tpu.models import BlockLM

    real = BlockLM._rope_rotate
    monkeypatch.setattr(
        BlockLM, "_rope_rotate", staticmethod(
            lambda x, cos, sin, interleaved=False: real(x, cos, sin, False)))
    rebuilt(runner)


def state_unchanged(runner, monkeypatch):
    real = runner.step

    def step(params, opt_state, tokens):
        import jax

        # the real step donates its arguments: hand it copies
        _, _, loss = real(jax.tree.map(lambda x: x + 0, params),
                          jax.tree.map(lambda x: x + 0, opt_state), tokens)
        return params, opt_state, loss
    runner.step = step


@pytest.mark.parametrize("fault,caught_by", [
    (no_mtp, {"loss1", "grad"}),
    (no_kv_norm, {"grad"}),
    (half_rope, {"grad"}),
    (state_unchanged, {"update"}),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch):
    result = run_cell(sabotage=lambda runner: fault(runner, monkeypatch))
    assert result["correct"] is False, json.dumps(result["compared"])
    assert caught_by <= over(result), json.dumps(result["compared"])
