"""What decides ``correct`` for the runner kind ``lm_loop_train``, shown to
fail where it must, at a size a test run can hold (the rehearsal set
``rehearsal/BENCH_loop.json``: two layers of 4 / 4 heads run four times,
sandwich norms, the exit gate, recomputed layers and a head in blocks of 96
rows that do not divide the 512; the readings at the cell's own size are in
PERF.md). Run by hand:

    python3 -m pytest perfbench/tests/test_correct_loop.py -q

* the control: the reference stored and computed in bfloat16 comes out as not
  correct under the rehearsal's limits;
* a fault underneath a whole run of the harness, on the timed path where the
  description can plant it (a step that returns its state unchanged; three
  passes for four; the loss taken from the last pass alone; the entropy term
  left out; half of the loss positions left out) and else on the reference's side, which the harness holds the
  program against just the same (the final norm between passes left out; the
  norms behind the parts left out; every pass reading pass 1's logits).
"""
import argparse
import functools
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

BENCH = os.path.join(TESTS, "rehearsal", "BENCH_loop.json")
CELL = "tiny_loop_train"


def run_cell(sabotage=None, seed=11):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=0.2, trace=0)
    return harness.run_cell(args, harness.read_json(BENCH), sabotage=sabotage)


def over(result):
    return {name for name, c in result["compared"].items()
            if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_the_sound_program_is_correct(seed):
    result = run_cell(seed=seed)
    assert result["correct"] is True and not over(result)
    assert {"loss1", "loss2", "loss3", "grad", "update", "pass_losses",
            "exit", "window_compiles"} == set(result["compared"])


@pytest.mark.parametrize("seed", [3, 4, 5])
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


# -- a fault underneath a whole run --------------------------------------------
def state_unchanged(runner):
    real = runner.step

    def step(params, opt_state, tokens):
        import jax

        # the real step donates its arguments: hand it copies
        _, _, loss = real(jax.tree.map(lambda x: x + 0, params),
                          jax.tree.map(lambda x: x + 0, opt_state), tokens)
        return params, opt_state, loss
    runner.step = step


def described(**fields):
    """The timed model described otherwise, before its first trace."""
    def fault(runner):
        for name, value in fields.items():
            setattr(runner.model, name, value)
    return fault


def three_passes(runner):
    described(passes=3)(runner)


def last_pass_loss(runner):
    import jax.numpy as jnp

    from fiber_tpu.models import make_train_step

    model = runner.model
    model.loss = lambda params, tokens: jnp.mean(
        model.pass_losses(params, tokens)[0][-1])
    runner.step = make_train_step(model, runner.opt, donate=True)


def half_loss(runner):
    import jax.numpy as jnp

    from fiber_tpu.models import make_train_step

    model = runner.model

    def loss(params, tokens):
        ce, p = model.pass_losses(params, tokens)
        half = tokens.shape[0] // 2
        ce, p = ce[:, :half], p[:, :half]
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        return jnp.mean(jnp.sum(p * ce, axis=0)
                        - model.exit_gate.beta * entropy)
    model.loss = loss
    runner.step = make_train_step(model, runner.opt, donate=True)


def no_entropy(runner):
    from fiber_tpu.models import ExitGate

    described(exit_gate=ExitGate(beta=0.0))(runner)


def on_the_reference(name):
    def fault(runner):
        runner.reference = functools.partial(runner.reference,
                                             faults=(name,))
    fault.__name__ = name
    return fault


@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"update"}),
    (three_passes, {"loss1", "grad", "pass_losses", "exit"}),
    (last_pass_loss, {"loss1", "grad"}),
    (no_entropy, {"loss1", "grad"}),
    (half_loss, {"loss1", "grad"}),
    (on_the_reference("no_pass_norm"), {"grad", "pass_losses", "exit"}),
    (on_the_reference("no_post_norm"), {"grad", "pass_losses", "exit"}),
    (on_the_reference("first_pass_logits"), {"grad", "pass_losses"}),
], ids=lambda f: getattr(f, "__name__", None))
def test_a_fault_underneath_a_run_is_not_correct(fault, caught_by):
    result = run_cell(sabotage=fault)
    assert result["correct"] is False, json.dumps(result["compared"])
    assert caught_by <= over(result), json.dumps(result["compared"])
