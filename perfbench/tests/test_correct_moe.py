"""What decides ``correct`` for the runner kind ``lm_moe_train``, shown to
fail where it must, at a size a test run can hold (the rehearsal set
``rehearsal/BENCH_moe.json``: three layers, full + dense, window + experts,
full + experts, 4 of 16 experts held as share 1 of 4; the readings at the
cell's own size are in PERF.md). Run by hand:

    python3 -m pytest perfbench/tests/test_correct_moe.py -q

* the control: the reference stored and computed in bfloat16 comes out as not
  correct under the rehearsal's limits;
* the timed path broken underneath a whole run of the harness: a step that
  returns its state unchanged; half of the loss positions left out; the held
  experts' part left out; the weights normalised over the held experts only;
  the window layers run without their window.
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

BENCH = os.path.join(TESTS, "rehearsal", "BENCH_moe.json")
CELL = "tiny_moe_train"


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
    assert {"loss1", "loss2", "loss3", "grad", "update", "update_routed",
            "routing", "window_compiles"} == set(result["compared"])


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


# -- the timed path broken underneath a whole run ------------------------------
def rebuilt(runner, traffic=None):
    """The runner's model and step built again, from the program as it is
    now (patched)."""
    from runner_lm_moe_train import make_step

    runner.model, _, runner.step, _ = make_step(
        runner.cfg, traffic or runner.traffic, runner.devices,
        rehearsal=True)


def state_unchanged(runner, monkeypatch):
    real = runner.step

    def step(params, opt_state, tokens):
        import jax

        # the real step donates its arguments: hand it copies
        _, _, loss = real(jax.tree.map(lambda x: x + 0, params),
                          jax.tree.map(lambda x: x + 0, opt_state), tokens)
        return params, opt_state, loss
    runner.step = step


def half_loss(runner, monkeypatch):
    import jax
    import jax.numpy as jnp

    from fiber_tpu.models import BlockLM

    def loss(self, params, tokens):
        logits = self.apply(params, tokens)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[1:, None], axis=1)
        return -jnp.mean(picked[:tokens.shape[0] // 2])
    monkeypatch.setattr(BlockLM, "loss", loss)
    rebuilt(runner)


def no_routed(runner, monkeypatch):
    import jax.numpy as jnp

    from fiber_tpu.ops import moe

    monkeypatch.setattr(moe, "routed_experts",
                        lambda h, *a, **kw: jnp.zeros_like(h))
    rebuilt(runner)


def held_norm(runner, monkeypatch):
    import jax.numpy as jnp

    from fiber_tpu.ops import moe

    real = moe.route
    first, count = moe.held_experts(16, (1, 4))

    def route(h, router, *, top_k, scale=1.0):
        ids, weights = real(h, router, top_k=top_k, scale=scale)
        here = (ids >= first) & (ids < first + count)
        total = jnp.sum(jnp.where(here, weights, 0.0), axis=-1,
                        keepdims=True)
        return ids, jnp.where(
            here, scale * weights / jnp.where(total > 0, total, 1.0),
            weights)
    monkeypatch.setattr(moe, "route", route)
    rebuilt(runner)


def no_window(runner, monkeypatch):
    rebuilt(runner, dict(runner.traffic, use_window=False))


@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"update"}),
    (half_loss, {"loss1", "grad"}),
    (no_routed, {"grad", "update_routed"}),
    (held_norm, {"grad"}),
    (no_window, {"grad", "routing"}),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch):
    result = run_cell(sabotage=lambda runner: fault(runner, monkeypatch))
    assert result["correct"] is False, json.dumps(result["compared"])
    assert caught_by <= over(result), json.dumps(result["compared"])
