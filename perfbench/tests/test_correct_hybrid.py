"""What decides ``correct`` for the runner kind ``lm_hybrid_train``, shown to
fail where it must, at a size a test run can hold (the rehearsal set
``rehearsal/BENCH_hybrid.json``: five layers, state-space, experts,
attention, state-space, experts; 4 of 16 ungated experts held as share 1 of
4; the readings at the cell's own size are in PERF.md). Run by hand:

    python3 -m pytest perfbench/tests/test_correct_hybrid.py -q

* the control: the reference stored and computed in bfloat16 comes out as not
  correct under the rehearsal's limits;
* the timed path broken underneath a whole run of the harness: a step that
  returns its state unchanged; half of the loss positions left out; the state
  between the scan's blocks left out (every block from zero: ``positions``,
  the first batch's loss position by position, is the number that holds it
  at the cell's own size, where the mean loss does not move); the
  convolution left out; the held experts' part left out.
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

BENCH = os.path.join(TESTS, "rehearsal", "BENCH_hybrid.json")
CELL = "tiny_hybrid_train"


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
            "routing", "positions", "window_compiles"} == set(
        result["compared"])


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
def rebuilt(runner):
    """The runner's model and step built again, from the program as it is
    now (patched)."""
    from runner_lm_hybrid_train import make_step

    runner.model, _, runner.step, _ = make_step(
        runner.cfg, runner.traffic, runner.devices, rehearsal=True)


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


def no_carry(runner, monkeypatch):
    import jax

    from fiber_tpu.ops import ssm

    real = ssm.ssd_scan

    def every_block_from_zero(x, dt, A, B, C, D, *, chunk):
        def blocks(a):
            return a.reshape((-1, chunk) + a.shape[1:])
        y = jax.vmap(lambda x, dt, B, C: real(x, dt, A, B, C, D,
                                              chunk=chunk))(
            blocks(x), blocks(dt), blocks(B), blocks(C))
        return y.reshape(x.shape)
    monkeypatch.setattr(ssm, "ssd_scan", every_block_from_zero)
    rebuilt(runner)


def no_conv(runner, monkeypatch):
    from fiber_tpu.ops import ssm

    monkeypatch.setattr(ssm, "causal_conv", lambda v, w, b: v)
    rebuilt(runner)


def no_routed(runner, monkeypatch):
    import jax.numpy as jnp

    from fiber_tpu.ops import moe

    monkeypatch.setattr(moe, "routed_experts",
                        lambda h, *a, **kw: jnp.zeros_like(h))
    rebuilt(runner)


@pytest.mark.parametrize("fault,caught_by", [
    (state_unchanged, {"update"}),
    (half_loss, {"loss1", "grad"}),
    (no_carry, {"loss1", "grad", "positions"}),
    (no_conv, {"loss1", "grad", "positions"}),
    (no_routed, {"grad", "update_routed", "positions"}),
])
def test_a_broken_timed_path_is_not_correct(fault, caught_by, monkeypatch):
    result = run_cell(sabotage=lambda runner: fault(runner, monkeypatch))
    assert result["correct"] is False, json.dumps(result["compared"])
    assert caught_by <= over(result), json.dumps(result["compared"])
