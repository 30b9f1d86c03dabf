"""Analytic FLOP counters + MFU accounting (utils/flops.py)."""

import pytest

from fiber_tpu.utils import flops


def test_matmul_and_attention_flops():
    assert flops.matmul_flops(4, 8, 16) == 2 * 4 * 8 * 16
    # Full (non-causal) attention: QK^T and P.V are each 2*S*S*D per
    # head; causal halves; train triples.
    s, h, d = 128, 4, 32
    full = flops.attention_flops(s, h, d, causal=False)
    assert full == 2 * (2 * s * s * d) * h
    assert flops.attention_flops(s, h, d, causal=True) == full / 2
    assert flops.attention_flops(s, h, d, causal=True, train=True) == \
        full / 2 * 3


def test_tinylm_flops_hand_count():
    from fiber_tpu.models import TinyLM

    m = TinyLM(vocab=256, dim=64, heads=8, layers=2, max_seq=128)
    s, d = 128, 64
    per_block = (
        2 * s * d * 3 * d      # wqkv
        + 2 * s * d * d        # wo
        + 2 * s * d * 4 * d    # w1
        + 2 * s * 4 * d * d    # w2
        + 2 * s * s * d        # causal attention (4*S^2*dim / 2)
    )
    fwd = 2 * per_block + 2 * s * d * 256
    assert flops.tinylm_flops_per_step(m, s, train=False) == fwd
    assert flops.tinylm_flops_per_step(m, s, train=True) == 3 * fwd


def test_policy_flops_counters():
    from fiber_tpu.models import ConvPolicy, GRUPolicy, MLPPolicy

    mlp = MLPPolicy(4, 2, hidden=(32, 32))
    assert flops.policy_flops_per_action(mlp) == \
        2 * (4 * 32 + 32 * 32 + 32 * 2)

    gru = GRUPolicy(4, 2, hidden=16)
    assert flops.policy_flops_per_action(gru) == \
        3 * 2 * (4 * 16 + 16 * 16) + 2 * 16 * 2

    conv = ConvPolicy((24, 24, 1), 5)
    got = flops.policy_flops_per_action(conv)
    assert got > 0
    # First conv layer alone: 12x12 output, 3x3x1 -> first out_c.
    _, (_, _, in_c, out_c) = conv._specs[0]
    assert got > 2 * 12 * 12 * 9 * in_c * out_c


def test_rollout_and_es_gen_flops_compose():
    from fiber_tpu.models import MLPPolicy

    mlp = MLPPolicy(4, 2, hidden=(32, 32))
    per_eval = flops.rollout_flops_per_eval(mlp, "CartPole", 500)
    assert per_eval == 500 * (flops.policy_flops_per_action(mlp)
                              + flops.ENV_STEP_FLOPS["CartPole"])
    gen = flops.es_flops_per_gen(mlp, "CartPole", 500, 4096, mlp.dim)
    assert gen == 4096 * per_eval + 2 * 4096 * mlp.dim \
        + 4 * 4096 * mlp.dim


def test_mfu_none_on_cpu():
    """The CPU has no peak row: "no peak" is an honest answer, and no
    environment variable stands in for one (FIBER_PEAK_FLOPS is gone)."""
    import jax

    dev = jax.devices()[0]  # CPU under the test tier
    assert flops.device_peak_flops(dev) is None
    assert flops.mfu(1e12, [dev]) is None
    assert flops.peak_report([dev])["peak_row"] is None


class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_peak_table_lookup():
    assert flops.device_peak_flops(_FakeTpu("TPU v4")) == 275e12
    assert flops.device_peak_flops(_FakeTpu("TPU v3")) == 61.5e12
    assert flops.device_peak_flops(_FakeTpu("TPU v5 lite")) == 197e12
    assert flops.device_peak_flops(_FakeTpu("TPU v5p")) == 459e12
    assert flops.device_peak_flops(_FakeTpu("TPU v6e")) == 918e12
    dev = _FakeTpu("TPU v5 lite")
    assert flops.mfu(197e12, [dev, dev]) == pytest.approx(0.5)


@pytest.mark.parametrize("kind", ["TPU v99", "TPU v77 mystery", "TPU v5"])
def test_peak_table_miss_raises(kind, monkeypatch):
    """A TPU device_kind with no peak row is an error — not a silent
    null MFU, not a guess (a bare "v5" is not assumed to be a v5p), and
    not an environment variable's stand-in."""
    monkeypatch.setenv("FIBER_PEAK_FLOPS", "2e12")  # must be ignored
    for call in (flops.device_peak_flops,
                 lambda d: flops.mfu(1e12, [d]),
                 lambda d: flops.peak_report([d])):
        with pytest.raises(LookupError, match=kind.lower()):
            call(_FakeTpu(kind))


def test_peak_report_fields():
    """bench records carry device_kind + the peak row it resolved to."""
    rep = flops.peak_report([_FakeTpu("TPU v5 lite")])
    assert rep["device_kind"] == "tpu v5 lite"
    assert rep["peak_row"] == "v5 lite:1.97e+14"


def test_tinylm_windowed_flops_honest():
    """A windowed TinyLM must be credited windowed attention FLOPs, not
    full-causal (advisor r4 #1): same model, window set, counts less."""
    from fiber_tpu.models import TinyLM

    full = TinyLM(vocab=256, dim=64, heads=8, layers=2, max_seq=4096)
    windowed = TinyLM(vocab=256, dim=64, heads=8, layers=2,
                      max_seq=4096, window=256, attention="flash",
                      interpret=True)
    f_full = flops.tinylm_flops_per_step(full, 4096, train=False)
    f_win = flops.tinylm_flops_per_step(windowed, 4096, train=False)
    assert f_win < f_full
    # the delta is exactly the attention delta
    att_full = flops.attention_flops(4096, 8, 8, causal=True)
    att_win = flops.attention_flops(4096, 8, 8, causal=True, window=256)
    assert f_full - f_win == pytest.approx(2 * (att_full - att_win))


def test_windowed_attention_flops():
    """Windowed FLOPs: ramp-up prefix + steady state, never more than
    full causal, linear in window for seq >> window."""
    s, h, d = 4096, 4, 64
    full = flops.attention_flops(s, h, d, causal=True)
    w256 = flops.attention_flops(s, h, d, causal=True, window=256)
    w512 = flops.attention_flops(s, h, d, causal=True, window=512)
    assert w256 < w512 < full
    # exact hand count at window=256: 256*257/2 ramp + (4096-256)*256
    kv = 256 * 257 / 2 + (4096 - 256) * 256
    assert w256 == 2 * 2 * kv * d * h
    # window >= seq degrades to full causal (the windowed count is the
    # exact s(s+1)/2 sum; the legacy causal formula approximates s^2/2)
    w_full = flops.attention_flops(s, h, d, causal=True, window=s)
    assert abs(w_full / full - 1) < 1e-3
