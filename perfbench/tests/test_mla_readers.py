"""The readers of the per-layer metrics that came with the runner kind
``lm_mla_train``, each on a hand-built record whose kernel text is the
compiled v5e program's (read off ``aot_lower(...).compile().as_text()``):
every number below can be checked on paper against ``workmodel_mla.py``.
Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_mla_readers.py -q
"""
import importlib.util
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
sys.path.insert(0, PERFBENCH)

import workmodel  # noqa: E402
import workmodel_mla  # noqa: E402
from trace_reduce import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
TAIL = (', custom_call_target="tpu_custom_call", operand_layout_constraints={'
        "f32[32,8192,192]{2,1,0}, f32[32,8192,192]{2,1,0}, "
        "f32[32,8192,128]{2,1,0}}")
T = "{2,1,0:T(8,128)}"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(PERFBENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def fwd(n):
    return (f"%flash_attn_fwd.{n} = (f32[32,8192,128]{T}, f32[32,8192,1]{T}) "
            "custom-call(%maximum_bitcast_fusion, %a, %bitcast.37)" + TAIL)


def dq(n):
    return (f"%flash_attn_dq.{n} = f32[32,8192,192]{T} custom-call(%a, %b, "
            "%c, %d, %copy.1159, /*index=5*/%copy.1160)" + TAIL)


def dkv(n):
    return (f"%flash_attn_dkv.{n} = (f32[32,8192,192]{T}, f32[32,8192,128]{T}) "
            "custom-call(%a, %b, %c, %d, %copy.1159, /*index=5*/%e)" + TAIL)


@pytest.fixture
def record():
    """One traced step of the cell: six latent applications, each one
    forward kernel of 4 ms and a dq and a dkv of 5 ms each; a fusion that is
    none of them."""
    with open(os.path.join(PERFBENCH, "configs",
                           "joyai_flash_l5_mtp1.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(PERFBENCH, "traffic", "train_8k.json")) as f:
        traffic = dict(json.load(f), trace_calls=1)
    events, t = [], 0
    for n in range(6):
        for name, ms in ((fwd(n), 4), (dq(n), 5), (dkv(n), 5)):
            events.append(Event(name, t, ms * MS))
            t += ms * MS
    events.append(Event("%fusion.7 = f32[8192,2048]{1,0} fusion(%a)", t,
                        20 * MS))
    t += 20 * MS
    return {"cfg": cfg, "traffic": traffic, "chips": 1, "peak": PEAK,
            "units": 30 * 8192, "units_per_call": 8192, "window_s": 10.0,
            "call_times": [0.33] * 30,
            "program_counters": {"latent_layers_traced": {
                "heads=32,q_rank=1536,kv_rank=512,qk_dim=192,v_dim=128": 12}},
            "trace": Trace(device={0: events}, host=[], window=(0, t))}


def test_the_work_model_of_the_cell(record):
    spec = workmodel_mla.describe(record["cfg"])
    assert (spec["heads"], spec["q_rank"], spec["kv_rank"], spec["nope"],
            spec["rope_dim"], spec["v_dim"]) == (32, 1536, 512, 128, 64, 128)
    assert [layer["ffn"] for layer in spec["layers"]] == [
        "gated", "experts", "experts", "experts", "experts"]
    assert spec["mtp"]["layer"]["ffn"] == "experts"
    assert spec["mtp"]["weight"] == 0.3 and spec["share"] == (0, 16)
    assert spec["layers"][1]["experts"] == {
        "total": 256, "top_k": 8, "width": 768, "shared_width": 768,
        "scale": 2.5}
    S, pairs = 8192, 8192 * 8193 / 2
    # a latent layer's five products and attention at 192 + 128
    proj = 2 * S * (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                    + 4096 * 2048)
    assert workmodel_mla.latent_projection_flops(spec, S) == proj
    assert workmodel_mla.attention_flops(spec, S) == 32 * 2 * pairs * 320
    # 27.84 TFLOP a step, 72% in latent attention, 21% in the MTP module
    total = workmodel_mla.train_flops(spec, S)
    assert total == pytest.approx(27.8393e12, rel=1e-4)
    latent = 3 * 6 * (proj + 32 * 2 * pairs * 320)
    assert latent / total == pytest.approx(0.7235, abs=1e-3)


def test_flash_work_counts_the_two_widths_apart():
    (f1, b1), (f2, b2) = workmodel_mla.flash_work(8192, 32, 192, 128)
    pairs = 8192 * 8193 / 2
    assert f1 == 32 * 2 * pairs * (192 + 128)
    assert f2 == 2 * f1
    assert b1 == 4 * 8192 * 32 * (2 * 192 + 2 * 128 + 1)
    assert b2 == 4 * 8192 * 32 * (4 * 192 + 4 * 128 + 2)
    # at one width they are workmodel's own
    (g1, c1), (g2, c2) = workmodel_mla.flash_work(8192, 32, 128, 128)
    assert (g1, c1) == workmodel.flash_fwd_work(8192, 32, 32, 128)
    assert (g2, c2) == workmodel.flash_bwd_work(8192, 32, 32, 128)


def test_mla_train_step_mfu(record):
    # 30 steps of 27.84 TFLOP in 10 s on one chip of 197 TFLOP/s
    spec = workmodel_mla.describe(record["cfg"])
    assert reader("mla_train_step_mfu").read(record) == pytest.approx(
        100 * 30 * workmodel_mla.train_flops(spec, 8192) / (10 * 197e12))


def test_mla_attn_roofline(record, capsys):
    # 6 forward events and 6 dq / dkv pairs in 84 ms of kernel time; the
    # kernels are compute-bound
    (f1, b1), (f2, b2) = workmodel_mla.flash_work(8192, 32, 192, 128)
    assert f1 / 197e12 > b1 / 819e9 and f2 / 197e12 > b2 / 819e9
    least = 6 * (f1 + f2) / 197e12
    assert reader("mla_attn_roofline").read(record) == pytest.approx(
        100 * least / 0.084)
    line = capsys.readouterr().out
    assert "(6 / 6 / 6 a step; latent_layers_traced 12)" in line


def test_nothing_to_read_is_nothing(record):
    """No trace, a trace without the kernels (the interpreter, a program
    that lacks them), another configuration, or dq and dkv events that do
    not pair: the readers return nothing and do not raise."""
    read = reader("mla_attn_roofline").read
    assert read(dict(record, trace=None)) is None
    bare = Trace(device={0: [Event("%fusion.1 = f32[8]{0} fusion(%a)", 0, MS)]},
                 host=[], window=(0, MS))
    assert read(dict(record, trace=bare)) is None
    events = [e for e in record["trace"].device[0]
              if not e.name.startswith("%flash_attn_dkv.0 ")]
    assert read(dict(record, trace=Trace(device={0: events}, host=[],
                                         window=record["trace"].window))) is None
    other = {k: v for k, v in record["cfg"].items() if k != "kv_lora_rank"}
    for name in ("mla_attn_roofline", "mla_train_step_mfu"):
        assert reader(name).read(dict(record, cfg=other)) is None
