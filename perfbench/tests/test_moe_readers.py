"""The readers of the per-layer metrics that came with the runner kind
``lm_moe_train``, each on a hand-built record: every number below can be
checked on paper against ``workmodel_moe.py``. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_moe_readers.py -q
"""
import importlib.util
import json
import os
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(PERFBENCH)
sys.path.insert(0, PERFBENCH)

import workmodel  # noqa: E402
import workmodel_moe  # noqa: E402
from trace_reduce import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={'


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(PERFBENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def fwd(heads, n):
    return (f"%flash_attn_fwd.{n} = (f32[{heads},8192,128]{{2,1,0:T(8,128)}}, "
            f"f32[{heads},8192,1]{{2,1,0:T(8,128)}}) custom-call(%a, %b, %c)"
            + TAIL + f"f32[{heads},8192,128]{{2,1,0}}, f32[8,8192,128]{{2,1,0}}}}")


def dq(heads, n):
    return (f"%flash_attn_dq.{n} = f32[{heads},8192,128]{{2,1,0:T(8,128)}} "
            "custom-call(%a, %b, %c, %d, %e, /*index=5*/%f)" + TAIL
            + f"f32[{heads},8192,128]{{2,1,0}}, f32[8,8192,128]{{2,1,0}}}}")


def dkv(heads, n, operands_have_shapes=False):
    q = (f"f32[{heads},8192,128]{{2,1,0}} %a" if operands_have_shapes
         else "%a")
    return (f"%flash_attn_dkv.{n} = (f32[8,8192,128]{{2,1,0:T(8,128)S(1)}}, "
            f"f32[8,8192,128]{{2,1,0:T(8,128)}}) custom-call({q}, %b, %c)"
            + TAIL + f"f32[{heads},8192,128]{{2,1,0}}, f32[8,8192,128]{{2,1,0}}}}")


def grouped(n, shape="f32[4096,512]"):
    return (f"%ragged-dot-none.{n} = {shape}{{1,0:T(8,128)}} custom-call(%g, "
            "%h, %i, %j, %g, /*index=5*/%x, %w)" + TAIL + "s32[1]{0}}")


METADATA = ("%ragged-dot-metadata.3 = (s32[9]{0:T(128)S(1)}, s32[15]{0}) "
            "custom-call(%gs), custom_call_target=\"tpu_custom_call\"")


@pytest.fixture
def record():
    """One traced step of the cell: 10 ms an attention kernel of a window
    layer, 30 ms one of a full layer, 1 ms a grouped product, 0.5 ms the
    helper; a fusion that is none of them."""
    cfg = read_json(PERFBENCH, "configs", "laguna_xs2_l5_e8.json")
    traffic = dict(read_json(PERFBENCH, "traffic", "train_8k.json"),
                   trace_calls=1)
    events, t = [], 0

    def add(name, ms):
        nonlocal t
        events.append(Event(name, t, int(ms * MS)))
        t += int(ms * MS)

    for n in range(3):                      # three window layers, 64 heads
        add(fwd(64, n), 10)
        add(dq(64, n), 10)
        add(dkv(64, n, operands_have_shapes=n == 0), 10)
    for n in range(3, 5):                   # two full layers, 48 heads
        add(fwd(48, n), 30)
        add(dq(48, n), 30)
        add(dkv(48, n), 30)
    for n in range(48):                     # 12 grouped products a layer
        add(grouped(n, "f32[8,2048,512]" if n % 12 > 8 else "f32[4096,512]"), 1)
    add(METADATA, 0.5)
    add("%fusion.7 = f32[8192,2048]{1,0} fusion(f32[8192,2048]{1,0} %a)", 20)
    return {"cfg": cfg, "traffic": traffic, "chips": 1, "peak": PEAK,
            "units": 40 * 8192, "units_per_call": 8192, "window_s": 10.0,
            "call_times": [0.25] * 40,
            "trace": Trace(device={0: events}, host=[], window=(0, t))}


def test_the_work_model_of_the_cell(record):
    spec = workmodel_moe.describe(record["cfg"])
    assert [(layer["heads"], layer["window"], layer["ffn"])
            for layer in spec["layers"]] == [
        (48, None, "gated"), (64, 512, "experts"), (64, 512, "experts"),
        (64, 512, "experts"), (48, None, "experts")]
    assert spec["share"] == (0, 32)
    assert spec["layers"][1]["experts"]["total"] == 256
    assert spec["layers"][0]["rope"]["rotary"] == 64
    assert spec["layers"][1]["rope"] == {"base": 10000.0, "rotary": None,
                                         "yarn": None}
    # 2,048 (token, held expert) pairs a step: 8,192 x 8 / 32
    assert workmodel_moe.expected_pairs(
        8192, spec["layers"][1]["experts"], 32) == 2048
    # a window layer forward: projections 2 x 8192 x 2048 x (8192 + 2048 +
    # 8192), 64 heads x 4 x 4,063,488 pairs x 128, router, shared, routed
    window_layer = (2 * 8192 * 2048 * (64 * 128 * 2 + 2048)
                    + 64 * 4 * (512 * 513 / 2 + 7680 * 512) * 128
                    + 2 * 8192 * 2048 * 256 + 3 * 2 * 8192 * 2048 * 512
                    + 3 * 2 * 2048 * 2048 * 512)
    assert workmodel_moe.layer_forward_flops(
        spec, spec["layers"][1], 8192) == pytest.approx(window_layer)
    assert workmodel_moe.train_flops(spec, 8192) == pytest.approx(
        19.212e12, rel=1e-4)
    # without the windows the window layers attend every causal pair
    full = workmodel_moe.describe(record["cfg"], use_window=False)
    assert all(layer["window"] is None for layer in full["layers"])


def test_moe_train_step_mfu(record):
    # 40 steps of 19.212 TFLOP in 10 s on one chip of 197 TFLOP/s
    assert reader("moe_train_step_mfu").read(record) == pytest.approx(
        100 * 40 * 19.212e12 / (10 * 197e12), rel=1e-4)


def test_attention_rooflines_tell_the_kinds_apart(record):
    spec = workmodel_moe.describe(record["cfg"])
    for name, windowed, heads, window, layers, ms in [
            ("attn_window_roofline", True, 64, 512, 3, 90.0),
            ("attn_full_roofline", False, 48, None, 2, 180.0)]:
        f1, b1 = workmodel.flash_fwd_work(8192, heads, 8, 128, window=window)
        f2, b2 = workmodel.flash_bwd_work(8192, heads, 8, 128, window=window)
        least = max(layers * (f1 + f2) / 197e12, layers * (b1 + b2) / 819e9)
        assert workmodel_moe.attention_work(spec, 8192, windowed) == (
            layers * (f1 + f2), layers * (b1 + b2), layers)
        assert reader(name).read(record) == pytest.approx(
            100 * least / (ms / 1e3))
    # the window layers' kernels are bound by HBM, the full layers' by compute
    f, b, _ = workmodel_moe.attention_work(spec, 8192, True)
    assert workmodel.least_seconds(f, b, PEAK)[1] == "memory"
    f, b, _ = workmodel_moe.attention_work(spec, 8192, False)
    assert workmodel.least_seconds(f, b, PEAK)[1] == "compute"


def test_moe_grouped_roofline_counts_what_is_certain(record):
    # 48 products = 4 chunks in 4 layer-steps, so no chunk is known to be
    # full: per chunk 3 weights' gradients write 8 matrices each and 6 other
    # products read one: 30 matrices of 2048 x 512 float32; 48 ms of kernels
    # (the helper's 0.5 ms is not theirs)
    spec = workmodel_moe.describe(record["cfg"])
    matrix = 4 * 2048 * 512
    assert workmodel_moe.grouped_work(spec, 4096, 4, 4) == (0.0, 4 * 30 * matrix)
    read = reader("moe_grouped_roofline").read
    assert read(record) == pytest.approx(
        100 * (4 * 30 * matrix / 819e9) / 0.048)
    # 24 more products: 6 chunks in 4 layer-steps, so two chunks hold 4,096
    # rows, which each of the 9 counted products reads and writes
    events = list(record["trace"].device[0])
    t = record["trace"].window[1]
    for n in range(48, 72):
        events.append(Event(grouped(n), t, MS))
        t += MS
    record["trace"] = Trace(device={0: events}, host=[], window=(0, t))
    flops = 9 * 2 * 8192 * 2048 * 512
    nbytes = 6 * 30 * matrix + 9 * 4 * 8192 * (2048 + 512)
    assert workmodel_moe.grouped_work(spec, 4096, 6, 4) == (flops, nbytes)
    assert nbytes / 819e9 > flops / 197e12              # bound by HBM
    assert read(record) == pytest.approx(100 * (nbytes / 819e9) / 0.072)


def test_moe_load_max_over_mean_reads_the_programs_gauges(record):
    read = reader("moe_load_max_over_mean").read
    record["program_gauges"] = {
        "moe_expert_load_max": {"layer=0": 300.0, "layer=1": 281.0},
        "moe_expert_load_mean": {"layer=0": 250.0, "layer=1": 256.0}}
    assert read(record) == pytest.approx(1.2)
    # the gauges as the program's registry holds them
    sys.path.insert(1, ROOT)
    from fiber_tpu.telemetry import device as device_telemetry

    del record["program_gauges"]
    device_telemetry.moe_load([[260, 250, 240, 274], [256, 256, 256, 256]])
    assert read(record) == pytest.approx(274 / 256)


@pytest.mark.parametrize("name", ["attn_window_roofline", "attn_full_roofline",
                                  "moe_grouped_roofline"])
def test_nothing_to_read_is_nothing(record, name):
    """No trace, or a trace without the kernels (the interpreter, a program
    that lacks them): the reader returns nothing and does not raise."""
    read = reader(name).read
    assert read(dict(record, trace=None)) is None
    bare = Trace(device={0: [Event("%fusion.1 = f32[8]{0} fusion(%a)", 0, MS)]},
                 host=[], window=(0, MS))
    assert read(dict(record, trace=bare)) is None


def test_no_gauge_is_nothing(record):
    record["program_gauges"] = {}
    assert reader("moe_load_max_over_mean").read(record) is None


def test_kinds_with_one_head_count_cannot_be_told_apart(record):
    cfg = dict(record["cfg"],
               num_attention_heads_per_layer=[64] * 40)
    assert reader("attn_full_roofline").read(dict(record, cfg=cfg)) is None
