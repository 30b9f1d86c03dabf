"""The readers of the per-layer metrics that came with the runner kind
``lm_hybrid_train``, each on a hand-built record (every number below can be
checked on paper against ``workmodel_hybrid.py``), and the scan reader's
pattern against every instruction that runs under the scope ``lm.ssm`` in the
compiled v5e program of ``nemotron3_nano_train_8k``
(``data/nemotron3_nano_train_8k.ssm_ops.txt``, written by
``tools/hybrid_scan_ops.py`` from a sandbox compile). Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_hybrid_readers.py -q
"""
import importlib.util
import json
import math
import os
import re
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
sys.path.insert(0, PERFBENCH)

import workmodel  # noqa: E402
import workmodel_hybrid  # noqa: E402
from trace_reduce import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={'
CELL = "nemotron3_nano_train_8k"
#: instructions that are no event of their own or move nothing
NO_EVENT = ("get-tuple-element", "bitcast", "constant", "reshape", "while",
            "compare", "add", "subtract", "tuple", "parameter")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(PERFBENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def spec_of():
    return workmodel_hybrid.describe(
        read_json(PERFBENCH, "configs", "nemotron3_nano_l9_e8.json"))


# -- hand-built traces ---------------------------------------------------------
def fwd(n):
    return (f"%flash_attn_fwd.{n} = (f32[32,8192,128]{{2,1,0:T(8,128)}}, "
            "f32[32,8192,1]{2,1,0:T(8,128)}) custom-call(%a, %b, %c)"
            + TAIL + "f32[32,8192,128]{2,1,0}, f32[2,8192,128]{2,1,0}}")


def dq(n):
    return (f"%flash_attn_dq.{n} = f32[32,8192,128]{{2,1,0:T(8,128)}} "
            "custom-call(%a, %b, %c, %d, %e, /*index=5*/%f)" + TAIL
            + "f32[32,8192,128]{2,1,0}, f32[2,8192,128]{2,1,0}}")


def dkv(n):
    return (f"%flash_attn_dkv.{n} = (f32[2,8192,128]{{2,1,0:T(8,128)S(1)}}, "
            "f32[2,8192,128]{2,1,0:T(8,128)}) custom-call(%a, %b, %c)"
            + TAIL + "f32[32,8192,128]{2,1,0}, f32[2,8192,128]{2,1,0}}")


SCAN_OPS = [
    "%fusion.1 = f32[64,8,8,128,128]{4,3,2,1,0:T(8,128)} fusion(%a, %b), "
    "kind=kLoop, calls=%fused_computation.1",
    "%fusion.2 = f32[64,128,8,8,64]{1,4,3,2,0:T(8,128)} fusion(%a, %b), "
    "kind=kOutput, calls=%fused_computation.2",
    "%copy.3 = f32[64,128,8,8]{1,3,2,0:T(8,128)} copy(%a)",
    "%fusion.4 = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8,64,128]{3,2,1,0:T(8,128)"
    "S(1)}) fusion(%a, %b), kind=kLoop, calls=%fused_computation.4",
]
OTHER_OPS = [
    "%fusion.9 = f32[8192,2688]{0,1:T(8,128)} fusion(%a, %b), kind=kLoop, "
    "calls=%fused_computation.9",
    "%fusion.10 = f32[8192,10304]{1,0:T(8,128)} fusion(%a, %b), kind=kOutput, "
    "calls=%fused_computation.10",
    "%while.3 = (s32[], f32[8,8,64,128]{3,2,1,0}) while(%t), condition=%c, "
    "body=%b",
]


def record_of(steps=1, scan_ops=SCAN_OPS, kernels=(fwd, dq, dkv)):
    """``steps`` traced steps of the cell: 5 ms each of four scan ops a
    state-space layer (four layers), 10 ms each attention kernel of the one
    attention layer, and ops that are neither."""
    cfg = read_json(PERFBENCH, "configs", "nemotron3_nano_l9_e8.json")
    traffic = dict(read_json(PERFBENCH, "traffic", "train_8k.json"),
                   trace_calls=steps)
    events, t = [], 0

    def add(name, ms):
        nonlocal t
        events.append(Event(name, t, int(ms * MS)))
        t += int(ms * MS)

    for step in range(steps):
        for _ in range(4):
            for op in scan_ops:
                add(op, 5)
        for n, kernel in enumerate(kernels):
            add(kernel(n), 10)
        for op in OTHER_OPS:
            add(op, 20)
    return {"cfg": cfg, "traffic": traffic, "chips": 1, "peak": PEAK,
            "units": 40 * 8192, "units_per_call": 8192, "window_s": 10.0,
            "call_times": [0.25] * 40,
            "trace": Trace(device={0: events}, host=[], window=(0, t))}


def test_the_work_model_of_the_cell():
    spec = spec_of()
    assert [layer["kind"] for layer in spec["layers"]] == [
        "ssm", "experts", "ssm", "experts", "ssm", "attention", "experts",
        "ssm", "experts"]
    assert spec["share"] == (0, 16) and spec["norm_eps"] == 1e-5
    mixer = spec["layers"][0]
    assert (mixer["heads"], mixer["head_dim"], mixer["state"],
            mixer["groups"], mixer["conv"], mixer["chunk"]) == (
        64, 64, 128, 8, 4, 128)
    experts = spec["layers"][1]
    assert (experts["total"], experts["top_k"], experts["width"],
            experts["shared_width"], experts["scale"]) == (
        128, 6, 1856, 3712, 2.5)
    # 3,072 (token, held expert) pairs a step: 8,192 x 6 / 16
    assert workmodel_hybrid.expected_pairs(8192, experts, 16) == 3072
    # one block of the scan: C B^T once a group and its product with x over
    # 8,256 causal pairs, the block's state and the earlier blocks' part
    # (each 2 x 128 x 128 x 4,096), the carry
    block = (8 * 2 * 8256 * 128 + 64 * 2 * 8256 * 64
             + 2 * 2 * 128 * 128 * 4096 + 2 * 64 * 64 * 128)
    assert workmodel_hybrid.scan_forward_flops(mixer, 8192) == 64 * block
    # a mixer forward: in_proj 2,688 x 10,304, conv 6,144 x 4, scan, out_proj
    assert workmodel_hybrid.layer_forward_flops(spec, mixer, 8192) == (
        2 * 8192 * 2688 * 10304 + 2 * 8192 * 6144 * 4 + 64 * block
        + 2 * 8192 * 4096 * 2688)
    # the attention layer: 32 / 2 heads of 128, the causal half
    assert workmodel_hybrid.layer_forward_flops(
        spec, spec["layers"][5], 8192) == (
        2 * 8192 * 2688 * (4096 + 512) + 2 * 8192 * 4096 * 2688
        + 32 * 4 * (8192 * 8193 / 2) * 128)
    # an expert layer: router, two shared matrices, two routed over the pairs
    assert workmodel_hybrid.layer_forward_flops(spec, experts, 8192) == (
        2 * 8192 * 2688 * 128 + 2 * 2 * 8192 * 2688 * 3712
        + 2 * 2 * 3072 * 2688 * 1856)
    # bytes of one layer's scan: x, B, C, dt in and y out forward (4,096 +
    # 2,048 + 64 + 4,096 floats a position), the same with dy for y
    # backward, and the four gradients out
    flops, nbytes = workmodel_hybrid.scan_work(mixer, 8192)
    assert flops == 3 * 64 * block
    assert nbytes == 4 * 8192 * (10304 + 10304 + 6208)
    assert workmodel_hybrid.train_flops(spec, 8192) == pytest.approx(
        17.5777e12, rel=1e-5)


def test_hybrid_train_step_mfu():
    record = record_of()
    flops = workmodel_hybrid.train_flops(spec_of(), 8192)
    assert reader("hybrid_train_step_mfu").read(record) == pytest.approx(
        100 * 40 * flops / (10 * 197e12))
    assert reader("hybrid_train_step_mfu").read(
        dict(record, cfg={"hidden_size": 8})) is None


def test_ssd_scan_roofline_on_a_hand_built_trace(capsys):
    record = record_of(steps=2)
    flops, nbytes = workmodel_hybrid.scan_work(spec_of()["layers"][0], 8192)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                      # bound by memory
    # 2 steps x 4 layers x 4 ops x 5 ms of matched device time
    assert reader("ssd_scan_roofline").read(record) == pytest.approx(
        100 * 2 * 4 * least / (2 * 4 * 4 * 0.005))
    said = capsys.readouterr().out
    assert "bound by memory" in said
    assert "32 matched events for 8 layer-steps (4.0 a layer-step)" in said
    assert "0.0000 s in 0 events that share" in said


def test_ssd_scan_roofline_takes_every_op_of_the_mixers_inner_width(capsys):
    """An op whose result is ``[S, H P]`` may be the scan's (x's gradient
    sums) or its neighbour's (the gate's): the trace cannot tell, so it is
    taken and the share reads low, never high."""
    gate = ("%fusion.7 = f32[8192,4096]{0,1:T(8,128)} fusion(%a, %b), "
            "kind=kLoop, calls=%fused_computation.7")
    sums = ("%multiply_reduce_fusion.2 = (f32[4096]{0:T(1024)S(1)}, "
            "f32[8192,4096]{0,1:T(8,128)}, f32[4096]{0:T(1024)S(1)}) "
            "fusion(%a, %b), kind=kLoop, calls=%fused_computation.2")
    record = record_of(steps=2, scan_ops=SCAN_OPS + [gate, sums])
    _, nbytes = workmodel_hybrid.scan_work(spec_of()["layers"][0], 8192)
    # 2 steps x 4 layers x 6 ops x 5 ms
    assert reader("ssd_scan_roofline").read(record) == pytest.approx(
        100 * 2 * 4 * (nbytes / 819e9) / (2 * 4 * 6 * 0.005))
    assert ("0.0800 s in 16 events that share the mixers' [S, H P]"
            in capsys.readouterr().out)


def test_ssd_scan_roofline_without_its_ops_reads_nothing(capsys):
    assert reader("ssd_scan_roofline").read(
        record_of(scan_ops=())) is None
    assert reader("ssd_scan_roofline").read(
        dict(record_of(), trace=None)) is None
    # a window that cuts a step: 3 steps' events read as 2 steps
    cut = record_of(steps=3)
    cut["trace"].device[0] = cut["trace"].device[0][:-4 * 4 - 6 + 1]
    cut["traffic"]["trace_calls"] = 2
    assert reader("ssd_scan_roofline").read(cut) is None
    assert "no whole multiple" in capsys.readouterr().out


def test_a_kernel_named_ssd_is_taken_by_its_name():
    ops = ["%ssd_chunk_scan.3 = f32[8192,4096]{1,0} custom-call(%a, %b)"]
    record = record_of(scan_ops=ops)
    assert reader("ssd_scan_roofline").read(record) is not None


def test_hybrid_attn_roofline_on_a_hand_built_trace(capsys):
    record = record_of(steps=2)
    f_f, b_f = workmodel.flash_fwd_work(8192, 32, 2, 128)
    f_b, b_b = workmodel.flash_bwd_work(8192, 32, 2, 128)
    least = max((f_f + f_b) / 197e12, (b_f + b_b) / 819e9)
    assert least == (f_f + f_b) / 197e12                # bound by compute
    assert reader("hybrid_attn_roofline").read(record) == pytest.approx(
        100 * 2 * least / (2 * 3 * 0.010))
    said = capsys.readouterr().out
    assert "bound by compute" in said and "6 events for 2 layer-steps" in said
    assert reader("hybrid_attn_roofline").read(
        record_of(kernels=())) is None
    assert reader("hybrid_attn_roofline").read(
        record_of(kernels=(fwd, dq))) is None            # a kernel missing


def ragged(n, shape="f32[4096,1856]{1,0:T(8,128)}"):
    return (f"%ragged-dot-none.{n} = {shape} custom-call(%a, %b, %c)" + TAIL
            + "f32[4096,2688]{1,0}, f32[8,2688,1856]{2,1,0}, s32[8]{0}}")


def test_moe_relu2_grouped_roofline_on_a_hand_built_trace(capsys):
    """One step, four expert layers: three with one chunk and one with two,
    8 products a chunk at 2 ms each. What is certain whatever the routing:
    per chunk the two weights' gradients write all 8 held matrices and the
    four other counted products read at least one; the one chunk that is
    not a layer's last is full (4,096 rows through six products)."""
    products = [ragged(n) for n in range(8)]
    helper = ("%ragged-dot-metadata.1 = s32[8]{0} custom-call(%a), "
              'custom_call_target="x"')
    record = record_of(scan_ops=products + [helper])
    # record_of repeats scan_ops four times a step: 4 chunks; a fifth
    record["trace"].device[0].extend(
        Event(ragged(10 + n), 10**12 + n, 2 * MS) for n in range(8))
    lo, hi = record["trace"].window
    record["trace"].window = (lo, 10**12 + 10**9)
    matrix = 4 * 2688 * 1856
    nbytes = 5 * (2 * 8 + 4) * matrix + 6 * 4 * 4096 * (2688 + 1856)
    flops = 6 * 2 * 4096 * 2688 * 1856
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9
    got = reader("moe_relu2_grouped_roofline").read(record)
    assert got == pytest.approx(100 * least / (4 * 8 * 0.005 + 8 * 0.002))
    said = capsys.readouterr().out
    assert "a floor, bound by memory" in said
    assert "40 products, 5 chunks" in said
    assert reader("moe_relu2_grouped_roofline").read(
        record_of(scan_ops=())) is None
    assert reader("moe_relu2_grouped_roofline").read(
        dict(record, cfg={"hidden_size": 8})) is None      # another kind's


# -- the pattern against the compiled program ------------------------------------
def compiled_ops():
    """[(scope, pass, times a step, text)] of the data file's
    instructions (``tools/hybrid_scan_ops.py`` says what it lists)."""
    path = os.path.join(TESTS, "data", CELL + ".ssm_ops.txt")
    rows = []
    with open(path) as f:
        for line in f:
            scope, times, text = line.rstrip("\n").split("\t")
            which, _, part = scope.rpartition(":")
            rows.append((part, which or "F", times, text))
    return rows


def kind_of(text):
    return re.search(r" = .*? ([a-z][\w\-]*)\(", text).group(1)


def test_the_pattern_takes_every_op_of_the_scan_and_names_what_else():
    """The two lists held together: every instruction of the compile under
    the scope ``scan`` (forward, recomputed, backward) beside what the
    pattern takes. It takes every one that is an event and moves more than
    a vector of H P elements; what it takes of other scopes is named here:
    the initial values of the loops' carries and the copies of the carried
    state (no ``op_name`` at all; the scan's by their shapes) and the ops that share ``[S, H P]`` with the scan (the gate's and its norm's,
    the out-projection's backward product, the attention layer's query
    projection and its output's gradient), which make the share read low.
    The events a step are pinned: 3,829 of the scan's own shapes, as the
    chip's first traced run counted (30,632 in 8 steps; PERF.md section 5),
    and 26 of the shared one."""
    mixer = spec_of()["layers"][0]
    metric = reader("ssd_scan_roofline")
    rx = re.compile(metric.pattern(mixer, 8192))
    own = re.compile(metric.pattern(mixer, 8192, shared=False))
    small = re.compile(
        r"^%[\w.\-]+ = (?:\((?:f32\[4096\]\{[^}]*\}(?:, )?)+\)"
        r"|f32\[(?:64|64,64)\]\{[^}]*\}|pred\[128,128\]\{[^}]*\}) ")
    rows = compiled_ops()
    assert {w for p, w, _, _ in rows if p == "scan"} == {"F", "R", "B"}
    events = {"own": 0, "shared": 0}
    scans_own = {"F": 0, "R": 0, "B": 0}
    others = {}
    for part, which, times, text in rows:
        if kind_of(text) in NO_EVENT:
            continue
        if not rx.search(text):
            # of the scan's, only vectors and the triangular mask are left
            assert part != "scan" or small.search(text), text[:200]
            continue
        is_own = bool(own.search(text))
        events["own" if is_own else "shared"] += int(times)
        if part == "scan":
            scans_own[which] += is_own
        elif is_own:
            # no op_name at all: a carry's initial value, or the copy of
            # the carried state that the loop over the blocks makes
            assert part == "-" and re.search(
                r" (?:broadcast|copy)\(| fusion\(\)", text), text[:200]
        else:
            others[(which, part)] = others.get((which, part), 0) + 1
    # the fusions, copies and broadcasts of the four layers' forward pass,
    # of the recomputed one and of the backward pass (19, 23 and 28 a layer;
    # those in the loop over the blocks run 64 times each)
    assert scans_own == {"F": 76, "R": 92, "B": 112}, scans_own
    assert events == {"own": 3829, "shared": 26}, events
    attn = [k for k in others if k[1].startswith("-")]
    assert sorted(others[k] for k in attn) == [1, 1], others
    assert {k: v for k, v in others.items() if k not in attn} == {
        ("F", "gate_norm"): 4, ("R", "gate_norm"): 4, ("B", "gate_norm"): 4,
        ("B", "out"): 4}, others
