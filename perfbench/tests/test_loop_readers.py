"""The readers of the per-layer metrics that came with the runner kind
``lm_loop_train``, each on a hand-built record (every number below can be
checked on paper against ``workmodel_loop.py``), and the head reader's
patterns against every instruction that runs under the scope ``lm.head_loss``
in the compiled v5e program of ``ouro_2p6b_train_8k``
(``data/ouro_2p6b_train_8k.head_ops.txt``, written by
``tools/loop_head_ops.py`` from a sandbox compile). Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/tests/test_loop_readers.py -q
"""
import importlib.util
import json
import os
import re
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
PERFBENCH = os.path.dirname(TESTS)
sys.path.insert(0, PERFBENCH)

import workmodel  # noqa: E402
import workmodel_loop  # noqa: E402
from trace_reduce import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
TAIL = ', custom_call_target="tpu_custom_call", operand_layout_constraints={'
CELL = "ouro_2p6b_train_8k"
S, V, D = 8192, 49152, 2048


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(PERFBENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cfg_of():
    return read_json(PERFBENCH, "configs", "ouro_2p6b_l4.json")


def record_of(events=(), steps=1, spans=None, **kw):
    """A record of ``steps`` traced steps whose device ran ``events``
    ((text, ms) one after another)."""
    traffic = dict(read_json(PERFBENCH, "traffic", "train_8k.json"),
                   trace_calls=steps)
    out, t = [], 0
    for name, ms in events:
        if isinstance(ms, tuple):               # a container: (start, ms)
            out.append(Event(name, int(ms[0] * MS), int(ms[1] * MS)))
            continue
        out.append(Event(name, t, int(ms * MS)))
        t += int(ms * MS)
    run = {"cfg": cfg_of(), "traffic": traffic, "chips": 1, "peak": PEAK,
           "units": 16 * S, "units_per_call": S, "window_s": 16.0,
           "call_times": [1.0] * 16,
           "trace": Trace(device={0: out}, host=[], window=(0, max(t, 1)))}
    if spans is not None:
        run["program_spans"] = spans
    run.update(kw)
    return run


def call_spans(n=16, **fields):
    return [{"name": "lm.train_step", "start_ns": i * 10, "end_ns": i * 10 + 9,
             "layers": "full/gated," * 3 + "full/gated", "tokens": S,
             **fields} for i in range(n)]


# -- the work model ------------------------------------------------------------
def test_the_work_model_of_the_cell():
    spec = workmodel_loop.describe(cfg_of())
    assert spec == {"vocab": V, "dim": D, "heads": 16, "kv_heads": 16,
                    "head_dim": 128, "width": 5632, "layers": 4, "passes": 4,
                    "rope_base": 1e6, "norm_eps": 1e-6, "beta": 0.05}
    # one layer application: four 2,048 x 2,048 projections, the causal
    # pairs of 16 heads of 128, three products at 5,632
    layer = (4 * 2 * S * D * D + 16 * 4 * (S * (S + 1) / 2) * 128
             + 3 * 2 * S * D * 5632)
    assert workmodel_loop.layer_forward_flops(spec, S) == layer
    head = 2 * S * D * V + 2 * S * D
    assert workmodel_loop.head_forward_flops(spec, S) == head
    assert workmodel_loop.train_flops(spec, S) == 3 * 4 * (4 * layer + head)
    # 73.4 TFLOP a step (layers' products 16 x 2.24 forward, attention
    # 16 x 0.275, heads 4 x 1.65), the heads 27% of it
    assert workmodel_loop.train_flops(spec, S) == pytest.approx(73.4e12,
                                                                rel=0.001)
    assert 3 * 4 * head / workmodel_loop.train_flops(spec, S) == \
        pytest.approx(0.27, abs=0.005)
    flops, nbytes = workmodel_loop.head_loss_work(spec, S)
    assert flops == 3 * 4 * 2 * S * D * V
    assert nbytes == 4 * 4 * 4 * S * V + 3 * 4 * D * V


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention"] * 3), ("hidden_act", "gelu"),
    ("sliding_window", 4096), ("early_exit_threshold", 0.9),
    ("tie_word_embeddings", True)])
def test_a_configuration_the_kind_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError):
        workmodel_loop.describe(dict(cfg_of(), **{key: value}))


# -- loop_train_step_mfu -------------------------------------------------------
def test_the_steps_share_of_the_peak():
    spec = workmodel_loop.describe(cfg_of())
    run = record_of(spans=call_spans(passes=4, recompute="layer+head"))
    # 16 steps in 16 s
    assert reader("loop_train_step_mfu").read(run) == pytest.approx(
        100 * workmodel_loop.train_flops(spec, S) / 197e12)


@pytest.mark.parametrize("spans", [
    call_spans(passes=3), call_spans(), [],
    call_spans(15, passes=4) + call_spans(1, passes=3)])
def test_spans_that_say_other_passes_give_nothing(spans, capsys):
    assert reader("loop_train_step_mfu").read(record_of(spans=spans)) is None
    assert "nothing" in capsys.readouterr().out


def test_another_kind_of_configuration_gives_every_reader_nothing():
    cfg = read_json(PERFBENCH, "configs", "starcoder2_3b_l4.json")
    run = record_of(spans=call_spans(passes=4), cfg=cfg)
    for name in ("loop_train_step_mfu", "loop_attn_roofline",
                 "loop_head_loss_roofline"):
        assert reader(name).read(run) is None


# -- loop_attn_roofline --------------------------------------------------------
HSD = "f32[16,8192,128]{2,1,0:T(8,128)}"


def fwd(n):
    return (f"%flash_attn_fwd.{n} = ({HSD}, f32[16,8192,1]{{2,1,0:T(8,128)}})"
            " custom-call(%a, %b, %c)" + TAIL + "f32[16,8192,128]{2,1,0}}")


def dq(n):
    return (f"%flash_attn_dq.{n} = {HSD} custom-call(%a, %b, %c, %d, %e, "
            "/*index=5*/%f)" + TAIL + "f32[16,8192,128]{2,1,0}}")


def dkv(n):
    return (f"%flash_attn_dkv.{n} = (f32[16,8192,128]{{2,1,0:T(8,128)S(1)}}, "
            f"{HSD}) custom-call(%a, %b, %c)" + TAIL
            + "f32[16,8192,128]{2,1,0}}")


OTHER = ("%fusion.9 = f32[8192,2048]{1,0:T(8,128)} fusion(%a, %b), "
         "kind=kOutput, calls=%fused_computation.9")


def kernel_events(steps, kinds=(fwd, fwd, dq, dkv)):
    """16 layer applications a step, each its kernels at 4 ms and another op
    at 10 ms: the forward kernel runs twice (the checkpoint)."""
    return [(kernel(n), 4) if kernel else (OTHER, 10)
            for n in range(16 * steps) for kernel in kinds + (None,)]


@pytest.mark.parametrize("steps", [1, 2])
def test_the_kernels_share_counts_the_events_that_ran(steps, capsys):
    shape = (S, 16, 16, 128)
    least_fwd, bound = workmodel.least_seconds(
        *workmodel.flash_fwd_work(*shape), PEAK)
    least_bwd, _ = workmodel.least_seconds(
        *workmodel.flash_bwd_work(*shape), PEAK)
    value = reader("loop_attn_roofline").read(
        record_of(kernel_events(steps), steps))
    assert value == pytest.approx(
        100 * (32 * least_fwd + 16 * least_bwd) / (64 * 0.004))
    said = capsys.readouterr().out
    assert f"bound by {bound}" in said and "(32 / 16 / 16 a step)" in said
    # one more rung of the ladder (the forward kernel a third time) changes
    # the events counted, not the share's meaning: all kernels at 4 ms
    again = reader("loop_attn_roofline").read(
        record_of(kernel_events(steps, (fwd, fwd, fwd, dq, dkv)), steps))
    assert again == pytest.approx(
        100 * (48 * least_fwd + 16 * least_bwd) / (80 * 0.004))


def test_no_kernel_event_or_unpaired_ones_give_nothing(capsys):
    read = reader("loop_attn_roofline").read
    assert read(record_of([(OTHER, 5)])) is None
    assert read(record_of(kernel_events(1, (fwd, fwd, dq)))) is None
    assert "do not pair" in capsys.readouterr().out
    assert read(dict(record_of(), trace=None)) is None


# -- loop_head_loss_roofline ---------------------------------------------------
LOOP_FWD = ("%while.14 = (s32[]{:T(128)}, f32[32,1024]{1,0:T(8,128)}, "
            "bf16[32,1024,2048]{2,1,0:T(8,128)(2,1)}, bf16[2048,49152]{1,0:"
            "T(8,128)(2,1)}) while(%tuple.622), condition=%c, body=%b")
LOOP_BWD = ("%while.15 = (s32[]{:T(128)}, f32[2048,49152]{1,0:T(8,128)}, "
            "f32[32,1024,2048]{2,1,0:T(8,128)}) while(%tuple.620), "
            "condition=%c, body=%b")
LOOP_PASSES = ("%while.9 = (s32[]{:T(128)}, f32[8192,2048]{1,0:T(8,128)}, "
               "f32[4,8192,2048]{2,1,0:T(8,128)}) while(%tuple.1), "
               "condition=%c, body=%b")
LOGITS = ("%fusion.709 = (f32[1024]{0:T(1024)S(1)}, f32[1024,49152]{1,0:"
          "T(8,128)}) fusion(%a, %b, %c), kind=kOutput, calls=%f.837")
REDUCE = ("%exponential_reduce_fusion.2 = f32[1024]{0:T(1024)S(1)} "
          "fusion(%a, %b), kind=kLoop, calls=%f.8")
GRAD = ("%convolution_add_fusion.22 = f32[2048,49152]{1,0:T(8,128)} "
        "fusion(%a, %b), kind=kOutput, calls=%f.22")
ADAMW = ("%fusion.293 = (f32[2048,49152]{1,0:T(8,128)}, f32[2048,49152]{1,0:"
         "T(8,128)}, f32[2048,49152]{1,0:T(8,128)}, bf16[2048,49152]{1,0:"
         "T(8,128)(2,1)}) fusion(%a, %b), kind=kLoop, calls=%f.293")
CAST = "%convert.57 = bf16[2048,49152]{1,0:T(8,128)(2,1)} convert(%a)"
EMBED_ADAMW = ("%fusion.290 = (f32[49152,2048]{1,0:T(8,128)}, f32[49152,2048]"
               "{1,0:T(8,128)}, f32[49152,2048]{1,0:T(8,128)}) fusion(%a), "
               "kind=kLoop, calls=%f.290")
EMBED_GRAD = ("%scatter.3 = f32[49152,2048]{1,0:T(8,128)} scatter(%a, %b, "
              "%c), to_apply=%add")


def head_events(steps):
    """A step: the passes' loop (200 ms, an op of 100 inside), the head's
    forward loop (two blocks of a 10 ms product and a 5 ms reduction), its
    backward loop (two blocks of 10 + 5 + 20 ms), then the cast, both
    AdamWs and the embedding's gradient at 3 ms each."""
    events, t = [], 0.0

    def loop(text, body):
        nonlocal t
        events.append((text, (t, sum(ms for _, ms in body))))
        for name, ms in body:
            events.append((name, (t, ms)))
            t += ms

    for _ in range(steps):
        loop(LOOP_PASSES, [(OTHER, 100), (OTHER, 100)])
        loop(LOOP_FWD, [(LOGITS, 10), (REDUCE, 5)] * 2)
        loop(LOOP_BWD, [(LOGITS, 10), (REDUCE, 5), (GRAD, 20)] * 2)
        for name in (CAST, ADAMW, EMBED_ADAMW, EMBED_GRAD):
            events.append((name, (t, 3)))
            t += 3
    return events, t


@pytest.mark.parametrize("steps", [1, 2])
def test_the_heads_share_takes_the_loops_ops_and_the_vocabularys(steps,
                                                                 capsys):
    events, end = head_events(steps)
    run = record_of(events, steps)
    run["trace"].window = (0, int(end * MS))
    spec = workmodel_loop.describe(cfg_of())
    flops, nbytes = workmodel_loop.head_loss_work(spec, S)
    least, bound = workmodel.least_seconds(flops, nbytes, PEAK)
    assert bound == "compute"
    # 30 + 70 ms in the loops, the cast and the head's AdamW by their
    # result; not the embedding's two, not the passes' loop
    assert reader("loop_head_loss_roofline").read(run) == pytest.approx(
        100 * least / 0.106)
    said = capsys.readouterr().out
    assert "bound by compute" in said and "(12 a step)" in said
    assert f"{10 * steps} inside {2 * steps} loops" in said


def test_no_head_event_gives_nothing_and_a_cut_one_is_counted(capsys):
    read = reader("loop_head_loss_roofline").read
    assert read(record_of([(OTHER, 5), (EMBED_ADAMW, 5)])) is None
    # an op that the traced window's edge cuts off is only counted: the
    # share is still read, over what the window holds
    events, end = head_events(2)
    run = record_of(events[:-4] + events[-3:], 2)
    run["trace"].window = (0, int(end * MS))
    assert read(run) is not None
    assert "(11.5 a step)" in capsys.readouterr().out
    # an op at a loop's edge that ends past the loop's own event is inside
    late = [(name, (ms[0] + 0.001, ms[1])) if name == GRAD else (name, ms)
            for name, ms in events]
    run = record_of(late, 2)
    run["trace"].window = (0, int(end * MS))
    read(run)
    assert "20 inside 4 loops" in capsys.readouterr().out
    assert read(dict(record_of(), trace=None)) is None


# -- the patterns against the compiled program ---------------------------------
#: instructions that are no event of their own or move nothing
NO_EVENT = ("get-tuple-element", "bitcast", "constant", "reshape", "while",
            "compare", "add", "subtract", "tuple", "parameter", "slice",
            "pad", "custom-call")


def compiled_rows():
    path = os.path.join(TESTS, "data", CELL + ".head_ops.txt")
    with open(path) as f:
        return [line.rstrip("\n").split("\t") for line in f if line.strip()]


def kind_of(text):
    return re.search(r"\s([a-z][\w\-]*)\(", " " + text.split(" = ", 1)[1]
                     ).group(1)


def test_the_patterns_take_what_the_compile_lists():
    spec = workmodel_loop.describe(cfg_of())
    loop, result = reader("loop_head_loss_roofline").patterns(spec)
    rows = compiled_rows()
    assert len(rows) == 112
    loops = [text for _, _, text in rows if loop.search(text)]
    # the head's two loops, forward and backward, and no other
    assert len(loops) == 2 and all(kind_of(t) == "while" for t in loops)
    for scope, taken, text in rows:
        if taken == "result":
            assert result.search(text), text
        elif taken == "no" and kind_of(text) != "while":
            assert not result.search(text), text
    # under the scope, what is not taken is no event of its own or small
    # (the token ids' shift, the blocks' zeroed accumulator, set-up of the
    # loops); every product, softmax and reduction is taken by its place
    left = [text for scope, taken, text in rows
            if scope.endswith("H") and taken == "no"]
    assert len(left) == 15
    for text in left:
        assert kind_of(text) in NO_EVENT or re.match(
            r"%(pad_add_fusion|broadcast_bitcast_fusion|broadcast_in_dim)",
            text), text
        assert kind_of(text) == "while" or str(V) not in text, text
    by_place = [text for scope, taken, text in rows if taken == "place"]
    assert sum(1 for t in by_place if f"f32[1024,{V}]" in t
               and kind_of(t) == "fusion") >= 2
    assert sum(1 for t in by_place if t.startswith("%convolution")) >= 1
    # outside the scope, by their result: the head's cast and its AdamW
    outside = [text for scope, taken, text in rows
               if taken == "result" and scope.startswith("-")
               and kind_of(text) in ("fusion", "convert")]
    assert len(outside) == 2
    # the embedding's table shape is left out
    assert not result.search(EMBED_ADAMW) and not result.search(EMBED_GRAD)
