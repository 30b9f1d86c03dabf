"""The instructions of the compiled v5e program of an ``lm_hybrid_train`` cell
that ``metrics/ssd_scan_roofline.py`` has to tell apart, for the test that
holds its pattern against them (``tests/test_hybrid_readers.py``). Compiles
the cell's step for a described ``v5e:2x2`` as ``tests/test_aot_compile.py``
does (no chip; about two minutes) and writes
``perfbench/tests/data/<cell>.ssm_ops.txt``: one line an instruction of the
entry computation or of a loop's body (never of a fused computation, which is
no event of its own) that runs under the scope ``lm.ssm``, or that the
reader's pattern takes, or whose result holds the mixers' ``[S, H P]``. Three
fields, tab-separated: the scope from its ``op_name`` (``scan``, ``R:scan``
for the recomputed forward pass, ``B:scan`` for the backward pass, likewise
``in_proj``, ``conv``, ``gate_norm``, ``out``; ``-`` and the ``op_name``'s
tail for an instruction outside ``lm.ssm``, ``-`` alone where it has none);
how often a step runs it (1, or the scan's blocks for the body of a loop
that a state-space layer's scan makes; ``?`` in another loop's body); the
instruction's text up to its operands' end, as a trace shows it.

    JAX_PLATFORMS=cpu python3 perfbench/tools/hybrid_scan_ops.py --workload nemotron3_nano_train_8k
"""
import argparse
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import run as harness  # noqa: E402

CALLS = re.compile(r"(?:body|condition|to_apply|true_computation|"
                   r"false_computation)=%?([\w.\-]+)")


def computations(text):
    """{name: [instruction lines]} and the entry computation's name."""
    found, entry, current = {}, None, None
    for line in text.split("\n"):
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            current = head.group(2)
            found[current] = []
            entry = current if head.group(1) else entry
        elif current and line.startswith("  "):
            found[current].append(line.strip())
    return found, entry


def running(found, entry, blocks):
    """{computation: times a step runs it} for the computations whose
    instructions run as events of their own: the entry and, from it, loops'
    bodies and conditions and called ones. A loop that a state-space
    layer's scan makes (its ``op_name`` says so) runs ``blocks`` times; how
    often another loop runs is decided on the device (``?``)."""
    seen, stack = {}, [(entry, 1)]
    while stack:
        name, times = stack.pop()
        if name in seen:
            continue
        seen[name] = times
        for line in found[name]:
            if re.search(r" (while|call|conditional)\(", line):
                op = re.search(r'op_name="([^"]*)"', line)
                inner = times
                if " while(" in line:
                    scan = op and "scan" in (scope_of(op.group(1)) or "")
                    inner = times * blocks if scan and times != "?" else "?"
                stack.extend((c, inner) for c in CALLS.findall(line))
    return seen


def scope_of(op_name):
    """``scan``, ``R:scan``, ``B:scan``, ...; None outside ``lm.ssm``. A
    fusion that spans scopes carries several names; the first is taken."""
    op_name = op_name.split(";")[0]
    if "lm.ssm" not in op_name:
        return None
    tail = op_name.rsplit("lm.ssm", 1)[1].lstrip(")/")
    recomputed = "rematted_computation/" in tail
    backward = tail.startswith("checkpoint/") and not recomputed
    tail = tail.replace("checkpoint/", "").replace(
        "rematted_computation/", "")
    return (("R:" if recomputed else "B:" if backward else "")
            + tail.split("/", 1)[0])


def rows_of(text, taken, blocks):
    """The file's lines from a compiled program's text; ``taken`` says
    whether the reader would take an instruction's text."""
    found, entry = computations(text)
    rows = []
    for name, times in sorted(running(found, entry, blocks).items()):
        for line in found[name]:
            op = re.search(r'op_name="([^"]*)"', line)
            scope = scope_of(op.group(1)) if op else None
            line = line.split(", metadata=")[0]
            line = line[5:] if line.startswith("ROOT ") else line
            if scope is None:
                if not taken(line):
                    continue
                scope = "-" + (op.group(1)[-60:] if op else "")
            rows.append(f"{scope}\t{times}\t{line[:600]}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic, _ = harness.cell_files(bench, args.workload)
    runner = harness.load_runner(cfg)
    compiled = runner.aot_lower(cfg, traffic,
                                list(topo.devices)[:1]).compile()
    reader = harness.load_module(
        os.path.join(HERE, "metrics", "ssd_scan_roofline.py"),
        "metric_ssd_scan_roofline")
    import workmodel_hybrid

    layer = workmodel_hybrid.ssm_layers(workmodel_hybrid.describe(cfg))[0]
    seq = int(traffic["seq"])
    rx = re.compile(reader.pattern(layer, seq))
    rows = rows_of(compiled.as_text(), rx.search, seq // layer["chunk"])
    out = os.path.join(HERE, "tests", "data", args.workload + ".ssm_ops.txt")
    with open(out, "w") as f:
        f.write("\n".join(rows) + "\n")
    print(f"{len(rows)} instructions -> {out}")


if __name__ == "__main__":
    main()
