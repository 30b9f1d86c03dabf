"""The instructions of the compiled v5e program of an ``lm_loop_train`` cell
that ``metrics/loop_head_loss_roofline.py`` has to take, for the test that
holds its patterns against them (``tests/test_loop_readers.py``). Compiles
the cell's step for a described ``v5e:2x2`` as ``tests/test_aot_compile.py``
does (no chip; half a minute) and writes
``perfbench/tests/data/<cell>.head_ops.txt``: one line an instruction of the
entry computation or of a loop's body (never of a fused computation, which is
no event of its own) that runs under the scope ``lm.head_loss``, or that the
reader takes. Three fields, tab-separated: the scope from its ``op_name``
(``H`` forward, ``R:H`` the recomputed forward, ``B:H`` the backward pass;
``-`` and the ``op_name``'s tail for an instruction outside the scope, ``-``
alone where it has none); how the reader takes it (``place``: it runs in a
loop whose own text carries the vocabulary's size; ``result``: its result's
shape carries it; ``no``); the instruction's text up to its operands' end, as
a trace shows it.

    JAX_PLATFORMS=cpu python3 perfbench/tools/loop_head_ops.py --workload ouro_2p6b_train_8k
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

from hybrid_scan_ops import CALLS, computations  # noqa: E402

SCOPE = "lm.head_loss"


def shown(line):
    """An instruction's text as a trace shows it."""
    line = line.split(", metadata=")[0]
    return line[5:] if line.startswith("ROOT ") else line


def running(found, entry, loop):
    """{computation: inside a loop of the head's?} for the computations
    whose instructions run as events of their own: the entry and, from it,
    loops' bodies and conditions and called ones."""
    seen, stack = {}, [(entry, False)]
    while stack:
        name, inside = stack.pop()
        if name in seen:
            continue
        seen[name] = inside
        for line in found[name]:
            if re.search(r" (while|call|conditional)\(", line):
                mine = inside or bool(loop.search(shown(line)))
                stack.extend((c, mine) for c in CALLS.findall(line))
    return seen


def scope_of(op_name):
    """``H``, ``R:H``, ``B:H``; None outside the scope. A fusion that spans
    scopes carries several names; any of them counts."""
    if SCOPE not in op_name:
        return None
    recomputed = "rematted_computation" in op_name
    backward = "transpose(" in op_name and not recomputed
    return ("R:" if recomputed else "B:" if backward else "") + "H"


def rows_of(text, loop, result):
    found, entry = computations(text)
    rows = []
    for name, inside in sorted(running(found, entry, loop).items()):
        for line in found[name]:
            op = re.search(r'op_name="([^"]*)"', line)
            scope = scope_of(op.group(1)) if op else None
            line = shown(line)
            container = re.search(r" (while|call|conditional)\(", line)
            taken = ("no" if container else "place" if inside
                     else "result" if result.search(line) else "no")
            if scope is None:
                if taken == "no":
                    continue
                scope = "-" + (op.group(1)[-60:] if op else "")
            rows.append(f"{scope}\t{taken}\t{line[:600]}")
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
        os.path.join(HERE, "metrics", "loop_head_loss_roofline.py"),
        "metric_loop_head_loss_roofline")
    import workmodel_loop

    loop, result = reader.patterns(workmodel_loop.describe(cfg))
    rows = rows_of(compiled.as_text(), loop, result)
    out = os.path.join(HERE, "tests", "data", args.workload + ".head_ops.txt")
    with open(out, "w") as f:
        f.write("\n".join(rows) + "\n")
    print(f"{len(rows)} instructions -> {out}")


if __name__ == "__main__":
    main()
