"""Readings that the limits of ``correct`` are set from (PERF.md): for each
seed the program against the plain reference, and on request the control
(the nearest precision below the configuration's) and the planted faults
against the same reference. Run on the chip at the cell's own size:

    python3 perfbench/tools/readings.py --workload W --seeds 1,2,3 [--control] [--faults]

A four-chip cell's reference, control and faults need one chip only, so its
readings can be taken in two calls: ``--side program`` on four chips keeps
the program's side of each seed in ``chiprun_out/readings/W.program.jsonl``
(``lm_train`` cells), and ``--side reference`` on one chip reads it back.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import run as harness  # noqa: E402


def to_json(side):
    return {k: (v.tolist() if hasattr(v, "tolist") else v)
            for k, v in side.items()}


def from_json(side):
    import numpy as np

    return {k: (np.asarray(v, np.float64) if k in ("grad", "update") else v)
            for k, v in side.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--side", choices=("both", "program", "reference"),
                    default="both")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    bench = harness.read_json(args.bench)
    cell, cfg, traffic, _ = harness.cell_files(bench, args.workload)
    import jax
    import jax.numpy as jnp

    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    chips = 1 if args.side == "reference" else int(cell["chips"])
    devices = harness.pick_devices(chips, bench.get("platform", "tpu"))
    mod = harness.load_runner(cfg)
    out_dir = os.path.join(ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, args.workload + ".jsonl")
    kept_path = os.path.join(out_dir, args.workload + ".program.jsonl")
    kept = {}
    if args.side == "reference":
        with open(kept_path) as f:
            kept = {row["seed"]: from_json(row["program"])
                    for row in map(json.loads, f)}

    def emit(kind, seed, numbers, seconds):
        row = {"workload": args.workload, "kind": kind, "seed": seed,
               "seconds": round(seconds, 2), **dict(numbers)}
        print(json.dumps(row), flush=True)
        with open(log_path, "a") as log:
            log.write(json.dumps(row) + "\n")

    def fresh(seed):
        return mod.Runner(cfg, traffic, harness.seed_key(seed), seed, devices,
                          harness.Spans(),
                          rehearsal=bool(bench.get("rehearsal")))

    for seed in [int(s) for s in args.seeds.split(",")]:
        runner = fresh(seed)
        if args.side == "reference":
            program = kept[seed]
            runner.draw_checked_batches()
        else:
            t = time.perf_counter()
            runner.build()
            runner.checked_steps()
            program = runner.program
            runner.free()
            jax.clear_caches()
            t_prog = time.perf_counter() - t
        if args.side == "program":
            with open(kept_path, "a") as f:
                f.write(json.dumps({"seed": seed,
                                    "program": to_json(program)}) + "\n")
            emit("program_side_kept", seed, [], t_prog)
            continue
        t = time.perf_counter()
        ref = runner.reference()
        t_ref = time.perf_counter() - t
        emit("program", seed, runner.compare(program, ref),
             0.0 if args.side == "reference" else t_prog)
        emit("reference_seconds", seed, [], t_ref)
        is_es = cfg["runner"] == "es_fused"
        if args.control:
            t = time.perf_counter()
            if is_es:
                ctl = fresh(seed)
                ctl.build(policy_dtype="bfloat16")
                ctl.checked_steps()
                side = ctl.program
                ctl.free()
                jax.clear_caches()
            else:
                side = runner.reference(dtype=jnp.bfloat16)
            emit("control_bf16", seed, runner.compare(side, ref),
                 time.perf_counter() - t)
        if args.faults:
            faults = {}
            if is_es:
                faults["half_population"] = dict(
                    members=int(cfg["population"]) // 4)
            else:
                faults["half_tokens"] = dict(
                    loss_tokens=int(traffic["seq"]) // 2)
                if traffic["mesh"]:
                    faults["no_exchange"] = dict(
                        seq_block=int(traffic["seq"]) // int(cell["chips"]))
            for name, kw in faults.items():
                t = time.perf_counter()
                side = runner.reference(**kw)
                emit("fault_" + name, seed, runner.compare(side, ref),
                     time.perf_counter() - t)


if __name__ == "__main__":
    main()
