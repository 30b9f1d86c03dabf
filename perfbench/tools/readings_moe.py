"""Readings that the limits of an ``lm_moe_train`` cell's ``correct`` are set
from (PERF.md): for each seed the program against the plain reference, and
on request the control (the reference in bfloat16, the nearest precision
below the configuration's) and the planted faults, each against the same
reference. Run on the chip at the cell's own size:

    python3 perfbench/tools/readings_moe.py --workload W --seeds 1,2,3 [--control] [--faults]

The faults are the reference's own (its head lists them): half of the loss
positions left out, the held experts' part left out, the weights normalised
over the held experts only, the window layers run without their window.
Each row goes to standard output and to
``chiprun_out/readings/W.jsonl``; ``over`` names the numbers over their
limits (a sound run has none, the control and each fault at least one).
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

FAULTS = ("half_loss", "no_routed", "held_norm", "no_window")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    bench = harness.read_json(args.bench)
    cell, cfg, traffic, limits = harness.cell_files(bench, args.workload)
    import jax
    import jax.numpy as jnp

    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    devices = harness.pick_devices(int(cell["chips"]),
                                   bench.get("platform", "tpu"))
    mod = harness.load_runner(cfg)
    out_dir = os.path.join(ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, args.workload + ".jsonl")

    def emit(kind, seed, numbers, seconds):
        numbers = dict(numbers)
        row = {"workload": args.workload, "kind": kind, "seed": seed,
               "seconds": round(seconds, 2), **numbers,
               "over": sorted(n for n, v in numbers.items()
                              if n in limits and not v <= limits[n])}
        print(json.dumps(row), flush=True)
        with open(log_path, "a") as log:
            log.write(json.dumps(row) + "\n")

    for seed in [int(s) for s in args.seeds.split(",")]:
        runner = mod.Runner(cfg, traffic, harness.seed_key(seed), seed,
                            devices, harness.Spans(),
                            rehearsal=bool(bench.get("rehearsal")))
        t = time.perf_counter()
        runner.build()
        runner.checked_steps()
        program = runner.program
        runner.free()
        jax.clear_caches()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = runner.reference()
        emit("program", seed, runner.compare(program, ref), t_prog)
        emit("reference_seconds", seed, [], time.perf_counter() - t)
        emit("load", seed, [("max_over_mean", float(
            (program["load"].max(axis=1) / program["load"].mean(axis=1)
             ).max()))], 0.0)
        if args.control:
            t = time.perf_counter()
            side = runner.reference(dtype=jnp.bfloat16)
            emit("control_bf16", seed, runner.compare(side, ref),
                 time.perf_counter() - t)
        if args.faults:
            for name in FAULTS:
                t = time.perf_counter()
                side = runner.reference(faults=(name,))
                emit("fault_" + name, seed, runner.compare(side, ref),
                     time.perf_counter() - t)
            t = time.perf_counter()
            side = runner.reference(skip_update=True)
            emit("fault_state_unchanged", seed, runner.compare(side, ref),
                 time.perf_counter() - t)


if __name__ == "__main__":
    main()
