"""Readings that the limits of an ``lm_hybrid_train`` cell's ``correct`` are
set from (PERF.md section 4): for each seed the plain reference, and against
it the control (the reference in bfloat16, the nearest precision below the
configuration's) and the planted faults, which are the reference's own (its
head lists them): half of the loss positions left out, the state between the
scan's blocks left out, the convolution left out, the held experts' part left
out, a state left unchanged. The program's own readings are the benchmark
runs' (``run.py`` prints every number, compared or not). Run on the chip at
the cell's own size:

    python3 perfbench/tools/readings_hybrid.py --workload W --seeds 1,2,3 [--kinds control,no_carry] [--program]

Each row goes to standard output and to ``chiprun_out/readings/W.jsonl``;
``over`` names the numbers over their limits (the control and each fault have
at least one). ``--program`` adds the program's own row (its set-up and
checked steps, as a benchmark run makes them). Each side's loss position by
position goes to ``chiprun_out/readings/W.<seed>.<kind>.npy``, so that the
positions ``positions`` reads can be chosen from readings.
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

KINDS = ("control", "half_loss", "no_carry", "no_conv", "no_routed",
         "state_unchanged")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(KINDS))
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    kinds = args.kinds.split(",")
    if set(kinds) - set(KINDS):
        ap.error(f"kinds are {', '.join(KINDS)}")
    bench = harness.read_json(args.bench)
    cell, cfg, traffic, limits = harness.cell_files(bench, args.workload)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    ensure_compile_cache()
    devices = harness.pick_devices(int(cell["chips"]),
                                   bench.get("platform", "tpu"))
    mod = harness.load_runner(cfg)
    out_dir = os.path.join(ROOT, "chiprun_out", "readings")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, args.workload + ".jsonl")

    def emit(kind, seed, numbers, seconds, side=None):
        numbers = dict(numbers)
        if side is not None:
            np.save(os.path.join(out_dir, f"{args.workload}.{seed}.{kind}.npy"),
                    side["positions"])
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
        program = None
        if args.program:
            t = time.perf_counter()
            runner.build()
            runner.checked_steps()
            program, t_program = runner.program, time.perf_counter() - t
            runner.free()
            jax.clear_caches()
        runner.draw_checked_batches()
        t = time.perf_counter()
        ref = runner.reference()
        emit("reference", seed, [], time.perf_counter() - t, ref)
        if program is not None:
            emit("program", seed, runner.compare(program, ref), t_program,
                 program)
        for kind in kinds:
            t = time.perf_counter()
            if kind == "control":
                side = runner.reference(dtype=jnp.bfloat16)
            elif kind == "state_unchanged":
                side = runner.reference(skip_update=True)
            else:
                side = runner.reference(faults=(kind,))
            emit(kind, seed, runner.compare(side, ref),
                 time.perf_counter() - t, side)


if __name__ == "__main__":
    main()
