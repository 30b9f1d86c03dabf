"""Readings that the limits of an ``lm_loop_train`` cell's ``correct`` are set
from (PERF.md section 4): for each seed the plain reference, and against it
the control (the reference in bfloat16, the nearest precision below the
configuration's) and the planted faults, which are the reference's own (its
head lists them): one pass left out, the final norm between passes left out,
the norms behind the parts left out, the loss taken from the last pass alone,
the entropy term left out, every pass reading pass 1's logits, half of the
loss positions left out, a state left unchanged. The program's own readings are the benchmark runs' (``run.py``
prints every number, compared or not). Run on the chip at the cell's own
size:

    python3 perfbench/tools/readings_loop.py --workload W --seeds 1,2,3 [--kinds control,three_passes] [--program]

Each row goes to standard output and to ``chiprun_out/readings/W.jsonl``;
``over`` names the numbers over their limits (the control and each fault have
at least one). ``--program`` adds the program's own row (its set-up and
checked steps, as a benchmark run makes them).
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

KINDS = ("control", "three_passes", "no_pass_norm", "no_post_norm",
         "last_pass_loss", "no_entropy", "first_pass_logits", "half_loss",
         "state_unchanged")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(KINDS))
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    kinds = [k for k in args.kinds.split(",") if k]
    if set(kinds) - set(KINDS):
        ap.error(f"kinds are {', '.join(KINDS)}")
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
        emit("reference", seed, [("loss1_value", ref["loss"][0])],
             time.perf_counter() - t)
        if program is not None:
            emit("program", seed, runner.compare(program, ref), t_program)
        for kind in kinds:
            t = time.perf_counter()
            if kind == "control":
                side = runner.reference(dtype=jnp.bfloat16)
            elif kind == "state_unchanged":
                side = runner.reference(skip_update=True)
            else:
                side = runner.reference(faults=(kind,))
            emit(kind, seed, runner.compare(side, ref),
                 time.perf_counter() - t)


if __name__ == "__main__":
    main()
