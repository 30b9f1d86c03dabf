"""The benchmark's one command.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips. A cell is data: its entry in
``BENCHMARK.json`` names a configuration (``perfbench/configs/<file>``)
and a traffic mix (``perfbench/traffic/<traffic>.json``); the limits of
its check are ``perfbench/limits/<cell>.json``; the configuration names
its runner kind (``perfbench/runners/<kind>.py``), which names the rate it
reports, and its plain reference (``"reference": "reference.<module>"``,
a file of ``perfbench/reference/``); each per-layer metric is a reader of
its own (``perfbench/metrics/<name>.py``). Adding a cell, a mix, a
configuration with its reference, a runner kind or a metric is adding
files and entries.

Set-up (imports, weights made on the device from ``--seed``, compile or
cache load, the first checked steps, which are the warm-up) is counted
as ``setup_s``; then the window of ``--seconds``; then the peak memory is
read, the program's state is freed and the plain reference follows the
checked steps. The last line of standard output is the result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: trace-time knobs of the program that would silently change what a cell
#: computes; the harness refuses to run with either set
FORBIDDEN_ENV = ("FIBER_POLICY_DTYPE", "FIBER_ROLLOUT_UNROLL")
HOST_SPANS = ("make_batch", "dispatch", "wait")
WINDOW_SPAN = "bench_window"


class Refused(Exception):
    """The run cannot be made; exit with a code other than 0, no result."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no file {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise Refused(f"cannot read {path}: {e}") from e


def cell_files(bench: dict, workload: str):
    """(cell, configuration, traffic, limits): the cell's entry and what its
    own files hold. Only a rehearsal file can name directories of its own
    for traffic and limits."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; there are {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}

    def directory(kind):
        return (os.path.join(ROOT, bench[kind + "_dir"])
                if kind + "_dir" in bench else os.path.join(HERE, kind))
    cfg = read_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = read_json(os.path.join(directory("traffic"),
                                     cell["traffic"] + ".json"))
    limits = read_json(os.path.join(directory("limits"),
                                    cell["name"] + ".json"))["limits"]
    return cell, cfg, traffic, limits


def load_runner(cfg: dict):
    """The module of the configuration's runner kind."""
    return load_module(os.path.join(HERE, "runners", cfg["runner"] + ".py"),
                       "runner_" + cfg["runner"])


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class Spans:
    """The harness's own host spans: kept in memory, and written into the
    profiler's trace (same clock as the device's ops) while it is on."""

    def __init__(self):
        self.totals = {}
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        agg = self.totals.setdefault(name, [0, 0.0])
        agg[0] += 1
        agg[1] += time.perf_counter() - t0


class CompileWatch:
    """Counts what JAX traces, compiles or loads from its cache."""

    def __init__(self):
        self.events = 0

    def install(self):
        from jax import monitoring

        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if "compilation_cache" in event:
            self.events += 1

    def _duration(self, event, duration, **kw):
        if "compil" in event or "jaxpr_trace" in event:
            self.events += 1


def pick_devices(chips: int, platform: str):
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise Refused(f"the cell runs on {platform!r}; JAX found "
                      f"{devices[0].platform!r} and nothing falls back")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip. On the TPU runtime
    ``peak_bytes_in_use`` counts live arrays only; the temporaries of the
    loaded programs are held apart as ``peak_bytes_reserved`` and occupy
    the chip all the same (PERF.md), so the two are added."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_parts(devices) -> str:
    stats = devices[0].memory_stats() or {}
    return "(chip 0: " + ", ".join(
        f"{k} {stats[k] / 2**30:.3f}" for k in (
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_in_use",
            "bytes_reserved", "bytes_limit") if k in stats) + " GiB)"


def timed_window(runner, spans, watch, seconds, trace_dir, trace_calls):
    """Drive ``runner.call()`` back to back until the first call that ends
    after ``seconds``. With a ``trace_dir`` the first ``trace_calls`` calls
    are traced, under the span ``WINDOW_SPAN``; the seconds that writing
    the trace took are left out of the window. Returns (each call's
    seconds, units of work done, window seconds, compilations seen)."""
    import jax

    window_ctx = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        spans.tracing = True
        window_ctx = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        window_ctx.__enter__()
    spans.totals.clear()
    events_before = watch.events
    times, units, overhead_s = [], 0, 0.0
    t0 = time.perf_counter()
    while True:
        t_call = time.perf_counter()
        units += runner.call()
        now = time.perf_counter()
        times.append(now - t_call)
        if spans.tracing and len(times) >= trace_calls:
            window_ctx.__exit__(None, None, None)
            spans.tracing = False
            jax.profiler.stop_trace()
            overhead_s = time.perf_counter() - now
        if now - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0 - overhead_s
    return times, units, window_s, watch.events - events_before


def run_cell(args, bench: dict, *, sabotage=None) -> dict:
    """One run of one cell; returns the result line as a dict.
    ``sabotage(runner)``, for the harness's own tests, breaks the timed
    path after it is built. Only a rehearsal file (``--bench``, never
    ``BENCHMARK.json``, whose keys are fixed) can name another platform
    than the TPU or the Pallas interpreter."""
    platform = bench.get("platform", "tpu")
    rehearsal = bool(bench.get("rehearsal", False))
    for name in FORBIDDEN_ENV:
        if os.environ.get(name):
            raise Refused(f"{name} is set: it would change what the cell "
                          "computes")
    cell, cfg, traffic, limits = cell_files(bench, args.workload)
    sys.path.insert(0, HERE)
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    try:
        import fiber_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        raise Refused(f"the program is not here: {e}") from e
    import jax

    from fiber_tpu.telemetry.device import DEVICE
    from fiber_tpu.utils.jaxcompat import ensure_compile_cache

    import trace_reduce

    cache_dir = ensure_compile_cache()
    # small programs too (weight init, the norms of the check): the second
    # run of a cell finds every program in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t_imported = time.perf_counter()
    devices = pick_devices(int(cell["chips"]), platform)
    kind = devices[0].device_kind
    peaks = bench["peaks"] if rehearsal else read_json(
        os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise Refused(f"no peaks for device kind {kind!r}: add its row, "
                      "with its source, to perfbench/peaks.json")
    DEVICE.install_listeners()
    watch = CompileWatch()
    watch.install()

    spans = Spans()
    runner_mod = load_runner(cfg)
    runner = runner_mod.Runner(cfg, traffic, seed_key(args.seed), args.seed,
                               devices, spans, rehearsal=rehearsal)
    t_devices = time.perf_counter()
    runner.build()
    t_built = time.perf_counter()
    if sabotage is not None:
        sabotage(runner)
    runner.checked_steps()
    compile_s = DEVICE.snapshot()["compile_seconds"]
    print(f"set-up: imports {t_imported - T_START:.1f} s, devices "
          f"{t_devices - t_imported:.1f} s, build {t_built - t_devices:.1f} s, "
          f"checked steps {time.perf_counter() - t_built:.1f} s "
          f"(of it compiling or loading {compile_s:.1f} s)", flush=True)

    trace_dir = (os.path.join(ROOT, ".perfbench_trace", args.workload)
                 if args.trace else None)
    setup_s = time.perf_counter() - T_START
    times, units, window_s, window_events = timed_window(
        runner, spans, watch, args.seconds, trace_dir,
        int(traffic["trace_calls"]))
    peak_bytes = memory_peak(devices)
    print(f"memory: peak {peak_bytes / 2**30:.3f} GiB on the fullest of "
          f"{len(devices)} chip(s) {memory_parts(devices)}; "
          f"compile cache at {cache_dir}; "
          f"set-up {setup_s:.1f} s; {len(times)} calls in {window_s:.2f} s "
          f"(median {1e3 * statistics.median(times):.2f} ms, slowest "
          f"{1e3 * max(times):.2f} ms)", flush=True)
    host_spans = {k: list(v) for k, v in spans.totals.items()}

    program_side = runner.program
    runner.free()
    jax.clear_caches()
    t_ref = time.perf_counter()
    reference_side = runner.reference()
    reference_s = time.perf_counter() - t_ref
    numbers = runner.compare(program_side, reference_side)
    compared = [(name, value, limits[name])
                for name, value in numbers if name in limits]
    for name, value in numbers:
        if name not in limits:
            print(f"not compared {name}: {value:.6g} (no limit holds: "
                  "PERF.md)", flush=True)
    if not compared:
        raise Refused("no number was compared")
    compared.append(("window_compiles", float(window_events), 0.0))
    correct = all(value <= limit and value == value
                  for _, value, limit in compared)

    record = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "chips": len(devices),
        "peak": peaks[kind],
        "units": units, "window_s": window_s, "call_times": times,
        "units_per_call": runner.units_per_call(),
        "compile_s": compile_s, "host_spans": host_spans, "trace": None,
    }
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": len(times), "failed": 0}
    if trace_dir is not None:
        trace = trace_reduce.load_xplane(
            trace_dir, HOST_SPANS, WINDOW_SPAN, platform=devices[0].platform)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = trace
        busy = trace_reduce.busy_seconds(trace)
        if not busy or max(busy.values()) <= 0:
            raise Refused("the trace shows no operation on the device")
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = trace_reduce.window_seconds(trace)
        chip0 = min(trace.device)
        result["metrics"] = per_layer_metrics(bench, cell, record)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace, chip0),
            "idle_gaps": trace_reduce.idle_gaps(trace, chip0)}
    else:
        result["metrics"] = end_to_end_metrics(
            bench, cell, {"setup_s": setup_s,
                          runner_mod.Runner.rate: units / window_s})
    result["device"] = device
    result["reference_s"] = reference_s
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    for name, value, limit in compared:
        print(f"compared {name}: {value:.6g} (limit {limit:.6g})"
              + ("" if value <= limit else "  <-- over"), file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end_metrics(bench, cell, values) -> dict:
    """The end-to-end metrics are the harness's own, from the host clock:
    the set-up, and the rate the runner kind names: all the work of the
    window over all its time."""
    return {metric["name"]: {"value": values[metric["name"]],
                             "unit": metric["unit"]}
            for metric in bench["end_to_end"]
            if applies(metric, cell["name"]) and metric["name"] in values}


def per_layer_metrics(bench, cell, record) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell["name"]):
            continue
        reader = load_module(
            os.path.join(HERE, "metrics", metric["name"] + ".py"),
            "metric_" + metric["name"].replace(".", "_"))
        value = reader.read(record)
        if value is None:
            print(f"metric {metric['name']}: nothing to read", flush=True)
            continue
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def stop_program_threads():
    """The program starts a daemon thread on import (its 1 Hz monitor
    sampler, which calls into JAX); the window runs with it, as users do.
    Left running, it can abort the interpreter as it exits (rc -6,
    "exception not rethrown"; PERF.md), so it is stopped through the
    program's own switch and waited for."""
    import threading

    from fiber_tpu.telemetry import TIMESERIES

    TIMESERIES.configure(enabled=False, interval=1.0, capacity=1)
    for thread in threading.enumerate():
        if thread.name == "fiber-monitor-sampler":
            thread.join(timeout=10.0)


def main(argv=None, **kw) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                        help="a rehearsal's own file (the harness's tests)")
    args = parser.parse_args(argv)
    try:
        bench = read_json(args.bench)
        result = run_cell(args, bench, **kw)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    finally:
        if "fiber_tpu.telemetry" in sys.modules:
            stop_program_threads()
    print(json.dumps(result), flush=True)      # "compared" comes last
    return 0


if __name__ == "__main__":
    sys.exit(main())
