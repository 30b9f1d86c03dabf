"""moe_load_max_over_mean: the most loaded held expert's tokens over the mean
of the held experts', the worst of the expert layers, on the batch the
program last probed (the first checked batch, under the initial weights).
Read where the program keeps it: the registry's gauges
``moe_expert_load_max`` and ``moe_expert_load_mean``, one series an expert
layer (``BlockLM.probe_routing`` sets them). A hand-built record (the tests)
brings them under ``"program_gauges"``. A program without them: nothing."""
import sys


def gauges_of(run, name):
    """{labels: value} of the program's gauge ``name``."""
    if "program_gauges" in run:
        return run["program_gauges"].get(name, {})
    telemetry = sys.modules.get("fiber_tpu.telemetry")
    if telemetry is None or not hasattr(telemetry, "REGISTRY"):
        return {}
    return telemetry.REGISTRY.snapshot().get(name, {}).get("series", {})


def read(run):
    most = gauges_of(run, "moe_expert_load_max")
    mean = gauges_of(run, "moe_expert_load_mean")
    ratios = [most[layer] / mean[layer] for layer in most
              if mean.get(layer, 0) > 0]
    return max(ratios) if ratios else None
