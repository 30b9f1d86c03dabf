"""es_gen_ms: median host-clock time of one ``run_fused`` call (ending in
``block_until_ready``) over its generations."""
import statistics


def read(run):
    gens = run["traffic"]["generations_per_call"]
    return 1e3 * statistics.median(run["call_times"]) / gens
