"""es_gen_mfu: the whole generation's share of the chip's bf16 peak: the
operations one generation needs (workmodel.es_generation_flops) times the
generations of the window, over window seconds times the peak."""
import workmodel


def read(run):
    cfg = run["cfg"]
    sizes = (cfg["obs_size"], *cfg["hidden"], cfg["action_count"])
    flops = workmodel.es_generation_flops(
        sizes=sizes, pop=cfg["population"], steps=cfg["episode_steps"],
        dim=cfg["parameter_count"])
    gens = run["units"] / cfg["population"]
    return 100.0 * flops * gens / (
        run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
