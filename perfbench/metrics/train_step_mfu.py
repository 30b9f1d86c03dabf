"""train_step_mfu: the whole step's share of the chips' bf16 peak: the
operations one step needs (workmodel.lm_train_flops; the window counted
where the cell applies one) times the steps of the window, over window
seconds times chips times the peak."""
import workmodel


def step_flops(run):
    cfg, traffic = run["cfg"], run["traffic"]
    return workmodel.lm_train_flops(
        seq=traffic["seq"], dim=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        mlp_hidden=cfg["intermediate_size"],
        window=cfg["sliding_window"] if traffic["use_window"] else None,
        batch=max(traffic["batch"], 1))


def read(run):
    steps = run["units"] / run["units_per_call"]
    return 100.0 * step_flops(run) * steps / (
        run["window_s"] * run["chips"] * run["peak"]["flops_bf16"])
