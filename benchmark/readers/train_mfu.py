"""Model FLOP/s utilization of the train step: the operations forward and
backward need (the configuration's count, `ctx["flops"]`: no recomputation,
no weight gradients of frozen blocks) for one chip's share of the global
batch, over the median device time of one execution of the train-step
program (over every chip's executions) times one chip's bf16 peak."""


def read(ctx, spec):
    red, peaks, s = ctx["reduction"], ctx["peaks"], ctx["shapes"]
    if not red or not peaks:
        return None
    seconds = ctx["trace"].median_execution_seconds(red, spec["programs"])
    if not seconds:
        return None
    f = ctx["flops"]
    if s["method"] == "ppo":
        ops = f.ppo_train_step_flops(ctx["arch"], s["batch"], s["prompt"], s["response"], s["unfrozen"])
    else:
        ops = f.ilql_train_step_flops(ctx["arch"], s["batch"], s["seq"], s["unfrozen"], s["two_qs"])
    return 100.0 * (ops / ctx["chips"]) / (seconds * peaks["bf16_flops_per_s"])
