"""An expert layer's grouped products against their roofline: the least
time the chip could take for the calls the traced window holds over the
summed device time of the operations the metric's file selects (see
`trace.op_rows`), in every program that makes them.

A grouped product's row buffer has a static size, so a call's own shapes do
not say how many rows were live or how many experts they touched; the
program's counters do. Per call the floor is the larger of operations over
peak FLOP/s and bytes over peak bytes/s of `counts.expert_ffn_call(rows,
touched, d, f)`: the three products of one expert layer over `rows` live
token-slots, each touched expert's weights read once. The calls, from the
cell's shapes and the traced window (`ctx["traced"]`):

    train step   tokens = batch x seq, rows = tokens x k x held_slot_share
                 (the step records' `moe/held_slot_share`), every held expert
                 touched; an expert layer's products are needed forward and
                 once more for the lhs-gradient (the recomputation under remat
                 is in the time and not in the need; the weight-gradient
                 contraction is an XLA fusion, not a grouped-product kernel,
                 and is in neither)
    scoring      the frozen branch (the top `unfrozen` blocks) forward over a
                 whole rollout chunk
    prefill      every expert layer forward over rollouts x prompt tokens

A decode step makes no grouped product: at 32 tokens the expert layer runs
every held expert over every token (models/moe.py, a small call), plain
dots that `decode_ms_per_step` sees; `experts_touched_per_step` reads how many
of them the step's tokens chose.

Nothing where the trace shows no such operation or the configuration's count
has no `expert_ffn_call`.
"""

import statistics


def read(ctx, spec):
    red, peaks, f, arch, s, traced = (ctx[k] for k in ("reduction", "peaks", "flops", "arch", "shapes", "traced"))
    if not red or not peaks or not traced or not hasattr(f, "expert_ffn_call") or "experts" not in arch.get("ffn_layers", ()):
        return None
    spent = sum(row["seconds"] for row in ctx["trace"].op_rows(red, spec))
    if not spent:
        return None
    d, width, k = arch["d_model"], arch["expert_d_ff"], arch["experts_per_token"]
    held = arch["experts_held"][1] if arch.get("experts_held") else arch["n_experts"]
    median = lambda records, key, default: statistics.median([r[key] for r in records if key in r] or [default])
    share = median(ctx["window"]["steps"], "moe/held_slot_share", held / arch["n_experts"])
    floor = lambda tokens, experts: f.least_seconds(*f.expert_ffn_call(tokens * k * share, experts, d, width), peaks)[0]

    kinds = list(arch["ffn_layers"])
    n_layer, unfrozen = len(kinds), s["unfrozen"] if 0 < s["unfrozen"] < len(kinds) else len(kinds)
    trains = [kind == "experts" and i >= n_layer - unfrozen for i, kind in enumerate(kinds)]
    expert_layers = kinds.count("experts")
    least = traced["train_steps"] * 2 * expert_layers * floor(s["batch"] * s["seq"], held)  # forward, lhs-gradient
    rollouts = ctx["cell"]["recipe"]["method"]["num_rollouts"]
    if traced["generated_tokens"]:  # a whole cycle: its scoring and its prefill too
        least += traced["iterations"] * (
            sum(trains) * floor(rollouts * s["seq"], held) + expert_layers * floor(rollouts * s["prompt"], held))
    return 100.0 * least / ctx["chips"] / spent
