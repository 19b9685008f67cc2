"""An attention kind's pass over many tokens against its roofline where the
pass is made of SEVERAL scopes, each with a count of its own:
`attention_scope_roofline`'s rule (the least time the chip could take for the
calls a train step needs over the device seconds a train step spends under the
scopes that make them), summed over spec["parts"]. A part is {"scopes": the
names its rows carry (`scope_time`'s rule), "count": the configuration's
function (arch, rows, positions) -> (operations, bytes) of one layer's forward,
"passes": how often a train step needs it (3: forward and twice more for the
gradients; 1: a part with no gradient)}. The layers that make the calls are
told by the configuration's `attention` kind: every layer whose mixer is
attention, in a configuration whose `attention` is spec["attention"].

The need is each part's own (the larger of operations over peak FLOP/s and
bytes over peak bytes/s); the time is the sum of the parts' scope times, the
recomputation under remat in it and not in the need.

Nothing on a run without the chip's peaks (a rehearsal), where the
configuration is of another attention kind or its count lacks a part's
function, or where the trace holds nothing under any of the scopes (the
parent of the PR that added them).
"""

from benchmark.readers import scope_time


def read(ctx, spec):
    f, peaks, arch, s = (ctx[k] for k in ("flops", "peaks", "arch", "shapes"))
    mixers = list(arch.get("mixer_layers") or ["attention"] * arch["n_layer"])
    layers = mixers.count("attention") if arch.get("attention", "mha") == spec["attention"] else 0
    if not peaks or not layers or not all(hasattr(f, part["count"]) for part in spec["parts"]):
        return None
    ms = need = 0.0
    for part in spec["parts"]:
        ms += scope_time.read(ctx, {**spec, "scopes": part["scopes"], "reduce": "ms_per_train_step"}) or 0.0
        need += part["passes"] * f.least_seconds(*getattr(f, part["count"])(arch, s["batch"], s["seq"]), peaks)[0]
    if not ms:
        return None
    return 100.0 * layers * need / (ms / 1000.0)
