"""An attention kind's pass over many tokens against its roofline, by SCOPE
time: `scope_roofline`'s rule (the least time the chip could take for the
calls a train step needs over the device seconds a train step spends under the
scope that makes them) where the layers that make the calls are told by the
configuration's `attention` kind, not by `mixer_layers`: every layer whose
mixer is attention, in a configuration whose `attention` is spec["attention"].

Per call the floor is the larger of operations over peak FLOP/s and bytes over
peak bytes/s of the configuration's count spec["count"](arch, rows, positions)
(one layer, forward); a train step needs it in every such layer over batch x
seq, forward and twice more for the gradients (the recomputation under remat
is in the time and not in the need).

Nothing on a run without the chip's peaks (a rehearsal), where the
configuration is of another attention kind or its count has no such function,
or where the trace holds nothing under the scope (the parent of the PR that
added it).
"""

from benchmark.readers import scope_time


def read(ctx, spec):
    f, peaks, arch, s = (ctx[k] for k in ("flops", "peaks", "arch", "shapes"))
    mixers = list(arch.get("mixer_layers") or ["attention"] * arch["n_layer"])
    layers = mixers.count("attention") if arch.get("attention", "mha") == spec["attention"] else 0
    if not peaks or not layers or not hasattr(f, spec["count"]):
        return None
    ms = scope_time.read(ctx, {**spec, "reduce": "ms_per_train_step"})
    if not ms:
        return None
    floor = f.least_seconds(*getattr(f, spec["count"])(arch, s["batch"], s["seq"]), peaks)[0]
    return 100.0 * 3 * layers * floor / (ms / 1000.0)
