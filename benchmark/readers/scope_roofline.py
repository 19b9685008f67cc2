"""A layer's pass over many tokens against its roofline, by SCOPE time: the
least time the chip could take for the calls a train step needs over the
device seconds a train step spends under the scope that makes them
(`scope_time`'s rows: spec["scopes"], spec["programs"]), whatever shapes or
kernels the pass is written with.

Per call the floor is the larger of operations over peak FLOP/s and bytes over
peak bytes/s of the configuration's count spec["count"](arch, rows, positions)
(one layer, forward). A train step needs it in every layer whose
`mixer_layers` kind is spec["mixer"], over batch x seq, forward and twice more
for the gradients (activation gradients cross every layer; the recomputation
under remat is in the time and not in the need).

Nothing on a run without the chip's peaks (a rehearsal), where the
configuration's count has no such function, or where the trace holds nothing
under the scope (the parent of the PR that added it).
"""

from benchmark.readers import scope_time


def read(ctx, spec):
    f, peaks, arch, s = (ctx[k] for k in ("flops", "peaks", "arch", "shapes"))
    layers = list(arch.get("mixer_layers", ())).count(spec["mixer"])
    if not peaks or not layers or not hasattr(f, spec["count"]):
        return None
    ms = scope_time.read(ctx, {**spec, "reduce": "ms_per_train_step"})
    if not ms:
        return None
    floor = f.least_seconds(*getattr(f, spec["count"])(arch, s["batch"], s["seq"]), peaks)[0]
    return 100.0 * 3 * layers * floor / (ms / 1000.0)
