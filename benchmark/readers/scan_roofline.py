"""A state-space layer's chunked scan against its roofline: the least time
the chip could take for the scans the traced window needs over the summed
device time of the scan's operations (see `trace.op_rows`), in every program
that makes them. Which operations those are is said once: the metric's file
names, under `operations_of`, the metric whose file holds the `select` (the
scan's share of busy time reads the same operations).

The scan is XLA fusions with no name of their own, so the calls are counted
from the cell's shapes, not parsed from the trace. Per call the floor is the
larger of operations over peak FLOP/s and bytes over peak bytes/s of
`counts.ssd_scan_call(arch, rows, positions)` (one layer, forward, the chunked
form). The calls of the traced window (`ctx["traced"]`):

    train step   every state-space layer over batch x seq, forward and twice
                 more for the gradients of x, the step, B and C (activation
                 gradients cross every layer; the recomputation under remat is
                 in the time and not in the need)
    scoring      the frozen branch (the state-space layers among the top
                 `unfrozen` blocks) forward over a rollout chunk x seq
    prefill      every state-space layer forward over a rollout chunk x prompt

A decode step runs no chunked scan (`ssm_decode_roofline` reads it). Nothing
where the trace shows no such operation or the configuration's count has no
`ssd_scan_call`."""

import json
import os


def read(ctx, spec):
    if "operations_of" in spec:
        with open(os.path.join(os.path.dirname(__file__), os.pardir, "layer_metrics", spec["operations_of"] + ".json")) as src:
            spec = {**spec, **{k: v for k, v in json.load(src).items() if k in ("programs", "select", "exclude")}}
    red, peaks, f, arch, s, traced = (ctx[k] for k in ("reduction", "peaks", "flops", "arch", "shapes", "traced"))
    if not red or not peaks or not traced or not hasattr(f, "ssd_scan_call") or "mamba" not in arch.get("mixer_layers", ()):
        return None
    spent = sum(row["seconds"] for row in ctx["trace"].op_rows(red, spec))
    if not spent:
        return None
    floor = lambda rows, positions: f.least_seconds(*f.ssd_scan_call(arch, rows, positions), peaks)[0]
    kinds = list(arch["mixer_layers"])
    unfrozen = s["unfrozen"] if 0 < s["unfrozen"] < len(kinds) else len(kinds)
    least = traced["train_steps"] * 3 * kinds.count("mamba") * floor(s["batch"], s["seq"])
    if traced["generated_tokens"]:  # a whole cycle: its scoring and its prefill too
        rollouts = ctx["cell"]["recipe"]["method"]["num_rollouts"]
        least += traced["iterations"] * (kinds[-unfrozen:].count("mamba") * floor(rollouts, s["seq"])
                                         + kinds.count("mamba") * floor(rollouts, s["prompt"]))
    return 100.0 * least / ctx["chips"] / spent
