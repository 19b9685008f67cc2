"""One number over the window's records (metrics.jsonl): spec["records"] names
them, "steps" (one per logged train step), "phases" (one per measured
iteration's phase window) or both, and spec["keys"] the keys read from them.
With spec["over"]: the sum of those keys over the sum of `over`, taken in the
records that carry a key (a stall's share of the seconds it fell in). Without:
the largest value of any key. 0.0 where the keys are there and all read 0;
nothing where no record has a key, as the parent of the PR that adds the
counters has not."""


def read(ctx, spec):
    records = [r for kind in spec["records"] for r in ctx["window"][kind] if any(k in r for k in spec["keys"])]
    if not records:
        return None
    values = [r[k] for r in records for k in spec["keys"] if k in r]
    if "over" not in spec:
        return float(max(values))
    whole = sum(r.get(spec["over"], 0.0) for r in records)
    return sum(values) / whole if whole > 0 else None
