"""Median over the window's step records (metrics.jsonl, one per logged train
step) of one key: spec {"key": ...}. Nothing where no record has the key."""

import statistics


def read(ctx, spec):
    values = [r[spec["key"]] for r in ctx["window"]["steps"] if spec["key"] in r]
    return statistics.median(values) if values else None
