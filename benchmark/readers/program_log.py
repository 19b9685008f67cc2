"""Median over the measured iterations of one key of the program's own
phase-window records (metrics.jsonl, `time/*`): spec {"key": ...}."""

import statistics


def read(ctx, spec):
    values = [p[spec["key"]] for p in ctx["window"]["phases"] if spec["key"] in p]
    return statistics.median(values) if values else None
