"""Median over the measured iterations' phase-window records of
100 x (1 - record[spec["part"]] / record[spec["whole"]]): the share of the
whole that the part leaves. Nothing where no record has both keys."""

import statistics


def read(ctx, spec):
    part, whole = spec["part"], spec["whole"]
    values = [100.0 * (1.0 - p[part] / p[whole]) for p in ctx["window"]["phases"]
              if part in p and p.get(whole)]
    return statistics.median(values) if values else None
