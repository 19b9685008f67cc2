"""`program_log` times spec["scale"]: the median over the measured iterations
of one key of the program's phase-window records, converted to the metric's
unit (bytes to GB: 1e-9). Nothing where no record has the key, as the parent
of the PR that adds the counter has not."""

from benchmark.readers import program_log


def read(ctx, spec):
    value = program_log.read(ctx, spec)
    return None if value is None else value * spec["scale"]
