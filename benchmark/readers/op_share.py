"""Device time of the operations a metric's file selects (see
`trace.op_rows`), as a share of the device's busy time."""


def read(ctx, spec):
    red = ctx["reduction"]
    if not red or not red["busy_s"]:
        return None
    return 100.0 * sum(r["seconds"] for r in ctx["trace"].op_rows(red, spec)) / red["busy_s"]
