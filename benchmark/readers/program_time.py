"""Device time of the jitted program(s) whose name matches spec["programs"],
from the modules line of the trace. spec["reduce"]: "median" (of one
execution of the program with most time) or "per_iteration" (summed, over
the traced iterations); spec["scale"] converts seconds to the metric's unit."""


def read(ctx, spec):
    red, traced = ctx["reduction"], ctx["traced"]
    if not red or not traced:
        return None
    rows = ctx["trace"].program_rows(red, spec["programs"])
    if not rows:
        return None
    if spec["reduce"] == "median":
        value = ctx["trace"].median_execution_seconds(red, spec["programs"])
    else:
        value = sum(r["total_s"] for r in rows) / traced["iterations"]
    return value * spec.get("scale", 1)
