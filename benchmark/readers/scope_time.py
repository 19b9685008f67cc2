"""Device time by the program's own scope names.

The program (trlx_tpu/observability/device_scopes.py) keeps, for every
jitted program it dispatched while the profiler was on, a table *compiled
instruction -> the `jax.named_scope` names on its `op_name` path, and its
pass* (`fwd` | `bwd` | `recompute`). `tables()` hands them over in the run's
own process; they join `reduction["ops"]` by `<module>/<instruction>`.

A row counts when it is not a container (a loop, a conditional or a call
contains its body's events, and those are counted), lies in a program
matching spec["programs"] (default: all), carries every name of
spec["scopes"] (default: none asked) on its path, so a scope stands for
everything under it, and is of spec["pass"] where given. spec["reduce"]:

- `ms_per_train_step`: milliseconds over the traced executions of the
  matching programs (one chip's: the reduction's seconds are the mean over
  the planes, and so is the count);
- `s_per_iteration`: seconds over `traced["iterations"]`;
- `attributed_pct`: of the seconds of the rows of the matching programs,
  the share of rows with a scope name on their path; a row without a table
  entry (the compiler's own copies and waits, a program outside the funnel)
  counts as unattributed.

Nothing where the trace holds no matching program, or the program has no
such module (the parent of the PR that added it).
"""

import re


def read(ctx, spec):
    red, traced = ctx["reduction"], ctx["traced"]
    if not red or not traced:
        return None
    try:
        from trlx_tpu.observability import device_scopes
    except ImportError:
        return None
    scopes = {}
    for table in device_scopes.tables():
        for name, entry in table["ops"].items():
            scopes.setdefault(f"{table['module']}/{name}", entry)
    want, which = spec.get("scopes", []), spec.get("pass")
    in_programs = lambda program: re.search(spec.get("programs", ""), program)
    total = matched = named = 0.0
    for name, row in red["ops"].items():
        if row["container"] or not in_programs(name.split("/", 1)[0]):
            continue
        total += row["seconds"]
        chain, row_pass = scopes.get(name, ("", ""))
        if chain:
            named += row["seconds"]
        path = chain.split("/")
        if all(s in path for s in want) and which in (None, row_pass):
            matched += row["seconds"]
    executions = sum(row["count"] for program, row in red["programs"].items() if in_programs(program))
    if not executions:
        return None
    if spec["reduce"] == "attributed_pct":
        return 100.0 * named / total if total else None
    if spec["reduce"] == "ms_per_train_step":
        return 1000.0 * matched / (executions / max(red["n_devices"], 1))
    return matched / traced["iterations"]
