"""Of the device's idle seconds that the reduction labels
(`reduction["idle_by_label"]`, label `<host event> [<program> -> <program>]`),
the share, in percent, whose host part is a host event and not
spec["unattributed"]: a span of the program, or one of JAX's own host events
inside one. Nothing where the trace shows no idle gap."""


def read(ctx, spec):
    red = ctx["reduction"]
    gaps = (red or {}).get("idle_by_label") or {}
    total = sum(gaps.values())
    if not total:
        return None
    named = sum(s for label, s in gaps.items() if label.split(" [", 1)[0] != spec["unattributed"])
    return 100.0 * named / total
