"""Tokens generated in the traced window over the device time of the
generate program(s) (spec["programs"])."""


def read(ctx, spec):
    red, traced = ctx["reduction"], ctx["traced"]
    if not red or not traced or not traced["generated_tokens"]:
        return None
    seconds = sum(r["total_s"] for r in ctx["trace"].program_rows(red, spec["programs"]))
    return traced["generated_tokens"] / seconds if seconds else None
