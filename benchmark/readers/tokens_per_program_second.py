"""One chip's share of the tokens generated in the traced window over the
device time of the generate program(s) (spec["programs"]; the mean over the
chips' planes): tokens per second of one chip."""


def read(ctx, spec):
    red, traced = ctx["reduction"], ctx["traced"]
    if not red or not traced or not traced["generated_tokens"]:
        return None
    seconds = sum(r["total_s"] for r in ctx["trace"].program_rows(red, spec["programs"]))
    return traced["generated_tokens"] / ctx["chips"] / seconds if seconds else None
