"""One field of the compile log over set-up (process start to the first
measured step): "seconds", "requests" or "cache_hits"."""


def read(ctx, spec):
    return float(ctx["compile"][spec["field"]])
