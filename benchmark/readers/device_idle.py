"""1 - (union of device-op intervals) / (traced window), in percent."""


def read(ctx, spec):
    red = ctx["reduction"]
    if not red or not red["window_s"] or not red["n_devices"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
