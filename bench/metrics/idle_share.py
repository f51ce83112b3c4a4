"""Device: share of the traced window in which no operation ran on the
device, in %, averaged over the chips (profiler trace)."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
