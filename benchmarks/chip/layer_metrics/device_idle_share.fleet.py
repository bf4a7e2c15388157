"""``device_idle_share.fleet``: the share of the traced window (%) in
which no operation ran on the chip, from the profiler's device trace."""


def read(ctx):
    red = ctx.reduction
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
