"""``bookkeeping_share``: the program's own ``phase_seconds["bookkeeping"]`` timer over
the traced window, as a share of it (%). A host span around asynchronous
dispatch: device work that nothing waits for lands in the phase that
next waits."""


def read(ctx):
    value = ctx.phases.get("bookkeeping")
    if value is None or ctx.window_s <= 0:
        return None
    return 100.0 * value / ctx.window_s
