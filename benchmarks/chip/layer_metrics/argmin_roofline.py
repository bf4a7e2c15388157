"""``argmin_roofline``: piece selection's share of its memory roofline
(%). The least time is the bytes every traced ``select`` call must move
(``work_counts.argmin_bytes`` of the call's rows and pieces) over the
chip's HBM bandwidth; the time taken is the device time of the operations
that ran inside the calls' ``bench.select`` spans."""


def read(ctx):
    red = ctx.reduction
    calls = [c for c in ctx.calls if c[0] == "select"]
    if red is None or ctx.peaks is None or not calls:
        return None
    spent = red["in_span"].get("select")
    if not spent:
        return None
    least = sum(ctx.work_counts.argmin_bytes(c[3], c[4]) for c in calls)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / spent
