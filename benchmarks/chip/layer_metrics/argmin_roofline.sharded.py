"""``argmin_roofline.sharded``: piece selection's share of its memory
roofline (%) where several chips share the client rows. The least time
is the bytes every traced ``select`` call must move
(``work_counts.argmin_bytes`` of all its rows, over every chip) over one
chip's HBM bandwidth; the time taken is the device time of the
selection programs summed over the traced chips (``argmin_roofline``
divides by one chip's average instead, which reads the chips' combined
bandwidth as one chip's)."""


def read(ctx):
    red = ctx.reduction
    calls = [c for c in ctx.calls if c[0] == "select"]
    if red is None or ctx.peaks is None or not calls:
        return None
    spent = red["in_span"].get("select")
    if not spent:
        return None
    least = sum(ctx.work_counts.argmin_bytes(c[3], c[4]) for c in calls)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / (
        spent * red["devices"])
