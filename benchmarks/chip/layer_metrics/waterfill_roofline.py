"""``waterfill_roofline``: the water-fill's share of its memory roofline
(%). The least time is the bytes every traced call must move
(``work_counts.waterfill_bytes``: the unpadded flow table read once,
rates written, node capacities read) over the chip's HBM bandwidth,
whatever implementation, padding or number of rounds ran; the time taken
is the device time of the operations inside the calls' ``bench.waterfill``
spans."""


def read(ctx):
    red = ctx.reduction
    calls = [c for c in ctx.calls if c[0] == "waterfill"]
    if red is None or ctx.peaks is None or not calls:
        return None
    spent = red["in_span"].get("waterfill")
    if not spent:
        return None
    least = sum(ctx.work_counts.waterfill_bytes(c[3], c[4], c[5])
                for c in calls)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / spent
