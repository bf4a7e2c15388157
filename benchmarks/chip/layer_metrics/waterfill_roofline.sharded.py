"""``waterfill_roofline.sharded``: the water-fill's share of its memory
roofline (%) where several chips share the client rows. The least time
is the bytes every traced call must move (``work_counts.waterfill_bytes``,
as ``waterfill_roofline`` counts them) over one chip's HBM bandwidth; the
time taken is the device time inside the calls' ``bench.waterfill`` spans
summed over the traced chips. The water-fill runs on the mesh's first
chip alone, so the sum is that chip's time (``waterfill_roofline``
divides by the chips' average instead, a quarter of it on four chips)."""


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
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / (
        spent * red["devices"])
