"""``select_shard_imbalance``: how far the busiest chip's rows set the
work of a selection where several chips share the client rows. Over the
program's ``fleet.select`` spans that start inside the traced window:
chips × Σ ``shard_rows_max`` (the rows of the call's busiest chip) ÷
Σ ``rows`` (the rows selected). 1.0 when every call splits its rows
evenly; each chip's bucket is padded to the busiest chip's. None where
the spans carry no such metadata (one chip, or a program without it)."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    span = None if red is None else red["spans"].get("fleet.select")
    if span is None:
        return None
    meta = span["meta"]
    if not meta.get("rows") or "shard_rows_max" not in meta:
        return None
    chips = meta["devices"] / span["count"]
    return chips * meta["shard_rows_max"] / meta["rows"]
