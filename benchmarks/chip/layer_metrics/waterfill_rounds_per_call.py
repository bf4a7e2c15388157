"""``waterfill_rounds_per_call``: the fixed-point rounds the program's
``fleet.waterfill`` spans carry (their ``rounds`` metadata, the change in
``FleetDeviceState.rounds`` across the call), summed, over the number of
those spans, of those that start inside the traced window."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    span = None if red is None else red["spans"].get("fleet.waterfill")
    if span is None or "rounds" not in span["meta"]:
        return None
    return span["meta"]["rounds"] / span["count"]
