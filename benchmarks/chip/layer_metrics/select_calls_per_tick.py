"""``select_calls_per_tick``: the program's ``fleet.select`` spans over
its ``fleet.tick`` spans, of those that start inside the traced window.
One span is one selection call; on the device path each is a round trip
that waits for its picks."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    ticks = None if red is None else red["spans"].get("fleet.tick")
    if ticks is None:
        return None
    calls = red["spans"].get("fleet.select", {"count": 0})["count"]
    return calls / ticks["count"]
