"""``flow_table_share``: the host time of the program's
``fleet.flow_table`` spans (HTTP admission, the swarm flows, choking to
the unchoke budget), clipped to the traced window, as a share of it
(%)."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    span = None if red is None else red["spans"].get("fleet.flow_table")
    if span is None:
        return None
    return 100.0 * span["host_s"] / red["window_s"]
