"""``resample_share``: the host time of the program's ``fleet.resample``
spans (sampling the rows' source tables from the holders of their
pieces), clipped to the traced window, as a share of it (%)."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    span = None if red is None else red["spans"].get("fleet.resample")
    if span is None:
        return None
    return 100.0 * span["host_s"] / red["window_s"]
