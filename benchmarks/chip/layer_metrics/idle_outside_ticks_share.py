"""``idle_outside_ticks_share``: the device's idle time while the host
was in no ``fleet.tick`` span (between the engine's ticks: the caller's
loop, and each ``run`` call's work before and after its tick), as a
share of the traced window (%). None without device planes, or without
the program's tick spans."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    if (red is None or red["idle_outside_ticks_s"] is None
            or "fleet.tick" not in red["spans"]):
        return None
    return 100.0 * red["idle_outside_ticks_s"] / red["window_s"]
