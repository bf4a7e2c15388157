"""``waterfill_contracted_share``: the share of the water-fill calls that
the one-hot contraction ran (%). Over the program's ``fleet.waterfill``
spans that start inside the traced window: Σ ``contracted`` (1 when the
contraction ran the call, else 0) ÷ their number. None where the spans
carry no such metadata (a program without the contraction)."""

from harness import HERE, load_module


def read(ctx):
    red = load_module(HERE / "program_trace.py").for_run(ctx)
    span = None if red is None else red["spans"].get("fleet.waterfill")
    if span is None or "contracted" not in span["meta"]:
        return None
    return 100.0 * span["meta"]["contracted"] / span["count"]
