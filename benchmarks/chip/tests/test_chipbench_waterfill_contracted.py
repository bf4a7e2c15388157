"""CPU tests of the ``waterfill_contracted_share`` reader on constructed
records: the share of the window's ``fleet.waterfill`` spans whose
``contracted`` metadata says the one-hot contraction ran them, and
nothing where the spans carry no such metadata (a program without the
contraction) or where there is no program trace at all."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

MS = 1e6  # nanoseconds


def _record(contracted) -> dict:
    """A window from 0 to 100 ms holding one tick and two water-fill
    calls; ``contracted`` gives each call's metadata, None for none."""
    calls = []
    for start, c in zip((20, 60), contracted or (None, None)):
        meta = {"rounds": 7}
        if c is not None:
            meta["contracted"] = c
        calls.append(["fleet.waterfill", start * MS, 10 * MS, meta])
    return {
        "spans": [["bench.window", 0.0, 100 * MS]],
        "devices": {"0": [["op", "", 5 * MS, 90 * MS]]},
        "modules": {},
        "program_spans": [["fleet.tick", 10 * MS, 80 * MS, {}], *calls],
    }


def _read(monkeypatch, record):
    pt = harness.load_module(CHIP / "program_trace.py")
    monkeypatch.setattr(
        pt, "for_run",
        lambda ctx: None if record is None else pt.reduce(record, 1))
    reader = harness.load_module(
        CHIP / "layer_metrics" / "waterfill_contracted_share.py")
    return reader.read(None)


@pytest.mark.parametrize("contracted,want", [
    ((1, 1), 100.0), ((0, 1), 50.0), ((0, 0), 0.0), (None, None)])
def test_waterfill_contracted_share_on_a_constructed_record(
        monkeypatch, contracted, want):
    assert _read(monkeypatch, _record(contracted)) == want


def test_waterfill_contracted_share_without_a_program_trace(monkeypatch):
    assert _read(monkeypatch, None) is None
