"""CPU tests of the readers of the program's own ``fleet.*`` spans.

``program_trace`` is checked by hand on a recorded excerpt of a traced
window on one v5e; the five readers on that excerpt and without the
program's spans (the program before it had them); and a traced run of
the cell on the CPU, in a copy of the benchmark, for what a profile
without device planes can give.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

NEW = ("select_calls_per_tick", "waterfill_rounds_per_call",
       "resample_share", "flow_table_share", "idle_outside_ticks_share")
HOST_SIDE = NEW[:4]


def _excerpt() -> dict:
    return json.loads((CHIP / "tests" / "data" / "program_trace_excerpt.json")
                      .read_text())


def _read_all(monkeypatch, record) -> dict:
    """Every new reader's value, with the profile's reduction given."""
    pt = harness.load_module(CHIP / "program_trace.py")
    monkeypatch.setattr(pt, "for_run", lambda ctx: pt.reduce(record, 1))
    return {name: harness.load_module(
        CHIP / "layer_metrics" / f"{name}.py").read(None) for name in NEW}


MS = 1e6  # nanoseconds


def _constructed() -> dict:
    """A window from 10 to 110 ms. Tick A (0-30 ms) straddles its start,
    tick B (50-120 ms) its end; seven device operations leave six gaps:
    21-23 ms in A's resampling, 40-40.5 ms between the ticks, 48.6-49.4 ms
    just before B (its middle outside B), 62-69.5 ms in a selection,
    76-78 ms in B outside its inner spans, 85-86 ms in a water-fill."""
    return {
        "spans": [["bench.window", 10 * MS, 100 * MS],
                  ["bench.select", 60 * MS, 10 * MS]],
        "devices": {"0": [["op", "", a * MS, (b - a) * MS] for a, b in (
            (5, 21), (23, 40), (40.5, 48.6), (49.4, 62), (69.5, 76),
            (78, 85), (86, 111))]},
        "modules": {},
        "program_spans": [
            ["fleet.tick", 0.0, 30 * MS, {}],
            ["fleet.resample", 20 * MS, 5 * MS, {}],
            ["fleet.tick", 50 * MS, 70 * MS, {}],
            ["fleet.select", 60 * MS, 10 * MS, {}],
            ["fleet.flow_table", 72 * MS, 3 * MS, {}],
            ["fleet.waterfill", 80 * MS, 10 * MS, {"rounds": 7}],
            ["fleet.waterfill", 95 * MS, 5 * MS, {"rounds": 5}],
        ],
    }


def test_program_trace_by_hand_on_a_constructed_record(monkeypatch):
    pt = harness.load_module(CHIP / "program_trace.py")
    red = pt.reduce(_constructed(), 1)
    assert red["window_s"] == pytest.approx(0.1)
    spans = red["spans"]
    # tick A starts before the window: not counted; tick B is clipped
    assert spans["fleet.tick"] == {"count": 1,
                                   "host_s": pytest.approx(0.060),
                                   "meta": {}}
    assert spans["fleet.waterfill"]["count"] == 2
    assert spans["fleet.waterfill"]["host_s"] == pytest.approx(0.015)
    assert spans["fleet.waterfill"]["meta"] == {"rounds": 12}
    assert spans["fleet.resample"]["host_s"] == pytest.approx(0.005)
    idle = red["idle_s"]
    assert idle == {"fleet.resample": pytest.approx(0.002),
                    "outside": pytest.approx(0.0013),
                    "fleet.tick": pytest.approx(0.002),
                    "fleet.select": pytest.approx(0.0075),
                    "fleet.waterfill": pytest.approx(0.001)}
    assert red["idle_outside_ticks_s"] == pytest.approx(0.0013)
    assert _read_all(monkeypatch, _constructed()) == {
        "select_calls_per_tick": 1.0, "waterfill_rounds_per_call": 6.0,
        "resample_share": pytest.approx(5.0),
        "flow_table_share": pytest.approx(3.0),
        "idle_outside_ticks_share": pytest.approx(1.3)}
    # without device planes: no idle figures
    record = _constructed()
    record["devices"] = {}
    assert pt.reduce(record, 1)["idle_s"] is None
    assert _read_all(monkeypatch, record)["idle_outside_ticks_share"] is None


def test_program_trace_on_recorded_excerpt(monkeypatch):
    """925 ms of a traced window of the cell on one v5e (seed 3141592653):
    the end of a tick's completion chain (the window opens inside it and
    inside a selection), the check's state copies between that tick and
    the next (the replayed tick), and the next tick's selection, flow
    table, water-fill and first chained selection and resampling (the
    window closes inside it). Operations nested in another (the
    water-fill loop's body) are left out: busy time is their union."""
    pt = harness.load_module(CHIP / "program_trace.py")
    red = pt.reduce(_excerpt(), 1)
    assert red["window_s"] == pytest.approx(0.925025056, abs=1e-12)
    spans = red["spans"]
    # the first tick and selection start before the window: not counted;
    # the second tick is clipped at the window's end
    assert spans["fleet.tick"] == {"count": 1, "host_s": pytest.approx(0.11),
                                   "meta": {}}
    assert spans["fleet.select"]["count"] == 4
    assert spans["fleet.select"]["host_s"] == pytest.approx(
        (12_872_349 + 12_636_479 + 11_846_329 + 34_988_919) * 1e-9)
    assert spans["fleet.resample"]["count"] == 4
    assert spans["fleet.resample"]["host_s"] == pytest.approx(
        (3_297_560 + 2_263_780 + 2_825_059 + 2_057_720) * 1e-9)
    assert spans["fleet.waterfill"]["meta"] == {"rounds": 28}
    assert spans["fleet.flow_table"]["host_s"] == pytest.approx(0.0107657)
    # idle by the innermost span at each gap's middle; the short gaps
    # between back-to-back operations add nanoseconds
    idle = red["idle_s"]
    assert idle["outside"] == pytest.approx(0.775448707, abs=1e-8)
    assert idle["fleet.flow_table"] == pytest.approx(0.013751306, abs=1e-9)
    assert idle["fleet.resample"] == pytest.approx(0.005811697, abs=1e-8)
    assert idle["fleet.waterfill"] == pytest.approx(0.004279261, abs=1e-7)
    assert idle["fleet.select"] == pytest.approx(
        (4_939_464 + 4_557_846) * 1e-9, abs=1e-4)
    assert red["idle_outside_ticks_s"] == idle["outside"]
    window = 925_025_056
    assert _read_all(monkeypatch, _excerpt()) == {
        "select_calls_per_tick": 4.0, "waterfill_rounds_per_call": 28.0,
        "resample_share": pytest.approx(100 * 10_444_119 / window),
        "flow_table_share": pytest.approx(100 * 10_765_700 / window),
        "idle_outside_ticks_share": pytest.approx(
            100 * 775_448_707 / window)}


def test_readers_give_nothing_without_the_programs_spans(monkeypatch):
    record = _excerpt()
    record["program_spans"] = []
    pt = harness.load_module(CHIP / "program_trace.py")
    red = pt.reduce(record, 1)
    assert red["spans"] == {}
    assert red["idle_s"] == {"outside": pytest.approx(
        red["idle_outside_ticks_s"])}
    assert _read_all(monkeypatch, record) == dict.fromkeys(NEW)


def test_run_traced_on_the_cpu_reports_the_host_side_metrics(tmp_path):
    """In a copy of the benchmark, so that its profile is the only one
    under ``.trace/`` while it runs."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace"))
    script = f"""
import json, sys, time
sys.path[:0] = [{str(chip)!r}, {str(ROOT / "src")!r}]
import harness
wl, cfg, mix = harness.resolve("imagenet2012.flash")
mix = dict(mix, trace_seconds=0.3, start_at=12.0)
cell = harness.Cell("imagenet2012.flash", wl, cfg, mix, 2718281828459, 0.8,
                    True, overrides={{"n": 40, "size_bytes": 2_000_000_000}})
res = harness.run_cell(cell, t_process=time.perf_counter(),
                       require_tpu=False)
print(json.dumps(res))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    got = res["metrics"]
    assert set(HOST_SIDE) <= set(got)
    assert "idle_outside_ticks_share" not in got
    assert got["select_calls_per_tick"]["value"] > 1
    assert got["waterfill_rounds_per_call"]["value"] >= 1
    assert 0 < got["resample_share"]["value"] < 100
    assert 0 < got["flow_table_share"]["value"] < 100
    line = next(s for s in proc.stderr.splitlines()
                if s.startswith("program trace: "))
    summary = json.loads(line[len("program trace: "):])
    assert summary["idle_s"] is None
    assert summary["count"]["fleet.waterfill"] == summary["count"][
        "fleet.tick"]
    assert not (chip / ".trace" / "imagenet2012.flash").exists()
