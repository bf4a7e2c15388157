"""CPU tests of the fleet cell's harness path, at a few dozen clients.

The chip's look for a TPU is turned off; everything else of a run goes
as on the chip: set-up with warm-up, the window, the check against the
plain reference. The planted faults and the control must each come out
not correct.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import harness  # noqa: E402

NAME = "imagenet2012.flash"
# 40 clients, 477 pieces of 4 MiB (the last one short): long enough that
# nobody finishes inside a test's window
SMALL = {"n": 40, "size_bytes": 2_000_000_000}


def small_cell(seed: int = 987654321012, fault=None, seconds: float = 0.6,
               trace: bool = False) -> harness.Cell:
    wl, cfg, mix = harness.resolve(NAME)
    mix = dict(mix, trace_seconds=0.3, start_at=12.0)
    return harness.Cell(NAME, wl, cfg, mix, seed, seconds, trace, fault,
                        overrides=SMALL)


def run(cell: harness.Cell) -> dict:
    return harness.run_cell(cell, t_process=time.perf_counter(),
                            require_tpu=False)


@pytest.mark.parametrize("seed", [5, 987654321012])
def test_cell_runs_correct_on_cpu(seed):
    res = run(small_cell(seed))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"sim_s_per_wall_s", "setup_s"}
    assert res["metrics"]["sim_s_per_wall_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"rate_gap", "flow_violations",
                                  "state_mismatches", "byte_gap"}
    assert res["checks"]["byte_gap"]["value"] == 0


@pytest.mark.parametrize("fault", ["control", "state_unchanged",
                                   "half_batch", "answer_altered",
                                   "progress_scaled"])
def test_planted_fault_is_not_correct(fault):
    res = run(small_cell(fault=fault, seconds=1.0))
    assert not res["correct"], (fault, res["checks"])
    assert res["failed"] >= 1


def test_progress_fault_is_caught_by_the_tick_replay_alone():
    # the rates the program reports are right; only the bookkeeping that
    # consumes them is off
    res = run(small_cell(fault="progress_scaled", seconds=1.0))
    checks = res["checks"]
    assert checks["rate_gap"]["value"] <= checks["rate_gap"]["limit"]
    assert checks["byte_gap"]["value"] > 1e3


@pytest.mark.parametrize("trace_seconds", [0.3, 5.0])
def test_traced_run_reports_layer_metrics(trace_seconds):
    # the profiler stops inside the window, or with it
    cell = small_cell(trace=True, seconds=1.0)
    cell.mix["trace_seconds"] = trace_seconds
    res = run(cell)
    assert res["correct"]
    got = res["metrics"]
    # no TPU planes on the CPU: only the program's own spans and counters
    assert got["compiles_in_window"]["value"] == 0
    shares = [got[name]["value"] for name in
              ("bookkeeping_share", "select_share", "waterfill_share")]
    assert all(v >= 0 for v in shares) and 50 <= sum(shares) <= 100.5
    assert "device_idle_share.fleet" not in got
    assert "argmin_roofline" not in got


def test_window_runs_one_tick_a_chunk_and_replays_the_drawn_ones():
    fleet = harness.load_module(CHIP / "drivers" / "fleet.py")
    cell = small_cell(seed=31, seconds=1.0)
    state = fleet.setup(cell)
    sim = state["sim"]
    assert sim.now == 12.0 and sim.ticks == 12
    assert len(state["log"].check_ticks) == fleet.REPLAYED_TICKS
    state["log"] = fleet.Log(31, 3, 6)
    chosen = state["log"].check_ticks
    assert len(chosen) == 3 and chosen <= set(range(6))
    out = fleet.window(state, cell, harness.Tracer(False, 0, Path(".")))
    ticks = out["extra"]["ticks"]
    assert ticks >= 6
    assert out["attempted"] == ticks == sim.ticks - 12
    assert out["extra"]["sim_s"] == pytest.approx(float(ticks))
    replayed = state["log"].ticks
    assert sorted(t["ticks"] - 12 for t in replayed) == sorted(chosen)
    assert all(t["one_tick"] and t["dt"] == 1.0 and "flows" in t
               for t in replayed)
    # the same seed draws the same ticks
    assert fleet.Log(31, 3, 6).check_ticks == chosen


def test_window_refuses_a_crowd_that_finishes():
    fleet = harness.load_module(CHIP / "drivers" / "fleet.py")
    cell = small_cell(seconds=30.0)
    cell.overrides = {"n": 8, "size_bytes": 8_000_000}
    state = fleet.setup(cell)
    with pytest.raises(RuntimeError, match="finished"):
        fleet.window(state, cell, harness.Tracer(False, 0, Path(".")))


def test_reference_picks_match_a_lexicographic_sort():
    ref = harness.load_module(CHIP / "references" / "fleet.py")
    rng = np.random.default_rng(4)
    k, P = 300, 57
    cand = rng.random((k, P)) < 0.3
    cand[7] = False
    avail = rng.integers(0, 4, P)
    jit = ref.jitter(11, k, P)
    jit[:, :5] = jit[:, 5:10]  # ties in jitter too
    got = ref.rarest_picks(cand, avail, jit, rows_per_block=64)
    for i in range(k):
        idx = np.flatnonzero(cand[i])
        if idx.size == 0:
            assert got[i] == -1
            continue
        order = np.lexsort((idx, jit[i, idx], avail[idx]))
        assert got[i] == idx[order[0]]
    jit[0, 0] = np.float32(2.0**-30)  # no draw of the generator's
    with pytest.raises(ValueError):
        ref.rarest_picks(cand, avail, jit)
