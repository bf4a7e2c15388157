"""CPU tests of the four-chip ImageNet cell, ``imagenet2012_100k.flash``.

Its configuration is the one-chip cell's, uncut: the whole crowd, with
four chips sharing the client rows. On the CPU the cell runs scaled down
through ``overrides``, in a subprocess that gives JAX four virtual
devices, from a copy of the benchmark's files (so that its profile is the
only one its readers can find).
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
NAME = "imagenet2012_100k.flash"


def _config(name: str) -> dict:
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_config_is_the_one_chip_crowd_uncut_over_four_chips():
    one, whole = _config("imagenet2012_crowd"), _config(
        "imagenet2012_crowd_100k")
    assert whole["clients"] == one["reduced_from"]["clients"] == 100_000
    assert whole["reduced"] == []
    assert whole["scenario"]["arrivals"][0]["n"] == 100_000
    assert whole["scenario"]["fleet"]["devices"] == 4
    assert whole["deployment"].startswith(one["deployment"])
    assert "four chips" in whole["deployment"]
    # everything else as the one-chip configuration has it; the keys
    # that describe its cut go with the cut
    want = copy.deepcopy(one)
    for key in ("reduced_from", "cut"):
        del want[key]
    want.update(clients=whole["clients"], reduced=[],
                deployment=whole["deployment"])
    want["scenario"]["arrivals"][0]["n"] = 100_000
    want["scenario"]["fleet"]["devices"] = 4
    assert whole == want


SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {chip!r})
sys.path.insert(0, {src!r})
import jax
import harness

assert jax.device_count() == 4
wl, cfg, mix = harness.resolve({name!r})
mix = dict(mix, trace_seconds=0.5, start_at=12.0)
out = []
for trace in (False, True):
    # 42 clients (the last chip holds padding rows), 477 pieces of 4 MiB
    cell = harness.Cell({name!r}, wl, cfg, mix, 2718281829123 + trace, 1.0,
                        trace,
                        overrides={{"n": 42, "size_bytes": 2_000_000_000}})
    out.append(harness.run_cell(cell, t_process=time.perf_counter(),
                                require_tpu=False))
print(json.dumps(out))
"""


def test_cell_runs_correct_on_four_cpu_devices(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    script = SCRIPT.format(chip=str(chip), src=str(ROOT / "src"), name=NAME)
    proc = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    plain, traced = json.loads(proc.stdout.strip().splitlines()[-1])
    for res in (plain, traced):
        assert res["correct"], res["checks"]
        assert res["attempted"] > 0 and res["failed"] == 0
        assert res["device"]["count"] == 4
    assert set(plain["metrics"]) == {"sim_s_per_wall_s", "setup_s"}
    assert plain["metrics"]["sim_s_per_wall_s"]["value"] > 0
    got = traced["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    # the rows of every call's busiest chip, against an even split
    imbalance = got["select_shard_imbalance"]["value"]
    assert 1.0 <= imbalance <= 4.0
    # no TPU planes on the CPU: nothing read from the device trace
    for name in ("device_idle_share.fleet", "argmin_roofline.sharded",
                 "waterfill_roofline.sharded",
                 "idle_outside_ticks_share"):
        assert name not in got
    assert {"select_share", "waterfill_share", "bookkeeping_share",
            "select_calls_per_tick"} <= set(got)


@pytest.mark.parametrize("kind, plain", [("select", "argmin_roofline"),
                                         ("waterfill", "waterfill_roofline")])
def test_sharded_rooflines_divide_by_every_chips_time(kind, plain):
    # the traced four-chip run's reduction averages device time over the
    # chips; the sharded readers take the chips' sum, a quarter of what
    # the one-chip readers give there, and the same on one chip
    sys.path.insert(0, str(CHIP))
    import harness
    import work_counts

    def ctx(devices):
        calls = [("select", 0.0, 0.1, 1000, 37504),
                 ("waterfill", 0.1, 0.4, 200_000, 100_001, 0)]
        return harness.LayerContext(
            reduction={"devices": devices,
                       "in_span": {"select": 0.004, "waterfill": 0.125}},
            calls=calls, phases={}, window_s=1.0, compiles_in_window=0,
            peaks={"hbm_bytes_per_s": 819e9}, work_counts=work_counts)

    def read(name, devices):
        return harness.load_module(
            CHIP / "layer_metrics" / f"{name}.py").read(ctx(devices))

    least = (work_counts.argmin_bytes(1000, 37504) if kind == "select"
             else work_counts.waterfill_bytes(200_000, 100_001))
    spent = 0.004 if kind == "select" else 0.125
    want = 100.0 * least / 819e9 / (4 * spent)
    assert read(f"{plain}.sharded", 4) == pytest.approx(want, rel=1e-12)
    assert read(plain, 4) == pytest.approx(4 * want, rel=1e-12)
    assert read(f"{plain}.sharded", 1) == read(plain, 1)
