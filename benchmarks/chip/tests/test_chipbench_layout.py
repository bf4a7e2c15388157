"""CPU tests of the chip benchmark's data, arithmetic and refusals.

Nothing here describes a topology or touches an accelerator.
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

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = sorted(p.stem for p in (CHIP / "workloads").glob("*.json"))


def test_benchmark_lists_every_workload_file():
    assert sorted(w["name"] for w in BENCH["workloads"]) == WORKLOADS


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves_to_its_files(name):
    wl, cfg, mix = harness.resolve(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    for key in ("config", "traffic", "chips", "why"):
        assert wl[key] == entry[key]
    assert (CHIP / "drivers" / f"{mix['driver']}.py").is_file()
    assert (CHIP / cfg["reference"]).is_file()
    conf = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert ROOT / conf["file"] == CHIP / "configs" / f"{wl['config']}.json"
    assert conf["source"] == cfg["source"]
    assert conf["reduced"] == cfg["reduced"]
    e2e, layer = harness.cell_metrics(BENCH, name)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in layer:
        assert (CHIP / "layer_metrics" / f"{m['name']}.py").is_file()
    assert set(wl["limits"]) == {"rate_gap", "flow_violations",
                                 "state_mismatches", "byte_gap"}


def test_fleet_config_copies_the_committed_scenario():
    """The deployment's scenario is the committed ``fleet_scaling`` one
    with the dataset, its piece size and the crowd's size set from the
    configuration's own keys."""
    committed = json.loads(
        (ROOT / "benchmarks" / "scenarios" / "fleet_scaling.json").read_text())
    cfg = json.loads((CHIP / "configs" / "imagenet2012_crowd.json").read_text())
    sc = json.loads(json.dumps(cfg["scenario"]))
    manifest = sc["content"]["manifests"][0]
    assert manifest["size_bytes"] == cfg["size_bytes"] == 157_300_000_000
    assert manifest["piece_length"] == cfg["piece_length"] == 4 << 20
    assert -(-cfg["size_bytes"] // cfg["piece_length"]) == cfg["pieces"]
    assert sc["arrivals"][0]["n"] == cfg["clients"]
    assert sc["fabric"]["mirrors"][0]["up_bps"] == cfg["origin_up_bps"]
    assert sc["arrivals"][0]["up_bps"] == cfg["client_up_bps"]
    assert sc["arrivals"][0]["down_bps"] == cfg["client_down_bps"]
    assert sc["fleet"]["backend"] == "pallas"
    assert cfg["reduced"] == ["clients"]
    assert cfg["clients"] < cfg["reduced_from"]["clients"]
    for key in ("size_bytes", "piece_length", "name"):
        manifest[key] = committed["content"]["manifests"][0][key]
    sc["arrivals"][0]["n"] = committed["arrivals"][0]["n"]
    sc["name"] = committed["name"]
    del sc["fleet"]["backend"]
    assert sc == committed


def test_peaks_table():
    row = harness.peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert row["ici_bits_per_s"] == 1600e9
    assert row["hbm_bytes"] == 16e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_work_counts_by_hand():
    wc = harness.load_module(CHIP / "work_counts.py")
    # 128 rows x 125 pieces: 2,000 bytes of have bits, 64,000 of jitter,
    # 500 of replica counts, 512 of picks
    assert wc.argmin_bytes(128, 125) == 2_000 + 64_000 + 500 + 512
    # 131,072 rows x 128 pieces
    assert wc.argmin_bytes(131_072, 128) == (
        2_097_152 + 67_108_864 + 512 + 524_288)
    # 700,466 flows over 100,001 nodes: 12 bytes a flow, 8 a node
    assert wc.waterfill_bytes(700_466, 100_001) == 8_405_592 + 800_008
    assert wc.waterfill_bytes(10, 3, links=1) == 120 + 24 + 40 + 4


def _run_py(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "imagenet2012.flash", "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run_py(ROOT, env)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  ".trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = _run_py(tmp_path, env)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_trace_reduce_on_recorded_excerpt():
    """A 123 ms excerpt of a traced dense-cell window on one v5e: one
    XLA water-fill loop (with the first six operations of its body), the
    completions scatters that followed, and two selection calls; module
    runs span each program's recorded operations."""
    tr = harness.load_module(CHIP / "trace_reduce.py")
    record = json.loads((CHIP / "tests" / "data" / "trace_excerpt.json")
                        .read_text())
    red = tr.reduce(record, 1)
    assert red["window_s"] == pytest.approx(0.123, abs=1e-12)
    # the loop covers its body: busy is the union of 57 merged intervals
    assert red["busy_s"] == pytest.approx(0.102442102, abs=1e-12)
    # own time: the loop's 101,695,815 ns less its six listed body ops
    assert red["top_ops"][0] == ["jit__unknown/while",
                                 pytest.approx(0.100748472)]
    assert red["top_ops"][1] == ["jit__unknown/fusion.43",
                                 pytest.approx(0.000944446)]
    # idle time by the innermost span the host was in at each gap's middle
    gaps = dict(red["idle_gaps"])
    assert gaps["waterfill"] == pytest.approx(0.001273039 + 0.005311963)
    assert gaps["select"] == pytest.approx(0.002453375)
    assert gaps["chunk"] == pytest.approx(0.011519521)
    assert sum(gaps.values()) == pytest.approx(0.123 - 0.102442102)
    # by whole programs: the selection program runs 0.3-0.5 ms before its
    # host spans on the device's clock and still counts for them; the
    # scatters that run between calls count for neither
    assert red["in_span"]["select"] == pytest.approx((153_831 + 69_208) * 1e-9)
    assert red["in_span"]["waterfill"] == pytest.approx(0.101695815)
    assert red["in_span"]["chunk"] == pytest.approx((478_145 + 45_183) * 1e-9)
    # without module runs: the union of the operations in or just before
    # each span
    del record["modules"]
    red = tr.reduce(record, 1)
    assert red["in_span"]["select"] == pytest.approx((153_811 + 69_193) * 1e-9)
    assert red["in_span"]["waterfill"] == pytest.approx(0.101695815)
