"""Benchmark harness — one function per paper table/figure + beyond-paper.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only eq1,table1,...] \
        [--json DIR] [--compare DIR [--tolerance REL]] \
        [--scenario FILE [--engine time|byte|fleet]] [--profile] [--list]

``--json DIR`` additionally persists each bench's rows as
``BENCH_<name>.json`` under DIR (repo-root convention), so the perf
trajectory accumulates across PRs.

``--compare DIR`` diffs the freshly produced rows against the committed
baselines ``DIR/BENCH_<name>.json`` (numbers extracted from each row's
``derived`` string, compared at ``--tolerance`` relative error;
``us_per_call`` wall times are ignored) and exits non-zero on any metric
regression — the CI gate that keeps the simulation goldens pinned.

``--scenario FILE`` runs a declarative ScenarioSpec JSON. When FILE is a
registered bench's base scenario (see ``--list``), the whole bench suite
runs seeded from it — combined with ``--compare`` this is the gate that
pins the *declarative* compile path bit-identical to the goldens. Any
other scenario file runs generically on ``--engine`` and reports one row
per torrent.

``--trace DIR`` (needs ``--scenario``) forces the flight recorder on,
runs the scenario generically, exports ``TRACE_<name>.jsonl`` +
``TRACE_<name>.chrome.json`` (load in chrome://tracing) +
``METRICS_<name>.json`` under DIR, and replays the trace through the
invariant checker — exits non-zero on any violation.

``--profile`` wraps each selected bench (or the generic scenario run) in
cProfile and dumps the top of the cumulative-time table — the first stop
when a per-tick regression trips the scaling-smoke CI job.

``--list`` prints the registered benchmarks and their scenario files.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from benchmarks import (  # noqa: E402
    bench_adversarial,
    bench_cluster_coldstart,
    bench_durability,
    bench_eq1_ud_ratio,
    bench_fabric_hillclimb,
    bench_fig1_server_load,
    bench_kernels,
    bench_mirror_fabric,
    bench_multi_torrent,
    bench_pipeline,
    bench_roofline,
    bench_swarm_scaling,
    bench_table1_costs,
    bench_tail_latency,
    bench_webseed_hybrid,
)

SUITES = {
    "eq1": bench_eq1_ud_ratio,
    "table1": bench_table1_costs,
    "fig1": bench_fig1_server_load,
    "coldstart": bench_cluster_coldstart,
    "scaling": bench_swarm_scaling,
    "webseed": bench_webseed_hybrid,
    "mirror_fabric": bench_mirror_fabric,
    "tail_latency": bench_tail_latency,
    "multi_torrent": bench_multi_torrent,
    "durability": bench_durability,
    "adversarial": bench_adversarial,
    "pipeline": bench_pipeline,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    # §Perf HC3 iteration suite — ~25 min of event simulation; run via
    # --only fabric_hc (results recorded in EXPERIMENTS.md §Perf)
    "fabric_hc": bench_fabric_hillclimb,
}
DEFAULT_SUITES = [k for k in SUITES if k != "fabric_hc"]


def scenario_file(key: str):
    """The bench's base ScenarioSpec file, or None for non-scenario suites."""
    return getattr(SUITES[key], "SCENARIO", None)


def list_benches() -> None:
    print(f"{'bench':<14} {'scenario file':<46} description")
    for key, mod in SUITES.items():
        scen = scenario_file(key)
        rel = scen.relative_to(Path(__file__).resolve().parent.parent) \
            if scen else "-"
        doc = (mod.__doc__ or "").strip().splitlines()[0]
        print(f"{key:<14} {str(rel):<46} {doc}")


def run_traced_scenario(path: Path, engine: str, trace_dir: Path) -> None:
    """Flight-recorder run: force telemetry on, export the trace artifacts
    and replay the invariant checker over them. Exits non-zero on any
    invariant violation — the CI trace gate."""
    import dataclasses

    from repro.core import ScenarioSpec, TelemetrySpec, TraceChecker

    spec = ScenarioSpec.load(path)
    tel = spec.telemetry or TelemetrySpec()
    spec = dataclasses.replace(
        spec, telemetry=dataclasses.replace(tel, enabled=True)
    )
    result = spec.build(engine).run()
    trace_dir.mkdir(parents=True, exist_ok=True)
    written = [
        result.trace.to_jsonl(trace_dir / f"TRACE_{spec.name}.jsonl"),
        result.trace.to_chrome(
            trace_dir / f"TRACE_{spec.name}.chrome.json"
        ),
    ]
    if result.metrics is not None:
        written.append(
            result.metrics.to_json(trace_dir / f"METRICS_{spec.name}.json")
        )
    for p in written:
        if p is not None:
            print(f"trace: wrote {p}", flush=True)
    if engine == "time":
        hedged = result.stats.hedge_cancelled_bytes if result.stats else 0.0
    else:
        hedged = sum(
            o.raw.hedge_cancelled_bytes for o in result.outcomes.values()
        )
    checker = TraceChecker(result.trace)
    violations = checker.check(hedge_cancelled_bytes=hedged)
    for origin, summary in checker.failover_summary().items():
        print(
            f"trace: {origin} failed@{summary['failed_at']:.0f} "
            f"failovers={summary['failovers']} "
            f"requests_after_fail={summary['requests_after_fail']}",
            flush=True,
        )
    print(
        f"trace: {len(result.trace.events)} events, "
        f"{len(violations)} invariant violation(s)", flush=True,
    )
    if violations:
        for v in violations:
            print(f"VIOLATION {v}", flush=True)
        raise SystemExit(f"{len(violations)} trace invariant violation(s)")


def run_generic_scenario(path: Path, engine: str, report,
                         profile: bool = False) -> None:
    """Run one scenario file that no bench claims: one row per torrent,
    plus the fairness row for multi-torrent scenarios. ``profile`` adds
    the fleet engine's per-phase wall breakdown."""
    from repro.core import ScenarioSpec

    spec = ScenarioSpec.load(path)
    t0 = time.perf_counter()
    result = spec.build(engine).run()
    wall = (time.perf_counter() - t0) * 1e6
    if profile and engine == "fleet":
        phases = next(iter(result.outcomes.values())).raw.phase_seconds
        total = max(sum(phases.values()), 1e-12)
        print("profile: fleet phase breakdown "
              + " ".join(f"{k}={v:.2f}s({v / total * 100:.0f}%)"
                         for k, v in sorted(phases.items())),
              flush=True)
    unit = "rounds" if engine == "byte" else "s"
    for name, out in result.outcomes.items():
        size = next(
            m.size_bytes for m in spec.content.manifests if m.name == name
        )
        pct = out.completion_percentiles
        report(
            f"scenario/{spec.name}/{name}", wall,
            f"done={out.completed}/{out.clients} "
            f"t={out.duration:.0f}{unit} "
            f"origin={out.origin_uploaded / size:.2f}copies "
            f"ud={out.ud_ratio:.1f}"
            + (f" p99={pct['p99']:.0f}{unit}" if pct else ""),
        )
    if result.jain_fairness is not None:
        report(
            f"scenario/{spec.name}/fairness", 0.0,
            f"jain={result.jain_fairness:.3f}",
        )

# every float in a derived string, sign/decimal/exponent included
_NUM_RE = re.compile(r"-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?")
_LABEL_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def _labeled_metrics(derived: str) -> list[tuple[str, float]]:
    """(label, value) pairs for every number in a ``derived`` string.

    Positional extraction is unchanged from the raw ``_NUM_RE`` scan (the
    metric *count* is what baselines pin); the label is the last
    identifier-ish token before each number — ``"done=12/14 ud=3.1"``
    yields ``[("done", 12), ("done#2", 14), ("ud", 3.1)]`` — so a diff can
    name the diverging metric instead of reporting a bare float."""
    out: list[tuple[str, float]] = []
    seen: dict[str, int] = {}
    label = "value"
    last = 0
    for m in _NUM_RE.finditer(derived):
        words = _LABEL_RE.findall(derived, last, m.start())
        if words:
            label = words[-1]
        last = m.end()
        n = seen.get(label, 0) + 1
        seen[label] = n
        out.append((label if n == 1 else f"{label}#{n}", float(m.group())))
    return out


def compare_rows(
    baseline: dict, fresh_rows: list[dict], tolerance: float
) -> list[str]:
    """Regressions of ``fresh_rows`` against a committed baseline file.

    Every baseline row must exist in the fresh run, carry the same number
    of metrics in its ``derived`` string, and match each metric within
    ``tolerance`` relative error (new rows in the fresh run are fine —
    they become baselines when committed). Returns human-readable problem
    strings — each naming the diverging metric with its expected and
    actual values — empty when the run is clean.
    """
    problems: list[str] = []
    if baseline.get("failed"):
        return problems  # a failed baseline pins nothing
    fresh = {r["name"]: r["derived"] for r in fresh_rows}
    for row in baseline.get("rows", []):
        name, want = row["name"], row["derived"]
        if name not in fresh:
            problems.append(f"{name}: row missing from fresh run")
            continue
        got = fresh[name]
        want_metrics = _labeled_metrics(want)
        got_metrics = _labeled_metrics(got)
        if len(want_metrics) != len(got_metrics):
            problems.append(
                f"{name}: metric count changed ({want!r} -> {got!r})"
            )
            continue
        for (label, w), (_, g) in zip(want_metrics, got_metrics):
            scale = max(abs(w), abs(g), 1e-12)
            if abs(w - g) / scale > tolerance:
                problems.append(
                    f"{name}: metric {label!r} diverged — expected {w:g}, "
                    f"got {g:g} (rel err {abs(w - g) / scale:.3f} > "
                    f"{tolerance}); baseline {want!r} vs fresh {got!r}"
                )
                break
    return problems


def maybe_profile(enabled: bool, label: str, fn):
    """Run ``fn`` (optionally under cProfile, dumping the top of the
    cumulative-time table) and return its result."""
    if not enabled:
        return fn()
    import cProfile
    import pstats

    prof = cProfile.Profile()
    prof.enable()
    try:
        return fn()
    finally:
        prof.disable()
        print(f"--- profile[{label}] top 15 by cumulative time ---",
              flush=True)
        pstats.Stats(prof).sort_stats("cumulative").print_stats(15)


def bench_file_name(key: str) -> str:
    """BENCH_<module>.json, module name sans the ``bench_`` prefix."""
    mod = SUITES[key].__name__.rsplit(".", 1)[-1]
    return f"BENCH_{mod.removeprefix('bench_')}.json"


def write_json(
    json_dir: Path, key: str, rows: list[dict], wall_s: float,
    error: str | None,
) -> Path:
    path = json_dir / bench_file_name(key)
    path.write_text(json.dumps({
        "bench": key,
        "wall_s": round(wall_s, 3),
        "failed": error is not None,
        **({"error": error} if error else {}),
        "rows": rows,
    }, indent=1) + "\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="persist each bench's rows as DIR/BENCH_<name>.json")
    ap.add_argument("--compare", default=None, metavar="DIR",
                    help="diff fresh rows against DIR/BENCH_<name>.json "
                         "baselines; exit non-zero on metric regressions")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative tolerance for --compare (default 0.05)")
    ap.add_argument("--scenario", default=None, metavar="FILE",
                    help="run a ScenarioSpec JSON: a registered bench's "
                         "base file runs that whole bench seeded from it; "
                         "any other file runs generically")
    ap.add_argument("--engine", default="time",
                    choices=["time", "byte", "fleet"],
                    help="engine for generic --scenario runs")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each selected bench (or the generic "
                         "--scenario run) and dump the top functions by "
                         "cumulative time")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="flight-recorder run of --scenario: export "
                         "TRACE_/METRICS_ artifacts under DIR and replay "
                         "the invariant checker (exit non-zero on any "
                         "violation)")
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmarks + scenario files")
    args = ap.parse_args()
    if args.list:
        list_benches()
        return
    from repro.accel import enable_compile_cache

    enable_compile_cache()
    scenario_path = Path(args.scenario).resolve() if args.scenario else None
    if args.trace is not None:
        if scenario_path is None:
            raise SystemExit("--trace needs --scenario FILE")
        run_traced_scenario(scenario_path, args.engine, Path(args.trace))
        return
    chosen = DEFAULT_SUITES if not args.only else args.only.split(",")
    if scenario_path is not None:
        # exact-path match only: a user file that merely shares a committed
        # scenario's basename must run generically, not trip the owning
        # bench's golden assertions
        owners = [
            key for key in SUITES
            if scenario_file(key) is not None
            and scenario_file(key).resolve() == scenario_path
        ]
        chosen = owners  # empty => generic run below
        if not owners and (args.json or args.compare):
            raise SystemExit(
                f"--json/--compare need a registered bench scenario; "
                f"{scenario_path} is not one (see --list). Generic runs "
                "have no BENCH_* baseline to write or diff."
            )
    json_dir = Path(args.json) if args.json else None
    if json_dir is not None:
        json_dir.mkdir(parents=True, exist_ok=True)
    compare_dir = Path(args.compare) if args.compare else None

    rows: list[str] = []

    def report(name: str, us: float, derived: str) -> None:
        # sub-100µs values keep decimals: the fleet scaling rows report
        # µs/client-tick here, where integer resolution would erase the
        # headline metric (wall times are unaffected by the rounding mode)
        us_txt = f"{us:.0f}" if us >= 100 else f"{us:.3f}"
        line = f"{name},{us_txt},{derived}"
        rows.append(line)
        print(line, flush=True)
        suite_rows.append(
            {"name": name,
             "us_per_call": round(us) if us >= 100 else round(us, 3),
             "derived": derived}
        )

    print("name,us_per_call,derived")
    measured_ud = None
    failures = []
    regressions: list[str] = []
    if scenario_path is not None and not chosen:
        # no bench claims this file: run the scenario itself
        suite_rows: list[dict] = []
        maybe_profile(
            args.profile, scenario_path.stem,
            lambda: run_generic_scenario(
                scenario_path, args.engine, report, profile=args.profile
            ),
        )
        return
    for key in chosen:
        mod = SUITES[key]
        suite_rows: list[dict] = []
        error = None
        t0 = time.perf_counter()
        try:
            if scenario_path is not None:
                maybe_profile(
                    args.profile, key,
                    lambda: mod.main(report, scenario=scenario_path),
                )
            elif key == "eq1":
                measured_ud, _ = maybe_profile(
                    args.profile, key, lambda: mod.main(report)
                )
            elif key == "table1":
                maybe_profile(
                    args.profile, key,
                    lambda: mod.main(report, measured_ud=measured_ud),
                )
            else:
                maybe_profile(args.profile, key, lambda: mod.main(report))
        except Exception as e:  # keep the harness running; record the failure
            error = repr(e)
            failures.append((key, error))
            report(f"{key}/FAILED", (time.perf_counter() - t0) * 1e6, error[:120])
        if json_dir is not None:
            write_json(
                json_dir, key, suite_rows, time.perf_counter() - t0, error
            )
        if compare_dir is not None and error is None:
            base_path = compare_dir / bench_file_name(key)
            if base_path.exists():
                found = compare_rows(
                    json.loads(base_path.read_text()), suite_rows,
                    args.tolerance,
                )
                for p in found:
                    print(f"REGRESSION[{key}] {p}", flush=True)
                regressions.extend(f"{key}: {p}" for p in found)
            else:
                print(f"compare: no baseline {base_path}, skipped", flush=True)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    if regressions:
        raise SystemExit(
            f"{len(regressions)} metric regression(s) vs baselines in "
            f"{compare_dir}"
        )


if __name__ == "__main__":
    main()
