"""The chip benchmark's harness: one cell, one run, one result line.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``mixes/<mix>.json``); the
mix names its window driver (``drivers/<driver>.py``); each per-layer
metric is a reader of its own (``layer_metrics/<metric>.py``). Which
metrics a cell reports comes from ``BENCHMARK.json`` at the checkout's
root. Everything is found by name, so a new cell, configuration, mix or
metric is new files and new entries, never an edit.

A driver module provides

- ``setup(cell) -> state``: build, warm every shape the window uses;
- ``window(state, cell, tracer) -> dict``: measure for ``cell.seconds``;
  returns ``e2e`` (end-to-end values by metric name), ``attempted``,
  ``failed``, ``calls`` (layer calls seen while tracing) and ``phases``
  (the program's phase seconds while tracing);
- ``finish(state) -> evidence``: copy what the check needs to the host and
  drop every reference to the program's device state;
- ``check(evidence, cell) -> [(name, value, limit), ...]``: the comparison
  with the plain reference; the run is correct when no value exceeds its
  limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric names carry dots, so no package
    import can reach them)."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one run knows about its cell."""

    name: str
    workload: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    fault: str | None = None
    overrides: dict | None = None  # test-only scale-down of the config

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def resolve(name: str) -> tuple[dict, dict, dict]:
    """Workload, configuration and mix files of cell ``name``."""
    wl = load_json(HERE / "workloads" / f"{name}.json")
    cfg = load_json(HERE / "configs" / f"{wl['config']}.json")
    mix = load_json(HERE / "mixes" / f"{wl['traffic']}.json")
    return wl, cfg, mix


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer entries of ``BENCHMARK.json`` that
    cell ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in names and cell in m.get("workloads", [cell])]
    return e2e, layer


# --------------------------------------------------------------------------- compiles


class CompileCounter:
    """Backend compile events with the host time at which each ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.events: list[tuple[float, str, float]] = []

    def __call__(self, event, secs, fun_name="?", **_):
        if event == self.EVENT:
            self.events.append((time.perf_counter(), fun_name, secs))

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[0] <= t1]


# --------------------------------------------------------------------------- tracing


class Tracer:
    """Starts and stops the JAX profiler between the window's chunks.

    Drivers call :meth:`poll` at every chunk boundary; with ``--trace 1``
    the profiler covers the first ``trace_seconds`` of the window (a mix
    parameter). ``annotate(name)`` marks a host span in the trace."""

    def __init__(self, enabled: bool, seconds: float, log_dir: Path) -> None:
        self.enabled = enabled
        self.seconds = seconds
        self.log_dir = log_dir
        self.t_start: float | None = None
        self.t_stop: float | None = None
        self._span = None

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def poll(self, now: float) -> bool:
        """Start or stop at a chunk boundary; True when a change was made
        (the driver then snapshots its counters)."""
        if not self.enabled:
            return False
        import jax

        if self.t_start is None:
            shutil.rmtree(self.log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.t_start = time.perf_counter()
            return True
        if self.active and now - self.t_start >= self.seconds:
            self.stop()
            return True
        return False

    def stop(self) -> None:
        if self.active:
            import jax

            self.t_stop = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def annotate(self, name: str):
        if self.active:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return _NULL_CTX


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


# --------------------------------------------------------------------------- devices


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       "in peaks.json")
    return table[kind]


# --------------------------------------------------------------------------- one run


def run_cell(cell: Cell, *, t_process: float, require_tpu: bool = True,
             bench: dict | None = None) -> dict:
    """Set up, measure, check; returns the result object. Raises where JAX
    finds no TPU or fewer chips than the cell asks for (unless a test
    turns the look off)."""
    import jax

    if bench is None:
        bench = load_json(ROOT / "BENCHMARK.json")
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise SystemExit(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < cell.chips:
            raise SystemExit(f"cell needs {cell.chips} chips, JAX found "
                             f"{len(devs)}")
    from repro.accel import enable_compile_cache

    enable_compile_cache()
    # keep every program, however quick its compile, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    driver = load_module(HERE / "drivers" / f"{cell.mix['driver']}.py")
    e2e_specs, layer_specs = cell_metrics(bench, cell.name)

    state = driver.setup(cell)
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    tracer = Tracer(cell.trace, float(cell.mix.get("trace_seconds", 5)),
                    HERE / ".trace" / cell.name)
    out = driver.window(state, cell, tracer)
    tracer.stop()
    t_end = time.perf_counter()
    peak = memory_peak(cell.chips)
    evidence = driver.finish(state)
    del state
    gc.collect()
    checks = driver.check(evidence, cell)
    correct = all(_within(v, lim) for _, v, lim in checks)

    info = device_info(cell.chips)
    info["memory_peak_bytes"] = peak
    metrics: dict = {}
    breakdown = None
    if not cell.trace:
        values = dict(out["e2e"], setup_s=setup_s)
        for spec in e2e_specs:
            if spec["name"] not in values:
                raise RuntimeError(f"driver gave no {spec['name']}")
            metrics[spec["name"]] = {"value": values[spec["name"]],
                                     "unit": spec["unit"]}
    else:
        trace_reduce = load_module(HERE / "trace_reduce.py")
        red = trace_reduce.reduce_dir(tracer.log_dir, cell.chips)
        if red is not None:
            print("trace: " + json.dumps({k: red[k] for k in (
                "window_s", "busy_s", "modules", "in_span")}),
                file=sys.stderr, flush=True)
        window_s = (tracer.t_stop or t_end) - (tracer.t_start or t_window)
        ctx = LayerContext(
            reduction=red, calls=out.get("calls", []),
            phases=out.get("phases", {}), window_s=window_s,
            compiles_in_window=len(compiles.between(t_window, t_end)),
            peaks=peaks_for(info["kind"]) if info["platform"] == "tpu"
            else None,
            work_counts=load_module(HERE / "work_counts.py"),
        )
        for spec in layer_specs:
            reader = load_module(HERE / "layer_metrics" / f"{spec['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        if red is not None:
            info["busy_s"] = red["busy_s"]
            info["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["top_ops"][:10],
                         "idle_gaps": red["idle_gaps"][:10]}
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    print(f"window compiles={len(compiles.between(t_window, t_end))} "
          f"names={[e[1] for e in compiles.between(t_window, t_end)]}",
          file=sys.stderr, flush=True)
    result = {
        "correct": correct,
        "attempted": int(out["attempted"]),
        "failed": int(out["failed"]) + sum(
            not _within(v, lim) for _, v, lim in checks),
        "metrics": metrics,
        "device": info,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def _within(value, limit) -> bool:
    return value is not None and math.isfinite(value) and value <= limit


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read."""

    reduction: dict | None   # trace_reduce.reduce_dir(...)
    calls: list              # layer calls made while the profiler ran
    phases: dict             # program phase seconds while the profiler ran
    window_s: float          # host seconds the profiler ran
    compiles_in_window: int
    peaks: dict | None       # peaks.json row of this device kind
    work_counts: object      # the work_counts module


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="plant a named fault or run the control (checks "
                         "of the comparison; never part of a measured run)")
    args = ap.parse_args(argv)
    wl, cfg, mix = resolve(args.workload)
    cell = Cell(args.workload, wl, cfg, mix, args.seed, args.seconds,
                bool(args.trace), args.fault)
    result = run_cell(cell, t_process=t_process)
    print_result(result)
    return 0
