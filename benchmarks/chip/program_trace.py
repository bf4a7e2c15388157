"""The program's own spans in the profile of a traced run.

The fleet engine marks its host work as ``fleet.*`` spans on the
profiler's clock (``repro.core.fleet``'s docstring lists them; the water-
fill's carries its fixed-point ``rounds``). This module reads them back
for the per-layer metrics that rest on them.

``LayerContext`` carries the reduction of the trace but not its path, so
:func:`for_run` searches ``.trace/`` beside this file for the newest
``*.xplane.pb``: the profile the harness's ``Tracer`` wrote for this run,
which ``run_cell`` removes only after the readers. The result is cached
per file (path and time written), so that the readers of one run parse
the profile once.

Two steps, as in ``trace_reduce``, so that the arithmetic can be checked
on a small recorded trace:

- :func:`load` keeps ``trace_reduce.load_dir``'s record (device
  operations, module runs, the ``bench.window`` span; its ``_merge``,
  ``_clip`` and ``_covering`` do the interval work) and adds one pass
  of its own over the host events named ``fleet.*``, with their numeric
  stats, as ``program_spans``: ``[name, start, duration, {stat: value}]``;
- :func:`reduce` gives, for the spans that start inside the window:
  per span name its ``count``, ``host_s`` (the union of its host time,
  clipped to the window) and ``meta`` (the sums of its numeric metadata);
  and, where the trace has device planes, ``idle_s``: the device's idle
  seconds by the innermost ``fleet.*`` span that covers the host at the
  gap's middle, ``outside`` where none does, and
  ``idle_outside_ticks_s``: the idle seconds in which the host was in no
  ``fleet.tick`` span at the gap's middle. The middle is read as it is:
  the gaps here last milliseconds (a selection's picks take 2-3 ms to
  cross back after its program ends), the clocks' skew under one, and a
  shift by ``trace_reduce.SKEW_NS`` would hand a selection's gap to the
  short resampling span that follows it.

All times are nanoseconds on the profiler's one clock; results are
seconds.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

from harness import HERE, load_module

tr = load_module(HERE / "trace_reduce.py")

SPAN_PREFIX = "fleet."
TICK_SPAN = "fleet.tick"
OUTSIDE = "outside"


def newest_profile() -> Path | None:
    files = list((HERE / ".trace").glob("**/plugins/profile/*/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime) if files else None


def load(path: Path) -> dict:
    """``trace_reduce``'s record of the profile at ``path``, with the
    program's spans added."""
    from jax.profiler import ProfileData

    record = tr.load_dir(Path(path).parents[3])
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    meta = {k: v for k, v in ev.stats
                            if isinstance(v, (int, float))
                            and not isinstance(v, bool)}
                    spans.append([ev.name, float(ev.start_ns),
                                  float(ev.duration_ns), meta])
    record["program_spans"] = spans
    return record


def _innermost(t: float, span_iv: dict) -> str:
    best = None
    for name, (ivs, starts) in span_iv.items():
        hit = tr._covering(t, ivs, starts)
        if hit is not None and (best is None or hit[1] - hit[0] < best[1]):
            best = (name, hit[1] - hit[0])
    return best[0] if best else OUTSIDE


def reduce(record: dict, n_devices: int) -> dict | None:
    """The figures of the module docstring; None without a window span."""
    win = [s for s in record["spans"] if s[0] == tr.WINDOW_SPAN]
    if not win:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    spans: dict[str, dict] = {}
    inside: dict[str, list] = {}
    span_iv: dict[str, tuple] = {}
    for name, s, d, meta in record["program_spans"]:
        span_iv.setdefault(name, []).append([s, s + d])
        if not lo <= s < hi:
            continue
        out = spans.setdefault(name, {"count": 0, "host_s": 0.0, "meta": {}})
        out["count"] += 1
        for key, value in meta.items():
            out["meta"][key] = out["meta"].get(key, 0) + value
        inside.setdefault(name, []).append([s, s + d])
    for name, ivs in inside.items():
        spans[name]["host_s"] = 1e-9 * sum(
            e - s for s, e in tr._merge(tr._clip(ivs, lo, hi)))
    for name in span_iv:
        ivs = tr._merge(span_iv[name])
        span_iv[name] = (ivs, [a for a, _ in ivs])
    result = {"window_s": (hi - lo) * 1e-9, "spans": spans, "idle_s": None,
              "idle_outside_ticks_s": None}
    devs = sorted(record["devices"].items(), key=lambda kv: int(kv[0]))
    devs = [ops for _, ops in devs[:n_devices] if ops]
    if not devs:
        return result
    ticks = {TICK_SPAN: span_iv[TICK_SPAN]} if TICK_SPAN in span_iv else {}
    idle: dict[str, float] = {}
    outside_ticks = 0.0
    for ops in devs:
        busy = tr._merge(tr._clip([[o[2], o[2] + o[3]] for o in ops], lo, hi))
        prev = lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                mid = (prev + s) / 2
                label = _innermost(mid, span_iv)
                idle[label] = idle.get(label, 0.0) + (s - prev)
                if _innermost(mid, ticks) == OUTSIDE:
                    outside_ticks += s - prev
            prev = max(prev, e)
    sec = 1e-9 / len(devs)
    result["idle_s"] = {k: v * sec for k, v in idle.items()}
    result["idle_outside_ticks_s"] = outside_ticks * sec
    return result


@functools.lru_cache(maxsize=4)
def _summary(path: str, mtime_ns: int, n_devices: int) -> dict | None:
    red = reduce(load(Path(path)), n_devices)
    if red is not None:
        line = {"window_s": red["window_s"],
                "count": {k: v["count"] for k, v in red["spans"].items()},
                "host_s": {k: v["host_s"] for k, v in red["spans"].items()},
                "idle_s": red["idle_s"]}
        print("program trace: " + json.dumps(line), file=sys.stderr,
              flush=True)
    return red


def for_run(ctx) -> dict | None:
    """The reduction of the profile this run wrote (None when there is
    none, or it has no window span), over the devices the harness's own
    reduction counted."""
    path = newest_profile()
    if path is None:
        return None
    n_devices = ctx.reduction["devices"] if ctx.reduction else 0
    return _summary(str(path), path.stat().st_mtime_ns, n_devices)
