"""From a JAX profiler trace to busy and idle time, device time per
operation, and the longest idle gaps by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded
trace without the profiler:

- :func:`load_dir` reads the ``.xplane.pb`` the profiler wrote and keeps a
  plain, JSON-serialisable record: every operation on each device
  (``XLA Ops`` lines of the ``/device:TPU:<i>`` planes) and every
  benchmark span (host events named ``bench.*``);
- :func:`reduce` computes the figures from that record.

All times are nanoseconds on the profiler's one clock; results are seconds.
"""

from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the device's clock can run up to about a millisecond ahead of the host's
# in one trace: an operation counts for a span it starts within this of
SKEW_NS = 1e6


def load_dir(log_dir: Path) -> dict | None:
    """The record of the newest trace under ``log_dir``, or None when the
    profiler wrote none. Operations are ``[name, module, start, duration]``
    (the HLO instruction's short name; its module where the event says),
    module runs ``[name, start, duration]``, spans ``[name, start,
    duration]``."""
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        return None
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(files[-1])
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices.setdefault(m.group(1), [])
                for ev in line.events:
                    stats = dict(ev.stats)
                    name = str(stats.get("hlo_op") or ev.name)
                    ops.append([name.split(" = ")[0].lstrip("%"),
                                str(stats.get("hlo_module", "")),
                                float(ev.start_ns), float(ev.duration_ns)])
            elif m and line.name == MODULES_LINE:
                runs = modules.setdefault(m.group(1), [])
                for ev in line.events:
                    runs.append([ev.name.split("(")[0], float(ev.start_ns),
                                 float(ev.duration_ns)])
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    return {"devices": devices, "modules": modules, "spans": spans}


def _merge(intervals: list) -> list:
    """Union of ``[start, end]`` intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _covering(t: float, ivs: list, starts: list):
    """The merged interval of ``ivs`` (sorted, with their ``starts``)
    that holds ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < ivs[i][1]:
        return ivs[i]
    return None


def _label(t: float, span_iv: dict) -> str:
    """The innermost benchmark span (other than the window) covering host
    time ``t``; ``host`` where the host was in none of them."""
    best = None
    for kind, (ivs, starts) in span_iv.items():
        hit = _covering(t, ivs, starts)
        if hit is not None and (best is None or hit[1] - hit[0] < best[1]):
            best = (kind, hit[1] - hit[0])
    return best[0] if best else "host"


def _exclusive(ops: list) -> list:
    """Each operation's own time: its duration less that of the
    operations nested in it (a loop's body runs inside the loop's event).
    ``ops`` are sorted by start."""
    own = [o[3] for o in ops]
    stack: list = []  # (end, index) of the enclosing operations
    for i, o in enumerate(ops):
        while stack and stack[-1][0] <= o[2]:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= o[3]
        stack.append((o[2] + o[3], i))
    return own


def _module_of(t: float, runs: list, starts: list) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= runs[i][1] + runs[i][2]:
        return runs[i][0]
    return ""


def reduce(record: dict, n_devices: int) -> dict:
    """Busy and window seconds (averaged over devices), per-op and
    per-module device seconds (own time, nesting removed), device seconds
    inside each kind of benchmark span, and idle seconds by host activity.

    The window is the ``bench.window`` span where the record has one,
    else the extent of all events."""
    spans = record["spans"]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    devs = sorted(record["devices"].items(), key=lambda kv: int(kv[0]))
    devs = [(k, ops) for k, ops in devs[:n_devices] if ops]
    if not devs:
        return None
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(o[2] for _, ops in devs for o in ops)
        hi = max(o[2] + o[3] for _, ops in devs for o in ops)
    busy_total = 0.0
    by_op: dict[str, float] = {}
    by_module: dict[str, float] = {}
    gaps: dict[str, float] = {}
    in_span: dict[str, float] = {}
    span_iv = {}
    for name, s, d in spans:
        if name != WINDOW_SPAN:
            span_iv.setdefault(name[len(SPAN_PREFIX):], []).append([s, s + d])
    for kind in span_iv:
        ivs = _merge(span_iv[kind])
        span_iv[kind] = (ivs, [a for a, _ in ivs])
    for dev, ops in devs:
        ops = sorted(ops, key=lambda o: (o[2], -o[3]))
        runs = sorted(record.get("modules", {}).get(dev, []),
                      key=lambda r: r[1])
        run_starts = [r[1] for r in runs]
        iv = _clip([[o[2], o[2] + o[3]] for o in ops], lo, hi)
        merged = _merge(iv)
        busy_total += sum(e - s for s, e in merged)
        spanned: dict[str, list] = {}
        for (name, module, s, d), own in zip(ops, _exclusive(ops)):
            if s + d <= lo or s >= hi:
                continue
            module = module or _module_of(s, runs, run_starts)
            key = f"{module}/{name}" if module else name
            by_op[key] = by_op.get(key, 0.0) + own
            by_module[module] = by_module.get(module, 0.0) + own
            for kind, (ivs, starts) in span_iv.items():
                if (_covering(s, ivs, starts) is not None
                        or _covering(s + SKEW_NS, ivs, starts) is not None):
                    spanned.setdefault(kind, []).append([s, s + d])
        for kind, ivs in spanned.items():
            in_span[kind] = in_span.get(kind, 0.0) + sum(
                e - s for s, e in _merge(_clip(ivs, lo, hi)))
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                lab = _label((prev + s) / 2, span_iv)
                gaps[lab] = gaps.get(lab, 0.0) + (s - prev)
            prev = max(prev, e)
    by_program = _program_time(record, devs, span_iv, lo, hi)
    nd = max(1, len(devs))
    sec = 1e-9 / nd
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * sec,
        "devices": len(devs),
        "top_ops": sorted(([k, v * sec] for k, v in by_op.items()),
                          key=lambda kv: -kv[1]),
        "modules": {k: v * sec for k, v in by_module.items()},
        # device seconds of each layer: by whole programs where the trace
        # has module runs, else by the operations inside the layer's spans
        "in_span": {k: v * sec for k, v in (by_program or in_span).items()},
        "idle_gaps": sorted(([k, v * sec] for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
    }


def _program_time(record, devs, span_iv, lo, hi) -> dict:
    """Device time of each kind of benchmark span, counted by whole
    programs: each program (module name) goes to the span kind in which
    most of its runs start (read at the run's start and ``SKEW_NS``
    later), so that a run the clocks place just before its span still
    counts, and a program that runs asynchronously after its caller's
    span does not."""
    votes: dict[str, dict] = {}
    runs_by_dev = record.get("modules", {})
    for dev, _ in devs:
        for name, s, d in runs_by_dev.get(dev, []):
            for t in (s, s + SKEW_NS):
                kind = _label(t, span_iv)
                votes.setdefault(name, {}).setdefault(kind, 0)
                votes[name][kind] += 1
    # ties go to the more specific kind: the one whose spans cover less
    extent = {kind: sum(b - a for a, b in ivs)
              for kind, (ivs, _) in span_iv.items()}
    owner = {name: max(v, key=lambda k: (v[k], -extent.get(k, float("inf"))))
             for name, v in votes.items()}
    out: dict[str, float] = {}
    for dev, _ in devs:
        for name, s, d in runs_by_dev.get(dev, []):
            kind = owner[name]
            if kind in span_iv:
                out[kind] = out.get(kind, 0.0) + max(
                    0.0, min(s + d, hi) - max(s, lo))
    return out


def reduce_dir(log_dir: Path, n_devices: int) -> dict | None:
    record = load_dir(log_dir)
    return None if record is None else reduce(record, n_devices)
