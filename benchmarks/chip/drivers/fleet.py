"""Window driver for the fleet engine: ``FleetSwarmSim.run`` on
``backend="pallas"``, as ``ScenarioSpec.build("fleet")`` builds it.

Mix parameters (``mixes/<mix>.json``):

- ``start_at``: simulated second up to which set-up runs the
  configuration's scenario, with the configuration's own seeds, so that
  every run starts its window from the same state.
- The window runs one engine tick per ``run(until=now + dt)`` call and
  ends at the first tick boundary after ``--seconds``. ``--seed`` seeds
  the engine's generator (source sampling, choking) at the window's
  start, and draws what the check keeps: ``REPLAYED_TICKS`` of the
  window's first ``REPLAY_SPAN`` ticks, whose state is copied before and
  after them, and water-fill calls (the first always, then each with
  chance ``WATERFILL_P``, at most ``WATERFILL_MOST``).
- ``trace_seconds``: how long the profiler runs in a ``--trace 1`` run.

The window's calls into selection and water-filling go through wrappers
on the run's ``FleetDeviceState`` (bound methods ``select`` and
``waterfill``) that time them, mark them in the trace, and keep what the
check needs.
"""

from __future__ import annotations

import copy
import time
from pathlib import Path

import numpy as np

CHIP = Path(__file__).resolve().parents[1]

# what the check keeps: few enough that copying the (n, P) have matrix and
# the reference's replay stay short beside the window
REPLAYED_TICKS, REPLAY_SPAN = 1, 4
WATERFILL_P, WATERFILL_MOST = 0.25, 3
# what a tick replay reads of the engine before the tick, and compares after
TICK_STATE = ("have", "cur_http", "cur_swarm", "prog_http", "prog_swarm",
              "nhave", "downloaded", "mirror_uploaded", "completed_at",
              "departed")


# --------------------------------------------------------------------------- building


def scenario(cell) -> dict:
    spec = copy.deepcopy(cell.config["scenario"])
    for key, value in (cell.overrides or {}).items():
        if key == "n":
            spec["arrivals"][0]["n"] = value
        else:  # manifest sizes
            spec["content"]["manifests"][0][key] = value
    return spec


def build(spec: dict):
    from repro.core.scenario import ScenarioSpec

    sim = ScenarioSpec.from_dict(spec).build("fleet").sim
    sim.run(until=0.0)  # freeze: arrays and device state, no tick
    return sim


def _pow2_buckets(lo: int, top: int):
    """Powers of two from the one at or below ``lo`` up to the one at or
    above ``top``."""
    b = 1 << max(0, int(lo).bit_length() - 1)
    while True:
        yield b
        if b >= top:
            return
        b <<= 1


def warm(sim, flows_now: int) -> None:
    """Run every padded select, scatter and water-fill shape the window
    can reach once, through the device state's own entry points, leaving
    the state as it was. Selections and completions reach any row count
    up to the crowd; flow tables reach from a quarter of the last
    set-up tick's up to every leecher's full fan-out."""
    dev = sim.device
    n, P = dev.n, dev.P
    pol = sim.policy
    stats = (dict(dev.waterfill_runs), dev.peak_flows, dev.rounds)
    for k in _pow2_buckets(128, n):
        rows = np.zeros(k, dtype=np.int64)
        other = np.full(k, -1, dtype=np.int64)
        for stream in ("http", "swarm"):
            dev.select(rows, other, stream=stream, mode=pol.mode,
                       fallback=pol.http_fallback)
    for k in _pow2_buckets(8, n):
        oob = np.full(k, n, dtype=np.int64)  # dropped: no state changes
        dev.add_pieces(oob, np.full(k, P, dtype=np.int64))
    M = len(sim.mirror_specs)
    up = np.concatenate([sim.up_bps, [m.up_bps for m in sim.mirror_specs]])
    down = np.concatenate([sim.down_bps, np.full(M, np.inf)])
    most = (n * sim.fanout * sim.swarm_cfg.per_peer_requests
            + int(sim._mirror_caps().sum()))
    for nf in _pow2_buckets(max(128, flows_now // 4), most):
        src = np.arange(nf, dtype=np.int64) % n
        dev.waterfill(src, (src + 1) % n, up, down, None, None)
    dev.waterfill_runs, dev.peak_flows, dev.rounds = stats


# --------------------------------------------------------------------------- probes


class Log:
    """Calls seen in the window: their shapes while tracing, and what the
    check keeps (sampled water-fill calls, the replayed ticks)."""

    def __init__(self, seed: int, replayed: int = REPLAYED_TICKS,
                 span: int = REPLAY_SPAN) -> None:
        self.rng = np.random.default_rng([seed, 0x5EED])
        chosen = self.rng.choice(span, size=min(replayed, span),
                                 replace=False)
        self.check_ticks = {int(k) for k in chosen}
        self.calls: list = []
        self.waterfills: list = []
        self.ticks: list = []
        self.tick: dict | None = None  # the replayed tick being recorded

    def keep_waterfill(self) -> bool:
        draw = self.rng.random()
        return len(self.waterfills) < WATERFILL_MOST and (
            not self.waterfills or draw < WATERFILL_P)


def probe(sim, log: Log, tracer, fault: str | None) -> None:
    """Wrap the sim's device-state methods (see the module docstring).
    ``fault`` plants a named fault or the control in the wrapped call."""
    dev = sim.device
    select, waterfill = dev.select, dev.waterfill

    def timed_select(rows, other, *, stream, mode, fallback):
        t0 = time.perf_counter()
        with tracer.annotate("bench.select"):
            pick = select(rows, other, stream=stream, mode=mode,
                          fallback=fallback)
        t1 = time.perf_counter()
        if fault == "half_batch":
            pick = pick.copy()
            pick[rows.size // 2:] = -1
        if tracer.active:
            log.calls.append(("select", t0, t1, int(rows.size), dev.P))
        return pick

    def timed_waterfill(src, dst, up_cap, down_cap, link_of, link_cap):
        t0 = time.perf_counter()
        with tracer.annotate("bench.waterfill"):
            if fault == "control":
                rates = _control_fill(src, dst, up_cap, down_cap, link_of,
                                      link_cap)
            else:
                rates = waterfill(src, dst, up_cap, down_cap, link_of,
                                  link_cap)
        t1 = time.perf_counter()
        if fault == "answer_altered" and rates.size:
            rates = rates.copy()
            rates[rates.size // 2] *= 1.01
        if tracer.active:
            log.calls.append(("waterfill", t0, t1, int(np.size(src)),
                              int(np.size(up_cap)),
                              0 if link_of is None else int(np.size(link_cap))))
        record = None
        if log.keep_waterfill():
            record = {
                "src": np.array(src), "dst": np.array(dst),
                "up": np.array(up_cap), "down": np.array(down_cap),
                "link_of": None if link_of is None else np.array(link_of),
                "link_cap": None if link_cap is None else np.array(link_cap),
                "rates": np.array(rates),
            }
            log.waterfills.append(record)
        if log.tick is not None:
            log.tick["flows"] = record or {"src": np.array(src),
                                           "dst": np.array(dst),
                                           "rates": np.array(rates)}
        if fault == "progress_scaled":  # bookkeeping credits 1% too much
            rates = rates * 1.01
        return rates

    dev.select = timed_select
    dev.waterfill = timed_waterfill
    if fault == "state_unchanged":
        dev.add_pieces = lambda rows, pieces: None


def _control_fill(src, dst, up_cap, down_cap, link_of, link_cap):
    """The control: the reference water-fill in bfloat16, in the program's
    place."""
    import ml_dtypes

    from harness import load_module

    ref = load_module(CHIP / "references" / "fleet.py")
    return ref.waterfill(src, dst, up_cap, down_cap, link_of, link_cap,
                         dtype=ml_dtypes.bfloat16).astype(np.float64)


def _tick_state(sim) -> dict:
    return {key: np.array(getattr(sim, key)) for key in TICK_STATE}


# --------------------------------------------------------------------------- driver


def setup(cell) -> dict:
    spec = scenario(cell)
    mix = cell.mix
    sim = build(spec)
    flows = []
    if float(mix["start_at"]) > 0:
        waterfill = sim.device.waterfill

        def counted(src, *args):
            flows.append(int(np.size(src)))
            return waterfill(src, *args)

        sim.device.waterfill = counted
        sim.run(until=float(mix["start_at"]))
        del sim.device.waterfill  # the bound method again
    warm(sim, flows[-1] if flows else 0)
    print(f"fleet: set-up reached t={sim.now} ticks={sim.ticks} "
          f"n={sim.n} pieces={sim.num_pieces} flows={flows[-1:]}",
          flush=True)
    return {"spec": spec, "sim": sim, "log": Log(cell.seed)}


def _finished(sim) -> bool:
    over = np.isfinite(sim.completed_at) | sim.departed
    return bool(over.all()) and bool((sim.arrive <= sim.now + 1e-9).all())


def _counters(sim) -> dict:
    """The device state's own counts: water-fill calls per path and
    fixed-point rounds (zeros where the state does not keep them)."""
    dev = sim.device
    runs = dict(getattr(dev, "waterfill_runs", {}))
    return dict(runs, rounds=getattr(dev, "rounds", 0))


def window(state: dict, cell, tracer) -> dict:
    """``run(until=now + dt)`` calls back to back until ``cell.seconds``
    have gone, keeping the drawn ticks' state for the check."""
    sim = state["sim"]
    log: Log = state["log"]
    sim.rng = np.random.default_rng(cell.seed)
    probe(sim, log, tracer, cell.fault)
    t_from, ticks_from = sim.now, sim.ticks
    c_from, p_from = _counters(sim), dict(sim.phase_seconds)
    p0 = p1 = None
    k = 0
    t0 = time.perf_counter()
    deadline = t0 + cell.seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        if _finished(sim):
            raise RuntimeError(f"every client finished at t={sim.now} "
                               "inside the window")
        if tracer.poll(now):
            snap = dict(sim.phase_seconds)
            if tracer.active:
                p0 = snap
            else:
                p1 = snap
        replay = k in log.check_ticks
        if replay:
            log.tick = {"before": _tick_state(sim), "t": sim.now,
                        "ticks": sim.ticks}
        with tracer.annotate("bench.run"):
            sim.run(until=sim.now + sim.dt)
        if replay:
            tick, log.tick = log.tick, None
            tick.update(after=_tick_state(sim), dt=sim.now - tick["t"],
                        one_tick=sim.ticks == tick["ticks"] + 1)
            log.ticks.append(tick)
        k += 1
    wall = time.perf_counter() - t0
    if tracer.active:
        tracer.stop()
        p1 = dict(sim.phase_seconds)
    sim_s, ticks = sim.now - t_from, sim.ticks - ticks_from
    counts = {key: v - c_from.get(key, 0) for key, v in _counters(sim).items()}
    phases = ({key: p1[key] - p0[key] for key in p0}
              if p0 is not None and p1 is not None else {})
    print(f"fleet: window {wall:.3f} s, {sim_s:.1f} sim s, {ticks} ticks, "
          f"now t={sim.now}; water-fill "
          + " ".join(f"{key}={v}" for key, v in counts.items()), flush=True)
    return {"e2e": {"sim_s_per_wall_s": sim_s / wall},
            "attempted": ticks, "failed": 0, "calls": log.calls,
            "phases": phases,
            "extra": {"ticks": ticks, "sim_s": sim_s, "wall_s": wall}}


def finish(state: dict) -> dict:
    sim = state["sim"]
    evidence = {
        "waterfills": state["log"].waterfills, "ticks": state["log"].ticks,
        "seed": int(state["spec"]["seed"]), "n": sim.n,
        "piece_sizes": sim.piece_sizes.copy(), "arrive": sim.arrive.copy(),
        "fraction": sim.policy.swarm_fraction, "mode": sim.policy.mode,
        "fallback": bool(sim.policy.http_fallback),
        "mirror_caps": sim._mirror_caps(), "upload_slots": sim.upload_slots,
        "ppr": sim.swarm_cfg.per_peer_requests,
        "end": {"have": np.array(sim.have), "departed": sim.departed.copy(),
                "dev_have": np.asarray(sim.device.have),
                "dev_repl": np.asarray(sim.device.repl)},
    }
    state.clear()
    return evidence


# --------------------------------------------------------------------------- check


def check(evidence: dict, cell) -> list:
    """Compare with the plain reference: every sampled water-fill's rates;
    each replayed tick's flow table against the rules it must keep, and
    its selections, progress, completions and byte ledgers recomputed
    from the state before it; and the device's have matrix and replica
    counts at the end against the host's."""
    from harness import load_module

    ref = load_module(CHIP / "references" / "fleet.py")
    limits = cell.workload["limits"]
    gap = 0.0
    flows_n = 0
    for w in evidence["waterfills"]:
        want = ref.waterfill(w["src"], w["dst"], w["up"], w["down"],
                             w["link_of"], w["link_cap"])
        gap = max(gap, ref.rate_gap(w["rates"], want))
        flows_n += int(want.size)
    P = evidence["piece_sizes"].size
    jit = ref.jitter(evidence["seed"], evidence["n"], P)
    swarm = ref.swarm_routed(P, evidence["fraction"])
    rules = dict(swarm=swarm, jitter=jit, sizes=evidence["piece_sizes"],
                 arrive=evidence["arrive"], mode=evidence["mode"],
                 fallback=evidence["fallback"],
                 mirror_caps=evidence["mirror_caps"],
                 pair_budget=evidence["upload_slots"] // evidence["ppr"],
                 ppr=evidence["ppr"])
    state_bad = flow_bad = 0
    byte_gap = 0.0
    picks = 0
    for tick in evidence["ticks"]:
        if not tick["one_tick"] or "flows" not in tick:
            state_bad += 1  # a replayed call must run one tick, with flows
            continue
        got = ref.replay_tick(tick["before"], tick["flows"], tick["t"],
                              tick["dt"], **rules)
        flow_bad += got["flow_violations"]
        picks += got["picks"]
        after = tick["after"]
        for key in ("have", "cur_http", "cur_swarm", "nhave",
                    "completed_at"):
            state_bad += int((got[key] != after[key]).sum())
        for key in ("prog_http", "prog_swarm", "downloaded",
                    "mirror_uploaded"):
            byte_gap = max(byte_gap, float(
                np.abs(got[key] - after[key]).max(initial=0.0)))
    end = evidence["end"]
    avail = end["have"][~end["departed"]].sum(axis=0)
    state_bad += int((end["dev_have"] != end["have"]).sum())
    state_bad += int((end["dev_repl"] != avail).sum())
    print(f"fleet check: {flows_n} rates in {len(evidence['waterfills'])} "
          f"calls, {len(evidence['ticks'])} ticks replayed with {picks} "
          "picks", flush=True)
    return [
        ("rate_gap", gap, limits["rate_gap"]),
        ("flow_violations", flow_bad, limits["flow_violations"]),
        ("state_mismatches", state_bad, limits["state_mismatches"]),
        ("byte_gap", byte_gap, limits["byte_gap"]),
    ]
