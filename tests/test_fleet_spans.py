"""The fleet tick's profile spans, read back from a CPU profile.

A small ``backend="pallas"`` crowd (kernels interpreted) runs several
``run(until)`` calls under ``jax.profiler``; the ``fleet.*`` host events
of the written ``.xplane.pb`` must count what the engine did, nest where
the engine documents them, and leave the simulated outcome unchanged.
"""

import glob
import json
import pathlib

import numpy as np
import pytest

from repro.core.scenario import ScenarioSpec
from repro.core.spans import Span

SCENARIOS = pathlib.Path(__file__).parent.parent / "benchmarks" / "scenarios"
# the crowd arrives at t = 2: the first call fast-forwards to it, and one
# call advances nothing
ARRIVE, STOPS = 2.0, (3.0, 5.0, 7.0, 7.0, 8.0)
STATE = ("have", "nhave", "cur_http", "cur_swarm", "prog_http", "prog_swarm",
         "src_tab", "downloaded", "uploaded_wire", "mirror_uploaded",
         "completed_at")


def _crowd():
    """24 clients fetching 200 pieces of 1 MB: every tick completes and
    selects pieces, and nobody finishes inside the stops."""
    spec = json.loads((SCENARIOS / "fleet_smoke.json").read_text())
    spec["arrivals"][0].update(n=24, at=ARRIVE)
    spec["content"]["manifests"][0].update(
        size_bytes=200_000_000, piece_length=1_000_000)
    spec["fleet"] = {"dt": 1.0, "fanout": None, "backend": "pallas"}
    return ScenarioSpec.from_dict(spec).build("fleet").sim


def _run(sim):
    for stop in STOPS:
        sim.run(until=stop)


def _events(log_dir) -> dict:
    """``fleet.*`` host events by name: ``(start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fleet."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """The crowd's run under the profiler, with its device selections
    counted by a wrapper, and the same run without a profiler."""
    import jax

    plain = _crowd()
    _run(plain)

    sim = _crowd()
    sim._freeze()  # builds the device state
    dev = sim.device
    select = dev.select
    calls = []

    def counted(*args, **kw):
        calls.append(1)
        return select(*args, **kw)

    dev.select = counted
    assert (sim.now, sim.ticks, dev.rounds) == (0.0, 0, 0)
    log_dir = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(log_dir))
    try:
        _run(sim)
    finally:
        jax.profiler.stop_trace()
    return {"sim": sim, "plain": plain, "events": _events(log_dir),
            "ticks": sim.ticks, "rounds": dev.rounds,
            "selects": len(calls)}


def test_tick_spans_count_the_ticks_advanced(profiled):
    assert profiled["ticks"] == int(STOPS[-1] - ARRIVE)
    assert len(profiled["events"]["fleet.tick"]) == profiled["ticks"]


def test_select_spans_count_the_device_selections(profiled):
    assert profiled["selects"] > profiled["ticks"]
    assert len(profiled["events"]["fleet.select"]) == profiled["selects"]


def test_waterfill_rounds_ride_on_their_spans(profiled):
    spans = profiled["events"]["fleet.waterfill"]
    assert len(spans) == profiled["ticks"]
    assert profiled["rounds"] > 0
    assert sum(s[2]["rounds"] for s in spans) == profiled["rounds"]


def test_waterfill_spans_say_whether_the_contraction_ran(profiled):
    # 24 clients' tables are far under the one-hot contraction's node
    # limit: it runs every call
    spans = profiled["events"]["fleet.waterfill"]
    runs = profiled["sim"].device.waterfill_runs
    assert all("contracted" in s[2] for s in spans)
    assert sum(s[2]["contracted"] for s in spans) == runs["onehot"] \
        == len(spans)
    assert runs["xla"] == 0


@pytest.mark.parametrize("name", ["fleet.resample", "fleet.flow_table",
                                  "fleet.completions", "fleet.select",
                                  "fleet.waterfill"])
def test_spans_lie_inside_ticks(profiled, name):
    ticks = profiled["events"]["fleet.tick"]
    spans = profiled["events"][name]
    assert spans
    for s, e, _ in spans:
        assert any(ts <= s and e <= te for ts, te, _ in ticks), name


def test_selections_nest_in_completions(profiled):
    done = profiled["events"]["fleet.completions"]
    inside = [s for s in profiled["events"]["fleet.select"]
              if any(cs <= s[0] and s[1] <= ce for cs, ce, _ in done)]
    assert inside and len(inside) < len(profiled["events"]["fleet.select"])


def test_phase_seconds_keep_their_four_keys(profiled):
    phases = profiled["sim"]._result().phase_seconds
    assert set(phases) == {"select", "waterfill", "bookkeeping", "telemetry"}
    assert phases["select"] > 0 and phases["waterfill"] > 0


def test_profiled_run_is_bit_identical(profiled):
    sim, plain = profiled["sim"], profiled["plain"]
    assert (sim.now, sim.ticks) == (plain.now, plain.ticks)
    for key in STATE:
        np.testing.assert_array_equal(getattr(sim, key), getattr(plain, key),
                                      err_msg=key)
    np.testing.assert_array_equal(np.asarray(sim.device.have),
                                  np.asarray(plain.device.have))
    np.testing.assert_array_equal(np.asarray(sim.device.repl),
                                  np.asarray(plain.device.repl))
    assert sim.device.rounds == plain.device.rounds


def test_span_times_its_phase_without_a_profile():
    phases = {"select": 0.0}
    with Span("fleet.select", phases, "select") as span:
        span.set_metadata(rounds=3)  # no profile: nothing to attach to
        assert span.annotation is None
    assert phases["select"] > 0
    with Span("fleet.tick"):
        pass
    assert list(phases) == ["select"]
