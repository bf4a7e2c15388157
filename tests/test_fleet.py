"""Fleet engine: waterfill port, batched selection, small-N equivalence.

The fleet engine trades per-peer event fidelity for array throughput; these
tests pin the contract that makes that trade safe (see the fidelity model in
``repro/core/fleet.py``):

* ``waterfill_rates`` allocates identically to the netsim reference
  ``FluidNetwork._recompute_rates`` on shared topologies.
* Pure-HTTP paths are *exact*: completion within one tick of the analytic
  fair-share time, origin egress exactly N copies, U/D exactly 1.
* The committed declarative scenarios agree with the ``time`` engine within
  the documented bounds (exact for HTTP-dominated runs, a tolerance band
  for swarm-dominated ones).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import (
    FleetSpec,
    FleetSwarmSim,
    FluidNetwork,
    MetaInfo,
    MirrorSpec,
    OriginPolicy,
    ScenarioSpec,
    SwarmConfig,
    flash_crowd,
    waterfill_rates,
)
from repro.core.piece_selection import batched_rarest, rarest_among

SCENARIOS = "benchmarks/scenarios"


# ------------------------------------------------------------------ waterfill


def _netsim_rates(src, dst, up_cap, down_cap, link_of=None, link_cap=None):
    """Reference allocation: the same topology through FluidNetwork."""
    net = FluidNetwork()
    nodes = [
        net.add_node(f"n{i}", up_bps=u, down_bps=d)
        for i, (u, d) in enumerate(zip(up_cap, down_cap))
    ]
    links = (
        [net.add_link(f"l{j}", c) for j, c in enumerate(link_cap)]
        if link_cap is not None else []
    )
    flows = []
    for k, (s, d) in enumerate(zip(src, dst)):
        lk = ()
        if link_of is not None and link_of[k] >= 0:
            lk = (links[link_of[k]],)
        flows.append(
            net.start_flow(nodes[s], nodes[d], size=1e18, links=lk)
        )
    net._recompute_rates()
    return np.array([f.rate for f in flows])


def random_topology(rng, with_links):
    nn = int(rng.integers(2, 9))
    nf = int(rng.integers(1, 25))
    src = rng.integers(0, nn, size=nf)
    dst = (src + rng.integers(1, nn, size=nf)) % nn  # src != dst
    up = rng.uniform(1.0, 100.0, size=nn)
    dn = rng.uniform(1.0, 100.0, size=nn)
    link_of = link_cap = None
    if with_links:
        nl = int(rng.integers(1, 4))
        link_cap = rng.uniform(1.0, 50.0, size=nl)
        link_of = rng.integers(-1, nl, size=nf)
    return src, dst, up, dn, link_of, link_cap


@pytest.mark.parametrize("with_links", [False, True])
def test_waterfill_matches_netsim_randomized(with_links):
    rng = np.random.default_rng(42)
    for _ in range(40):
        src, dst, up, dn, link_of, link_cap = random_topology(rng, with_links)
        got = waterfill_rates(src, dst, up, dn, link_of, link_cap)
        want = _netsim_rates(src, dst, up, dn, link_of, link_cap)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_waterfill_bottleneck_shares():
    # 3 flows out of one 30-unit uplink into ample sinks: 10 each
    rates = waterfill_rates(
        np.array([0, 0, 0]), np.array([1, 2, 3]),
        np.array([30.0, 0, 0, 0]), np.array([0.0, 100, 100, 4]),
    )
    # the third sink caps at 4, freeing headroom for the other two
    np.testing.assert_allclose(rates, [13.0, 13.0, 4.0])


def test_waterfill_empty():
    assert waterfill_rates(
        np.zeros(0, np.int64), np.zeros(0, np.int64),
        np.array([1.0]), np.array([1.0]),
    ).size == 0


# ------------------------------------------------------------------ selection


def test_batched_rarest_picks_minimum_availability():
    rng = np.random.default_rng(0)
    P = 37
    avail = rng.integers(0, 6, size=P).astype(np.float64)
    cand = rng.random((50, P)) < 0.3
    jitter = rng.random((50, P), dtype=np.float32)
    pick = batched_rarest(cand, avail, jitter)
    for i in range(50):
        row = np.flatnonzero(cand[i])
        if row.size == 0:
            assert pick[i] == -1
            continue
        assert cand[i, pick[i]]
        assert avail[pick[i]] == avail[row].min()
        # agrees with the scalar kernel's winner set
        best = row[avail[row] == avail[row].min()]
        assert rarest_among(row, avail, np.random.default_rng(i)) in best


# ------------------------------------------------------------------ spec


def test_fleet_spec_validation_and_roundtrip():
    with pytest.raises(ValueError):
        FleetSpec(dt=0.0)
    with pytest.raises(ValueError):
        FleetSpec(fanout=0)
    spec = FleetSpec(dt=0.5, fanout=3, backend="pallas")
    assert FleetSpec.from_dict(spec.to_dict()) == spec


def test_fleet_spec_backend_knob():
    assert FleetSpec().backend == "numpy"
    with pytest.raises(ValueError, match="'numpy' or 'pallas'"):
        FleetSpec(backend="cuda")
    for backend in ("numpy", "pallas"):
        spec = FleetSpec(backend=backend)
        assert FleetSpec.from_dict(spec.to_dict()) == spec
        assert spec.to_dict()["backend"] == backend
        assert "jit" not in spec.to_dict()
    # pre-backend dicts (no "backend" key, "jit": false) still load
    old = FleetSpec.from_dict({"dt": 1.0, "fanout": None, "jit": False})
    assert old == FleetSpec(dt=1.0)


@pytest.mark.parametrize("build", [
    pytest.param(lambda: FleetSpec(backend="jit"), id="backend"),
    pytest.param(lambda: FleetSpec.from_dict({"jit": True}), id="from_dict"),
])
def test_fleet_spec_refuses_the_retired_jit_backend(build):
    with pytest.raises(ValueError) as err:
        build()
    msg = str(err.value)
    assert "'jit'" in msg and "'numpy'" in msg and "'pallas'" in msg


def test_fleet_rejects_unsupported_policies():
    mi = MetaInfo.from_sizes_only(int(64e6), int(8e6), name="x")
    with pytest.raises(ValueError, match="hedg"):
        FleetSwarmSim(mi, OriginPolicy(hedge=True))
    with pytest.raises(ValueError, match="static"):
        FleetSwarmSim(mi, OriginPolicy(selection="least_loaded"))
    sim = FleetSwarmSim(mi, OriginPolicy())
    with pytest.raises(ValueError, match="event kind"):
        sim.schedule_event(1.0, "piece_corrupt", "p0")


# ------------------------------------------------------------------ exact paths


def test_pure_http_analytic_exact():
    # 4 clients share a 50 MB/s origin: 1 GB each at 12.5 MB/s -> 80 s.
    # HTTP paths are exact in the fleet engine: completion within one tick,
    # origin egress exactly N copies, U/D exactly 1.
    mi = MetaInfo.from_sizes_only(int(1e9), int(25e6), name="http")
    sim = FleetSwarmSim(
        mi,
        OriginPolicy(mode="http_first", swarm_fraction=0.0),
        SwarmConfig(),
        FleetSpec(dt=1.0),
        seed=0,
    )
    sim.add_mirrors([MirrorSpec("origin", up_bps=50e6)])
    sim.add_peers(flash_crowd(4), up_bps=25e6, down_bps=50e6)
    res = sim.run()
    assert res.completed == 4
    t_all = res.completed_at.max()
    assert 80.0 - 1e-9 <= t_all <= 80.0 + 2 * res.dt
    assert res.origin_uploaded == pytest.approx(4 * 1e9)
    assert res.ud_ratio == pytest.approx(1.0)


def test_churn_and_linger():
    mi = MetaInfo.from_sizes_only(int(1e9), int(25e6), name="churn")
    sim = FleetSwarmSim(
        mi,
        OriginPolicy(mode="http_first", swarm_fraction=0.0),
        fleet=FleetSpec(dt=1.0),
    )
    sim.add_mirrors([MirrorSpec("origin", up_bps=50e6)])
    sim.add_peers(flash_crowd(3), up_bps=25e6, down_bps=50e6,
                  seed_linger=5.0)
    # a straggler keeps the sim alive long enough for the early finishers'
    # linger departures to actually execute (the run ends with the last
    # download, so the final seeds' departures stay scheduled-but-unrun)
    sim.add_peers([("late", 200.0)], up_bps=25e6, down_bps=50e6)
    sim.schedule_event(10.0, "peer_churn", "peer0001")
    res = sim.run()
    idx = {pid: i for i, pid in enumerate(res.peer_ids)}
    churned = idx["peer0001"]
    assert res.departed_at[churned] == pytest.approx(10.0)
    assert not np.isfinite(res.completed_at[churned])
    assert np.isfinite(res.completed_at[idx["late"]])
    others = [idx["peer0000"], idx["peer0002"]]
    assert np.isfinite(res.completed_at[others]).all()
    # finished seeds linger then depart
    done = res.completed_at[others]
    gone = res.departed_at[others]
    assert ((gone >= done + 5.0 - 1e-9) & (gone <= done + 5.0 + res.dt)).all()


def test_mirror_fail_heal_events():
    mi = MetaInfo.from_sizes_only(int(4e8), int(25e6), name="fail")
    sim = FleetSwarmSim(
        mi,
        OriginPolicy(mode="http_first", swarm_fraction=0.0),
        fleet=FleetSpec(dt=1.0),
    )
    sim.add_mirrors([
        MirrorSpec("a", up_bps=50e6, weight=2.0),
        MirrorSpec("b", up_bps=50e6, weight=1.0),
    ])
    sim.add_peers(flash_crowd(2), up_bps=25e6, down_bps=50e6)
    sim.schedule_event(2.0, "mirror_fail", "a")
    sim.schedule_event(6.0, "mirror_heal", "a")
    res = sim.run()
    assert res.completed == 2
    by = dict(zip(res.mirror_names, res.mirror_uploaded))
    assert by["b"] > 0  # failover actually happened
    assert res.origin_uploaded == pytest.approx(2 * 4e8)


# ------------------------------------------------------------------ equivalence


def outcomes(name):
    spec = ScenarioSpec.load(f"{SCENARIOS}/{name}.json")
    return {
        eng: next(iter(spec.build(eng).run().outcomes.values()))
        for eng in ("time", "fleet")
    }


def test_equivalence_tail_latency_exact():
    # pure-HTTP scenario: both engines must land on the identical analytic
    # completion time (1024 s) and U/D of exactly 1
    out = outcomes("tail_latency")
    assert out["time"].duration == pytest.approx(1024.0)
    assert out["fleet"].duration == pytest.approx(1024.0)
    assert out["fleet"].ud_ratio == pytest.approx(1.0)
    assert out["fleet"].completed == out["time"].completed == 12


def test_equivalence_mirror_failover_within_piece_bound():
    # failover diverges by at most one piece service time + one tick: the
    # fleet engine keeps partial-piece bytes across a mirror failure, the
    # time engine re-requests the whole range (4 MB / (15 MB/s / 12) = 3.2 s)
    out = outcomes("mirror_failover")
    bound = 4e6 / (15e6 / 12) + out["fleet"].raw.dt
    assert abs(out["fleet"].duration - out["time"].duration) <= bound
    assert out["fleet"].ud_ratio == pytest.approx(1.0)
    assert out["fleet"].completed == 12


def test_equivalence_webseed_hybrid_band():
    # swarm-dominated run: structural agreement (documented tens-of-percent
    # band), plus the pinned fleet-side goldens so drift is caught even
    # inside the band
    out = outcomes("webseed_hybrid")
    t, f = out["time"], out["fleet"]
    assert abs(f.duration - t.duration) / t.duration < 0.25
    assert abs(f.ud_ratio - t.ud_ratio) / t.ud_ratio < 0.25
    assert f.duration == pytest.approx(86.5, abs=0.5)
    assert f.ud_ratio == pytest.approx(10.47, abs=0.05)


def test_scenario_fleet_block_roundtrip():
    spec = ScenarioSpec.load(f"{SCENARIOS}/fleet_scaling.json")
    assert spec.fleet == FleetSpec(dt=1.0)
    again = ScenarioSpec.from_dict(spec.to_dict())
    assert again.fleet == spec.fleet


def test_fleet_spec_devices_round_trip():
    spec = FleetSpec(dt=1.0, backend="pallas", devices=4)
    assert spec.to_dict()["devices"] == 4
    assert FleetSpec.from_dict(spec.to_dict()) == spec
    # pre-devices dicts still load, on one chip
    old = FleetSpec.from_dict({"dt": 1.0, "fanout": None, "jit": False,
                               "backend": "pallas"})
    assert old.devices == 1
    with pytest.raises(ValueError, match="unknown keys.*device"):
        FleetSpec.from_dict({"backend": "pallas", "device": 4})
    d = ScenarioSpec.load(f"{SCENARIOS}/fleet_scaling.json").to_dict()
    d["fleet"].update(backend="pallas", devices=2)
    again = ScenarioSpec.from_dict(d)
    assert again.fleet.devices == 2
    assert ScenarioSpec.from_dict(again.to_dict()) == again


@pytest.mark.parametrize("devices", [0, -4])
def test_fleet_spec_refuses_devices_out_of_range(devices):
    with pytest.raises(ValueError, match="devices must be >= 1"):
        FleetSpec(backend="pallas", devices=devices)


@pytest.mark.parametrize("backend", ["numpy"])
def test_fleet_spec_refuses_devices_off_the_device_backend(backend):
    with pytest.raises(ValueError, match="needs backend='pallas'"):
        FleetSpec(backend=backend, devices=4)
    d = ScenarioSpec.load(f"{SCENARIOS}/fleet_scaling.json").to_dict()
    d["fleet"].update(backend=backend, devices=2)
    with pytest.raises(ValueError, match="needs backend='pallas'"):
        ScenarioSpec.from_dict(d)


def test_fleet_build_refuses_more_devices_than_jax_sees():
    import jax

    spec = ScenarioSpec.load(f"{SCENARIOS}/fleet_scaling.json")
    seen = jax.device_count()
    spec.fleet = FleetSpec(dt=1.0, backend="pallas", devices=seen + 1)
    with pytest.raises(ValueError, match=f"JAX sees {seen}"):
        spec.build("fleet")


def test_fleet_rejects_multi_torrent():
    spec = ScenarioSpec.load(f"{SCENARIOS}/multi_torrent_fairness.json")
    with pytest.raises(ValueError):
        spec.build("fleet")


def test_fleet_metrics_sampler_wired():
    spec = ScenarioSpec.load(f"{SCENARIOS}/mirror_failover.json")
    result = spec.build("fleet").run()
    assert result.metrics is not None
    series = result.metrics.series()
    assert series["t"].size > 0, "sampler produced no points"
    # same gauge schema core as the object engines
    for gauge in ("seeders", "leechers", "origin_bytes", "peer_bytes",
                  "min_replication", "mean_replication"):
        assert gauge in series
    assert series["seeders"][-1] + series["leechers"][-1] == 12
    assert (np.diff(series["origin_bytes"]) >= 0).all()
    assert (np.diff(series["min_replication"]) >= 0).all()


def test_fleet_scaling_smoke_small():
    # miniature of the CI scaling-smoke job: the committed scaling scenario
    # down-sized to 64 clients still self-scales and stays exact on copies
    spec = ScenarioSpec.load(f"{SCENARIOS}/fleet_scaling.json")
    spec = dataclasses.replace(
        spec, arrivals=(dataclasses.replace(spec.arrivals[0], n=64),)
    )
    res = spec.build("fleet").run().primary
    assert res.completed == 64
    assert res.origin_uploaded < 8 * 4e9  # swarm amplification, not N copies
