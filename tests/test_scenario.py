"""ScenarioSpec: round-trip, validation, events, multi-torrent fairness."""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import (
    ArrivalSpec,
    ContentSpec,
    EventSpec,
    FabricSpec,
    FairShareLedger,
    ManifestSpec,
    MetaInfo,
    MirrorSpec,
    OriginPolicy,
    PodCacheSpec,
    ScenarioSpec,
    SwarmConfig,
    TopologySpec,
    Tracker,
    WebSeedSwarmSim,
    flash_crowd,
    jain_index,
)


def small_spec(**over) -> ScenarioSpec:
    base = dict(
        content=ContentSpec(manifests=(
            ManifestSpec("ds", 1 << 21, 1 << 17, payload="random"),
        )),
        fabric=FabricSpec(mirrors=(MirrorSpec("origin", up_bps=4e6),)),
        arrivals=(ArrivalSpec(kind="flash", n=4, up_bps=2e6, down_bps=4e6),),
        policy=OriginPolicy(swarm_fraction=1.0, origin_up_bps=4e6),
        seed=1,
    )
    base.update(over)
    return ScenarioSpec(**base)


# --------------------------------------------------------------------- round trip


def test_json_round_trip_full_tree():
    spec = ScenarioSpec(
        name="full",
        content=ContentSpec(manifests=(
            ManifestSpec("a", 1 << 20, 1 << 16, weight=2.0),
            ManifestSpec("b", 1 << 21, 1 << 16, payload="random", seed=9),
        )),
        fabric=FabricSpec(
            mirrors=(MirrorSpec("m0", up_bps=8e6, latency_s=0.5, weight=3.0),
                     MirrorSpec("m1", up_bps=2e6, max_concurrent=7)),
        ),
        topology=TopologySpec(num_pods=2, hosts_per_pod=4,
                              host_up_bps=25e6, host_down_bps=50e6,
                              spine_bps=float("inf"), same_pod_frac=0.9),
        arrivals=(
            ArrivalSpec(kind="poisson", n=8, up_bps=25e6, down_bps=50e6,
                        rate_per_sec=0.5, seed=3, torrent="a", prefix="x"),
            ArrivalSpec(kind="staggered", n=4, up_bps=25e6, down_bps=50e6,
                        interval=5.0, start=2.0, torrent="b", prefix="y",
                        seed_linger=0.0),
        ),
        events=(
            EventSpec(kind="corrupt_once", target="m0", piece=0, torrent="b"),
            EventSpec(kind="mirror_fail", at=30.0, target="m0"),
            EventSpec(kind="mirror_heal", at=60.0, target="m0"),
        ),
        policy=OriginPolicy(swarm_fraction=0.5, hedge=True,
                            fairness="weighted"),
        swarm=SwarmConfig(pipeline=4, max_neighbors=3),
        seed=42,
    )
    again = ScenarioSpec.from_json(spec.to_json())
    assert again == spec
    # and through a real JSON parse cycle (inf handling included)
    assert ScenarioSpec.from_dict(json.loads(spec.to_json())) == spec
    # strict RFC 8259: non-finite floats serialize as strings, never as
    # the non-standard Infinity/NaN tokens a foreign parser would choke on
    text = spec.to_json()
    assert "Infinity" not in text and '"inf"' in text
    json.loads(text, parse_constant=lambda c: pytest.fail(f"token {c}"))


@pytest.mark.parametrize("leaf,cls", [
    (MirrorSpec("m", up_bps=1e6, latency_s=0.1, weight=2.0,
                max_concurrent=3), MirrorSpec),
    (SwarmConfig(pipeline=2, corruption_prob=0.5), SwarmConfig),
])
def test_leaf_spec_round_trip(leaf, cls):
    assert cls.from_dict(leaf.to_dict()) == leaf


def test_property_round_trip_randomized():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    del hyp

    kinds = st.sampled_from(["flash", "staggered", "poisson"])

    @st.composite
    def specs(draw):
        n_manifests = draw(st.integers(1, 3))
        manifests = tuple(
            ManifestSpec(
                f"ds{i}",
                size_bytes=draw(st.integers(1, 1 << 24)),
                piece_length=draw(st.integers(1, 1 << 20)),
                seed=draw(st.integers(0, 9)),
                weight=draw(st.floats(0.1, 8.0, allow_nan=False)),
            )
            for i in range(n_manifests)
        )
        mirrors = tuple(
            MirrorSpec(
                f"m{i}",
                up_bps=draw(st.floats(1.0, 1e9, allow_nan=False)),
                latency_s=draw(st.floats(0.0, 5.0, allow_nan=False)),
                weight=draw(st.floats(0.1, 4.0, allow_nan=False)),
                max_concurrent=draw(
                    st.one_of(st.none(), st.integers(1, 64))
                ),
            )
            for i in range(draw(st.integers(1, 3)))
        )
        arrivals = tuple(
            ArrivalSpec(
                kind=draw(kinds),
                n=draw(st.integers(1, 32)),
                up_bps=draw(st.floats(1.0, 1e8, allow_nan=False)),
                down_bps=draw(st.floats(1.0, 1e8, allow_nan=False)),
                rate_per_sec=draw(st.floats(0.01, 5.0, allow_nan=False)),
                interval=draw(st.floats(0.0, 60.0, allow_nan=False)),
                seed=draw(st.integers(0, 99)),
                prefix=f"g{i}",
                torrent=manifests[
                    draw(st.integers(0, n_manifests - 1))
                ].name if n_manifests > 1 else None,
            )
            for i in range(draw(st.integers(1, 3)))
        )
        # distinct times: two identical events are rightly refused
        events = tuple(
            EventSpec(kind="mirror_fail", at=at, target=mirrors[0].name)
            for at in draw(st.lists(st.floats(0, 1e4, allow_nan=False),
                                    max_size=2, unique=True))
        )
        return ScenarioSpec(
            content=ContentSpec(manifests=manifests),
            fabric=FabricSpec(mirrors=mirrors),
            arrivals=arrivals,
            events=events,
            policy=OriginPolicy(
                swarm_fraction=draw(st.floats(0, 1, allow_nan=False)),
                hedge=draw(st.booleans()),
                fairness=draw(st.sampled_from(["none", "weighted"])),
            ),
            swarm=SwarmConfig(
                pipeline=draw(st.integers(1, 16)),
                policy=draw(st.sampled_from(
                    ["rarest_first", "sequential", "random_first"]
                )),
            ),
            seed=draw(st.integers(0, 999)),
            name=f"s{draw(st.integers(0, 9))}",
        )

    @given(spec=specs())
    @settings(max_examples=40, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def check(spec):
        assert ScenarioSpec.from_dict(spec.to_dict()) == spec
        assert ScenarioSpec.from_json(spec.to_json()) == spec

    check()


# --------------------------------------------------------------------- validation


def test_unknown_keys_rejected_everywhere():
    with pytest.raises(ValueError, match="unknown keys.*up_bsp"):
        MirrorSpec.from_dict({"name": "m", "up_bps": 1e6, "up_bsp": 2e6})
    with pytest.raises(ValueError, match="unknown keys.*pipelines"):
        SwarmConfig.from_dict({"pipelines": 4})
    spec = small_spec()
    d = spec.to_dict()
    d["polcy"] = d.pop("policy")
    with pytest.raises(ValueError, match="unknown keys.*polcy"):
        ScenarioSpec.from_dict(d)
    d2 = spec.to_dict()
    d2["policy"]["swarm_fractions"] = 0.5
    with pytest.raises(ValueError, match="unknown keys.*swarm_fractions"):
        ScenarioSpec.from_dict(d2)


def test_mirror_spec_validation():
    with pytest.raises(ValueError, match="up_bps must be positive"):
        MirrorSpec("m", up_bps=0.0)
    with pytest.raises(ValueError, match="up_bps must be positive"):
        MirrorSpec("m", up_bps=-5.0)
    with pytest.raises(ValueError, match="weight must be positive"):
        MirrorSpec("m", up_bps=1e6, weight=0.0)
    with pytest.raises(ValueError, match="max_concurrent"):
        MirrorSpec("m", up_bps=1e6, max_concurrent=0)
    with pytest.raises(ValueError, match="duplicate mirror"):
        FabricSpec(mirrors=(MirrorSpec("m", up_bps=1e6),
                            MirrorSpec("m", up_bps=2e6)))


def test_swarm_config_validation():
    with pytest.raises(ValueError, match="pipeline must be >= 1"):
        SwarmConfig(pipeline=0)
    with pytest.raises(ValueError, match="unknown selection policy"):
        SwarmConfig(policy="rarest_frist")
    with pytest.raises(ValueError, match="corruption_prob"):
        SwarmConfig(corruption_prob=1.5)


def test_scenario_cross_validation():
    with pytest.raises(ValueError, match="duplicate manifest"):
        ContentSpec(manifests=(ManifestSpec("d", 1, 1),
                               ManifestSpec("d", 2, 1)))
    with pytest.raises(ValueError, match="unknown torrent"):
        small_spec(arrivals=(
            ArrivalSpec(kind="flash", n=2, up_bps=1e6, down_bps=1e6,
                        torrent="nope"),
        ))
    with pytest.raises(ValueError, match="unknown mirror"):
        small_spec(events=(
            EventSpec(kind="mirror_fail", at=1.0, target="ghost"),
        ))
    with pytest.raises(ValueError, match="prefixes must be unique"):
        small_spec(arrivals=(
            ArrivalSpec(kind="flash", n=2, up_bps=1e6, down_bps=1e6),
            ArrivalSpec(kind="staggered", n=2, up_bps=1e6, down_bps=1e6,
                        interval=1.0),
        ))
    with pytest.raises(ValueError, match="pod caches need a topology"):
        small_spec(fabric=FabricSpec(
            mirrors=(MirrorSpec("origin", up_bps=4e6),),
            pod_caches=PodCacheSpec(up_bps=1e6),
        ))
    with pytest.raises(ValueError, match="corrupt_once needs piece"):
        EventSpec(kind="corrupt_once", target="m")


def test_engine_restrictions():
    spec = small_spec(content=ContentSpec(manifests=(
        ManifestSpec("ds", 1 << 21, 1 << 17),   # size_only
    )))
    with pytest.raises(ValueError, match="payload='random'"):
        spec.build("byte")
    churny = small_spec(events=(
        EventSpec(kind="peer_churn", at=2.0, target="peer0000"),
    ))
    with pytest.raises(ValueError, match="time-engine only"):
        churny.build("byte")
    with pytest.raises(ValueError, match="unknown engine"):
        small_spec().build("quantum")


# --------------------------------------------------------------------- compile equivalence


def test_time_build_matches_imperative():
    """The declarative compile is the imperative wiring, bit for bit."""
    spec = ScenarioSpec(
        content=ContentSpec(manifests=(
            ManifestSpec("webseed", int(64e6), int(8e6)),
        )),
        fabric=FabricSpec(mirrors=(MirrorSpec("origin", up_bps=8e6),)),
        arrivals=(ArrivalSpec(kind="flash", n=6, up_bps=25e6,
                              down_bps=50e6),),
        policy=OriginPolicy(swarm_fraction=0.5, origin_up_bps=8e6),
        seed=3,
    )
    res = spec.build("time").run().primary

    mi = MetaInfo.from_sizes_only(int(64e6), int(8e6), name="webseed")
    sim = WebSeedSwarmSim(
        mi, OriginPolicy(swarm_fraction=0.5, origin_up_bps=8e6),
        SwarmConfig(), seed=3,
    )
    sim.add_web_origin()
    sim.add_peers(flash_crowd(6), up_bps=25e6, down_bps=50e6)
    ref = sim.run()
    assert res.completion_time == ref.completion_time
    assert res.origin_uploaded == ref.origin_uploaded
    assert res.sim_time == ref.sim_time
    assert res.events == ref.events


def test_byte_engine_runs_and_verifies():
    spec = small_spec()
    result = spec.build("byte").run()
    out = result.outcomes["ds"]
    assert out.completed == out.clients == 4
    swarm = out.raw
    mi = swarm.metainfo
    for peer in swarm.peers.values():
        assert all(mi.verify_piece(i, d) for i, d in peer.store.items())


# --------------------------------------------------------------------- events


def test_same_tick_events_fire_in_listed_order():
    """fail@t then heal@t leaves the mirror up; heal@t then fail@t leaves
    it down — insertion order breaks the tie, deterministically."""
    base = small_spec(
        fabric=FabricSpec(mirrors=(MirrorSpec("m0", up_bps=4e6, weight=2.0),
                                   MirrorSpec("m1", up_bps=4e6))),
        policy=OriginPolicy(swarm_fraction=0.0, origin_up_bps=4e6),
    )
    fail_then_heal = dataclasses.replace(base, events=(
        EventSpec(kind="mirror_fail", at=1.0, target="m0"),
        EventSpec(kind="mirror_heal", at=1.0, target="m0"),
    ))
    out = fail_then_heal.build("time")
    res = out.run()
    assert out.sims["ds"].origin_set.failed == set()
    assert res.outcomes["ds"].completed == 4

    heal_then_fail = dataclasses.replace(base, events=(
        EventSpec(kind="mirror_heal", at=1.0, target="m0"),
        EventSpec(kind="mirror_fail", at=1.0, target="m0"),
    ))
    out2 = heal_then_fail.build("time")
    res2 = out2.run()
    assert out2.sims["ds"].origin_set.failed == {"m0"}
    # the survivor carried the swarm: everyone still completed, verified
    assert res2.outcomes["ds"].completed == 4


def test_event_after_completion_is_harmless():
    base = small_spec(
        fabric=FabricSpec(mirrors=(MirrorSpec("m0", up_bps=4e6, weight=2.0),
                                   MirrorSpec("m1", up_bps=4e6))),
    )
    quiet = base.build("time").run()
    late = dataclasses.replace(base, events=(
        EventSpec(kind="mirror_fail", at=1e5, target="m0"),
    )).build("time").run()
    # completion behaviour identical; only the timeline ran longer to
    # deliver the (pointless) event
    a = {k: v.completed for k, v in quiet.outcomes.items()}
    b = {k: v.completed for k, v in late.outcomes.items()}
    assert a == b
    assert quiet.outcomes["ds"].raw.completion_time == \
        late.outcomes["ds"].raw.completion_time


def test_mirror_fail_and_heal_round_trip_serves_again():
    spec = small_spec(
        fabric=FabricSpec(mirrors=(MirrorSpec("m0", up_bps=8e6, weight=2.0),
                                   MirrorSpec("m1", up_bps=1e6))),
        policy=OriginPolicy(swarm_fraction=0.0, origin_up_bps=8e6,
                            backoff=0.5),
        arrivals=(
            ArrivalSpec(kind="staggered", n=6, up_bps=2e6, down_bps=4e6,
                        interval=8.0),
        ),
        events=(
            EventSpec(kind="mirror_fail", at=2.0, target="m0"),
            EventSpec(kind="mirror_heal", at=20.0, target="m0"),
        ),
    )
    out = spec.build("time")
    res = out.run()
    assert res.outcomes["ds"].completed == 6
    sim = out.sims["ds"]
    assert sim.origin_set.failed == set()
    # the healed preferred mirror picked traffic back up after t=20
    assert sim.origin_set.origins["m0"].http_uploaded > 0
    assert sim.origin_set.origins["m1"].http_uploaded > 0


# --------------------------------------------------------------------- fairness


def fairness_spec(**over) -> ScenarioSpec:
    base = dict(
        name="fair",
        content=ContentSpec(manifests=(
            ManifestSpec("big", int(64e6), int(8e6), weight=1.0),
            ManifestSpec("small", int(64e6), int(8e6), weight=1.0),
        )),
        fabric=FabricSpec(mirrors=(MirrorSpec("origin", up_bps=16e6),)),
        arrivals=(
            ArrivalSpec(kind="flash", n=9, up_bps=25e6, down_bps=50e6,
                        torrent="big", prefix="a"),
            ArrivalSpec(kind="flash", n=3, up_bps=25e6, down_bps=50e6,
                        torrent="small", prefix="b"),
        ),
        policy=OriginPolicy(swarm_fraction=0.0, origin_up_bps=16e6,
                            max_concurrent=6, backoff=0.5,
                            fairness="weighted"),
        seed=5,
    )
    base.update(over)
    return ScenarioSpec(**base)


def test_weighted_fairness_jain_and_ledger():
    res = fairness_spec().build("time").run()
    # both torrents complete and the equal-weight Jain gate holds
    for out in res.outcomes.values():
        assert out.completed == out.clients
    assert res.jain_fairness is not None and res.jain_fairness >= 0.95
    # per-torrent egress is ledgered in SwarmStats and decomposes exactly
    per = res.stats.per_torrent_uploaded
    assert set(per) == {"big", "small"}
    assert sum(per.values()) == pytest.approx(res.stats.origin_uploaded)
    assert per["big"] == pytest.approx(
        res.outcomes["big"].origin_uploaded)


def test_fcfs_is_less_fair_than_weighted_on_asymmetric_crowds():
    fair = fairness_spec().build("time").run()
    fcfs = fairness_spec(
        policy=dataclasses.replace(fairness_spec().policy, fairness="none"),
    ).build("time").run()
    assert fcfs.jain_fairness is not None
    assert fair.jain_fairness > fcfs.jain_fairness


def test_weighted_shares_track_weights():
    res = fairness_spec(
        content=ContentSpec(manifests=(
            ManifestSpec("big", int(64e6), int(8e6), weight=3.0),
            ManifestSpec("small", int(64e6), int(8e6), weight=1.0),
        )),
    ).build("time").run()
    share = res.concurrent_origin_uploaded
    ratio = share["big"] / share["small"]
    assert 2.2 <= ratio <= 3.8, (ratio, share)
    # weight-normalized service is near-equal => Jain ~1
    assert res.jain_fairness >= 0.95


def test_byte_engine_multi_torrent_fairness():
    spec = fairness_spec(
        content=ContentSpec(manifests=(
            ManifestSpec("big", 1 << 21, 1 << 17, weight=1.0,
                         payload="random"),
            ManifestSpec("small", 1 << 21, 1 << 17, weight=1.0,
                         payload="random", seed=2),
        )),
        arrivals=(
            ArrivalSpec(kind="flash", n=6, up_bps=2e6, down_bps=4e6,
                        torrent="big", prefix="a"),
            ArrivalSpec(kind="flash", n=2, up_bps=2e6, down_bps=4e6,
                        torrent="small", prefix="b"),
        ),
    )
    res = spec.build("byte").run()
    for out in res.outcomes.values():
        assert out.completed == out.clients
    assert res.jain_fairness is not None


def test_late_arriving_torrent_does_not_starve_active_one():
    """Fairness must be work-conserving: a torrent whose crowd lands much
    later neither throttles the active torrent beforehand (pending
    arrivals are not demand) nor floods catch-up afterward (idle past
    earns no service credit)."""
    late = fairness_spec(arrivals=(
        ArrivalSpec(kind="flash", n=6, up_bps=25e6, down_bps=50e6,
                    torrent="big", prefix="a"),
        ArrivalSpec(kind="flash", n=6, at=500.0, up_bps=25e6, down_bps=50e6,
                    torrent="small", prefix="b"),
    ))
    fair = late.build("time").run()
    solo = fairness_spec(arrivals=(
        ArrivalSpec(kind="flash", n=6, up_bps=25e6, down_bps=50e6,
                    torrent="big", prefix="a"),
        ArrivalSpec(kind="flash", n=6, at=500.0, up_bps=25e6, down_bps=50e6,
                    torrent="small", prefix="b"),
    ), policy=dataclasses.replace(fairness_spec().policy, fairness="none"))
    base = solo.build("time").run()
    for out in fair.outcomes.values():
        assert out.completed == out.clients
    # the early torrent finishes long before the late crowd even arrives,
    # and within a whisker of the unthrottled run
    assert fair.outcomes["big"].duration < 500.0
    assert fair.outcomes["big"].duration <= \
        base.outcomes["big"].duration * 1.05


def test_byte_mirror_fail_applies_to_all_torrents():
    spec = fairness_spec(
        content=ContentSpec(manifests=(
            ManifestSpec("big", 1 << 20, 1 << 17, payload="random"),
            ManifestSpec("small", 1 << 20, 1 << 17, payload="random",
                         seed=2),
        )),
        fabric=FabricSpec(mirrors=(MirrorSpec("m0", up_bps=4e6,
                                              weight=2.0),
                                   MirrorSpec("m1", up_bps=4e6))),
        arrivals=(
            ArrivalSpec(kind="flash", n=3, up_bps=2e6, down_bps=4e6,
                        torrent="big", prefix="a"),
            ArrivalSpec(kind="flash", n=3, up_bps=2e6, down_bps=4e6,
                        torrent="small", prefix="b"),
        ),
        events=(EventSpec(kind="mirror_fail", at=1.0, target="m0"),),
    )
    out = spec.build("byte")
    res = out.run()
    for swarm in out.sims.values():
        # the un-torrented event failed the mirror for EVERY torrent's
        # origin set (shared box), and the survivor carried the load
        assert swarm.origin_set.failed == {"m0"}
        assert swarm.origin_set.origins["m1"].http_uploaded > 0
    for o in res.outcomes.values():
        assert o.completed == o.clients


def test_mirror_event_with_torrent_rejected_in_multi():
    with pytest.raises(ValueError, match="fleet-wide"):
        fairness_spec(events=(
            EventSpec(kind="mirror_fail", at=1.0, target="origin",
                      torrent="big"),
        ))
    # and the converse: per-torrent corrupt_once must say which torrent
    with pytest.raises(ValueError, match="must name"):
        fairness_spec(events=(
            EventSpec(kind="corrupt_once", target="origin", piece=0),
        ))


def test_peer_churn_target_validated():
    with pytest.raises(ValueError, match="unknown client"):
        small_spec(events=(
            EventSpec(kind="peer_churn", at=2.0, target="peer12"),
        ))
    # a valid target churns a real peer mid-download
    spec = small_spec(
        arrivals=(ArrivalSpec(kind="flash", n=5, up_bps=2e6,
                              down_bps=4e6),),
        events=(EventSpec(kind="peer_churn", at=1.0, target="peer0004"),),
    )
    out = spec.build("time")
    res = out.run()
    assert out.sims["ds"].agents["peer0004"].departed
    assert res.outcomes["ds"].completed == 4  # the churned peer never did


def test_multi_torrent_duration_is_per_torrent():
    res = fairness_spec().build("time").run()
    # the 3-client torrent finishes well before the 9-client one; both
    # durations must be their own completion times, not the shared clock
    assert res.outcomes["small"].duration < res.outcomes["big"].duration
    for name, out in res.outcomes.items():
        assert out.duration == pytest.approx(
            max(out.raw.finish_at.values())
        )


def test_fair_share_ledger_unit():
    led = FairShareLedger()
    led.register("a", 2.0, live=lambda: True)
    led.register("b", 1.0, live=lambda: True)
    with pytest.raises(ValueError, match="duplicate torrent"):
        led.register("a", 1.0, live=lambda: True)
    with pytest.raises(ValueError, match="weight must be positive"):
        led.register("c", 0.0, live=lambda: True)
    # unregistered torrents bypass arbitration
    assert led.allow("o", "ghost", 100.0)
    # deficit arbitration: a may lead b by at most one piece (normalized)
    assert led.allow("o", "a", 100.0)
    led.record("o", "a", 100.0)
    assert led.allow("o", "a", 100.0)      # lead 50 <= 100/2: at the bound
    led.record("o", "a", 100.0)
    assert not led.allow("o", "a", 100.0)  # lead 100 > 50: deferred
    assert led.allow("o", "b", 100.0)      # the deficited torrent goes
    led.record("o", "b", 100.0)
    assert led.allow("o", "a", 100.0)      # b caught up; a eligible again
    assert led.granted_by_torrent() == {"a": 200.0, "b": 100.0}
    assert led.deferred["a"] == 1


def test_jain_index():
    assert jain_index([1.0, 1.0, 1.0]) == pytest.approx(1.0)
    assert jain_index([1.0, 0.0]) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        jain_index([])


def test_scrape_fleet_decomposition():
    tr = Tracker()
    a = MetaInfo.from_bytes(b"a" * 4096, 1024, name="a")
    b = MetaInfo.from_bytes(b"b" * 2048, 1024, name="b")
    for mi in (a, b):
        tr.register(mi)
        tr.announce(mi, "origin", uploaded=0, downloaded=0, event="started",
                    is_origin=True)
    tr.announce(a, "p1", uploaded=0, downloaded=0, event="started", now=0.0)
    tr.announce(b, "p2", uploaded=0, downloaded=0, event="started", now=0.0)
    tr.announce(a, "p1", uploaded=0, downloaded=4096.0, event="completed",
                now=3.0)
    tr.announce(a, "origin", uploaded=4096.0, downloaded=0, event="update",
                is_origin=True)
    tr.announce(b, "p2", uploaded=0, downloaded=2048.0, event="completed",
                now=5.0)
    tr.announce(b, "origin", uploaded=2048.0, downloaded=0, event="update",
                is_origin=True)
    fleet = tr.scrape_fleet([a, b])
    assert fleet.per_torrent_uploaded == {"a": 4096.0, "b": 2048.0}
    assert fleet.origin_uploaded == 6144.0
    assert fleet.total_downloaded == 6144.0
    assert fleet.completed == 2
    assert fleet.completion_percentiles["p50"] == pytest.approx(4.0)
