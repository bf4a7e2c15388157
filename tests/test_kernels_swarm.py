"""Swarm kernels (rarest-argmin + water-filling) vs their oracles.

Three exactness tiers (see ``repro/kernels/swarm/ref.py``):

- rarest-argmin is *index-exact* against the numpy engine hot path;
- both device water-fill paths (the padded one-hot contraction and the
  padded scatter fixed point), and the device state's entry point that
  picks between them, are *bit-exact* against the pure-jnp scatter
  oracle (padding / dummy-slot / one-hot machinery adds nothing);
- against numpy references it holds a tight relative band (XLA:CPU fuses
  ``alloc + count * delta`` into FMAs; numpy rounds twice), and the
  engine-level test pins that the band never moves a piece completion on
  the smoke scenario — piece-granular ledgers match the numpy engine
  exactly.
"""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.core.fleet import waterfill_rates
from repro.core.piece_selection import batched_rarest
from repro.kernels.swarm import kernel as swarm_kernel
from repro.kernels.swarm import ops as swarm_ops
from repro.kernels.swarm import (
    FleetDeviceState,
    fleet_waterfill,
    rarest_argmin,
    waterfill_f32_ref,
    waterfill_jnp_ref,
)

RNG = np.random.default_rng(7)
SCENARIOS = pathlib.Path(__file__).parent.parent / "benchmarks" / "scenarios"


# ------------------------------------------------------------------ rarest-argmin


def _random_selection(k, P, density):
    cand = RNG.random((k, P)) < density
    avail = RNG.integers(0, 50, P).astype(np.float64)
    jitter = RNG.random((k, P), dtype=np.float32)
    return cand, avail, jitter


@pytest.mark.parametrize(
    "k,P,density",
    [
        (1, 1, 1.0),         # minimum everything
        (3, 5, 0.6),         # tiny, non-pow2
        (17, 100, 0.3),      # non-multiples of both block dims
        (128, 256, 0.5),     # exactly one tile
        (130, 300, 0.1),     # spills into partial tiles, sparse
        (64, 1000, 0.9),     # many piece tiles, dense
        (200, 37, 0.4),      # more rows than pieces
    ],
)
def test_rarest_argmin_index_exact(k, P, density):
    cand, avail, jitter = _random_selection(k, P, density)
    np.testing.assert_array_equal(
        rarest_argmin(cand, avail, jitter),
        batched_rarest(cand, avail, jitter),
    )


def test_rarest_argmin_all_masked_rows():
    cand, avail, jitter = _random_selection(40, 90, 0.5)
    cand[::3] = False  # every third row has no candidate -> -1
    out = rarest_argmin(cand, avail, jitter)
    assert (out[::3] == -1).all()
    np.testing.assert_array_equal(out, batched_rarest(cand, avail, jitter))


def test_rarest_argmin_single_candidate_rows():
    k, P = 31, 70
    cand = np.zeros((k, P), dtype=bool)
    only = RNG.integers(0, P, k)
    cand[np.arange(k), only] = True
    avail = RNG.integers(0, 9, P).astype(np.float64)
    jitter = RNG.random((k, P), dtype=np.float32)
    np.testing.assert_array_equal(rarest_argmin(cand, avail, jitter), only)


def test_rarest_argmin_forced_ties():
    # constant availability and heavily quantized jitter force both
    # tie-break stages: the lexicographic (avail, jitter, index) order and
    # first-occurrence argmin must match the numpy engine across tiles
    k, P = 64, 520
    cand = RNG.random((k, P)) < 0.8
    avail = np.full(P, 3.0)
    jitter = (RNG.integers(0, 4, (k, P)) / 4.0).astype(np.float32)
    np.testing.assert_array_equal(
        rarest_argmin(cand, avail, jitter),
        batched_rarest(cand, avail, jitter),
    )


# ------------------------------------------------------------------ water-filling


def _random_topology(nf, nn, spine=False, inf_caps=False):
    src = RNG.integers(0, nn, nf)
    dst = RNG.integers(0, nn, nf)
    dst = np.where(dst == src, (dst + 1) % nn, dst)
    up = RNG.uniform(1.0, 100.0, nn)
    dn = RNG.uniform(1.0, 100.0, nn)
    if inf_caps:
        dn[RNG.random(nn) < 0.3] = np.inf
    link_of = link_cap = None
    if spine:
        link_of = np.where(RNG.random(nf) < 0.5, 0, -1).astype(np.int64)
        link_cap = np.array([RNG.uniform(5.0, 60.0)])
    return src, dst, up, dn, link_of, link_cap


@pytest.mark.parametrize("nf,nn", [(1, 2), (5, 3), (37, 10), (300, 40)])
@pytest.mark.parametrize("spine", [False, True])
@pytest.mark.parametrize("impl", ["xla", "onehot", "device"])
def test_waterfill_bit_exact_vs_jnp_oracle(nf, nn, spine, impl):
    src, dst, up, dn, lof, lcap = _random_topology(nf, nn, spine=spine)
    ref, ref_rounds = waterfill_jnp_ref(src, dst, up, dn, lof, lcap,
                                        with_rounds=True)
    if impl != "device":
        out, rounds, _ = swarm_ops._waterfill(src, dst, up, dn, lof, lcap,
                                              impl)
    else:
        # the engine's entry point: the plan's path, and the statistics
        # the benchmark reads
        dev = FleetDeviceState(np.zeros((1, 1), np.float32),
                               np.zeros(1, bool))
        out = dev.waterfill(src, dst, up, dn, lof, lcap)
        assert dev.waterfill_runs == {"onehot": 1, "xla": 0}
        assert dev.peak_flows == nf
        rounds = dev.rounds
    np.testing.assert_array_equal(out.astype(np.float32), ref)
    assert rounds == ref_rounds


@pytest.mark.parametrize("spine", [False, True])
def test_waterfill_onehot_spans_node_blocks(spine):
    # 3,000 nodes padded to 4,096 (32 blocks of 128) and 20,000 flows to
    # 32,768 with -1 pads: the contraction's two-level incidences give
    # the scatter oracle's rates and rounds bit for bit. Equal capacities
    # keep the rounds to a few hundred
    nf, nn = 20_000, 3_000
    src, dst, _, _, lof, lcap = _random_topology(nf, nn, spine=spine)
    up, dn = np.full(nn, 25.0), np.full(nn, 50.0)
    rate, rounds, plan = swarm_ops._waterfill(
        src, dst, up, dn, lof, lcap, "onehot")
    assert (plan.pf, plan.pn) == (1 << 15, 1 << 12)
    ref, ref_rounds = waterfill_jnp_ref(src, dst, up, dn, lof, lcap,
                                        with_rounds=True)
    np.testing.assert_array_equal(rate.astype(np.float32), ref)
    assert rounds == ref_rounds > 1


def test_waterfill_bit_exact_with_inf_caps():
    src, dst, up, dn, lof, lcap = _random_topology(80, 12, inf_caps=True)
    for impl in ("xla", "onehot"):
        out = swarm_ops._waterfill(src, dst, up, dn, None, None, impl)[0]
        np.testing.assert_array_equal(
            out.astype(np.float32), waterfill_jnp_ref(src, dst, up, dn)
        )


def test_waterfill_band_vs_numpy_refs():
    # cross-domain (XLA vs numpy) parity is a band, not bitwise: XLA:CPU
    # emits FMAs for the allocation updates. Observed max ~1.3e-6 relative.
    for trial in range(10):
        spine = trial % 2 == 1
        src, dst, up, dn, lof, lcap = _random_topology(
            16 * (trial + 1), 3 * (trial + 1), spine=spine
        )
        out = fleet_waterfill(src, dst, up, dn, lof, lcap)
        f32 = waterfill_f32_ref(src, dst, up, dn, lof, lcap)
        f64 = waterfill_rates(src, dst, up, dn, lof, lcap)
        np.testing.assert_allclose(out, f32, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(out, f64, rtol=1e-3, atol=1e-3)


def test_waterfill_empty_and_zero_cap():
    assert fleet_waterfill(
        np.zeros(0, np.int64), np.zeros(0, np.int64),
        np.ones(2), np.ones(2),
    ).size == 0
    for impl in ("xla", "onehot"):
        # zero-capacity uplink: all its flows freeze at 0 immediately
        out = swarm_ops._waterfill(
            np.zeros(4, np.int64), np.arange(1, 5),
            np.array([0.0, 10, 10, 10, 10]), np.full(5, 10.0), None, None,
            impl,
        )[0]
        np.testing.assert_array_equal(out, np.zeros(4))


@pytest.mark.parametrize("nf,nn,want", [
    (14_000, 2_001, "onehot"),     # the 2k crowd's largest
    (90_000, 16_385, "onehot"),    # the one-chip ImageNet cell's largest
    (200_000, 100_001, "onehot"),  # the 100k crowd's (pn 2^17)
    (1_000_000, 524_288, "onehot"),  # the last node count it takes
    (1_000_000, 524_289, "xla"),     # the first it leaves to the scatter
    (2_000_000, 1_000_001, "xla"),   # a 1M-client crowd (pn 2^20)
])
def test_waterfill_plan_picks_the_path_from_padded_shapes(nf, nn, want):
    assert swarm_ops.waterfill_plan(nf, nn, 0).impl == want


def _count_compiles(fn):
    import jax

    seen = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    return len(seen)


def test_waterfill_compiles_once_per_padded_shape():
    # flow counts inside one power-of-two bucket reuse one executable: a
    # fleet run sees a new flow count nearly every tick
    src, dst, up, dn, _, _ = _random_topology(300, 40)
    fleet_waterfill(src[:200], dst[:200], up, dn)  # warm the 256 bucket
    assert _count_compiles(lambda: [
        fleet_waterfill(src[:nf], dst[:nf], up, dn) for nf in (131, 199, 256)
    ]) == 0


# ------------------------------------------------------------------ device state


def test_device_state_tracks_incremental_updates():
    n, P = 50, 30
    jitter = RNG.random((n, P), dtype=np.float32)
    swarm_class = RNG.random(P) < 0.7
    dev = FleetDeviceState(jitter, swarm_class)
    have = np.zeros((n, P), dtype=bool)
    repl = np.zeros(P, dtype=np.int64)
    for _ in range(6):
        # unique (row, piece) pairs not yet held — the engine's completion
        # batches are duplicate-free by construction
        flat = np.unique(RNG.integers(0, n * P, RNG.integers(1, 12)))
        rows, pieces = flat // P, flat % P
        newly = ~have[rows, pieces]
        rows, pieces = rows[newly], pieces[newly]
        have[rows, pieces] = True
        np.add.at(repl, pieces, 1)
        dev.add_pieces(rows, pieces)
    np.testing.assert_array_equal(np.asarray(dev.have), have)
    np.testing.assert_array_equal(np.asarray(dev.repl), repl)
    # departures subtract the rows' held pieces
    drop = np.unique(RNG.integers(0, n, 7))
    repl -= have[drop].sum(axis=0)
    dev.drop_rows(drop)
    np.testing.assert_array_equal(np.asarray(dev.repl), repl)


def test_device_state_add_pieces_consumes_the_have_matrix():
    # one chip: each completion batch scatters into the donated have
    # matrix, so the previous one is gone, and the state still follows
    # the host's, an all-padding batch (the benchmark's warm-up) included
    n, P = 40, 150
    dev = FleetDeviceState(RNG.random((n, P), dtype=np.float32),
                           RNG.random(P) < 0.5)
    assert dev.devices == 1
    have = np.zeros((n, P), dtype=bool)
    repl = np.zeros(P, dtype=np.int64)
    flat = RNG.permutation(n * P)[:60]  # unique pairs, in 4 batches
    batches = [(flat[i:i + 15] // P, flat[i:i + 15] % P)
               for i in range(0, 60, 15)]
    batches.insert(2, (np.full(16, n), np.full(16, P)))  # all padding
    for rows, pieces in batches:
        old = dev.have_rows
        dev.add_pieces(rows, pieces)
        assert old.is_deleted()
        assert not dev.have_rows.is_deleted()
        held = rows < n
        have[rows[held], pieces[held]] = True
        np.add.at(repl, pieces[held], 1)
    np.testing.assert_array_equal(dev.have, have)
    np.testing.assert_array_equal(np.asarray(dev.repl), repl)


def test_device_select_compiles_once_per_row_bucket():
    n, P = 300, 45
    dev = FleetDeviceState(RNG.random((n, P), dtype=np.float32),
                           RNG.random(P) < 0.6)
    sel = functools.partial(dev.select, stream="swarm", mode="swarm_first",
                            fallback=True)
    sel(np.arange(140), np.full(140, -1))  # warm the 256-row bucket
    assert _count_compiles(lambda: [
        sel(np.arange(k), np.full(k, -1)) for k in (129, 200, 256)
    ]) == 0


def _engine_cand(have, repl, swarm_class, rows, other, stream, mode,
                 fallback):
    """The engine's numpy cand build (FleetSwarmSim._select)."""
    missing = ~have[rows]
    if stream == "http":
        if mode == "http_first":
            cand = missing.copy()
        else:
            cand = missing & ~swarm_class[None, :]
            if fallback:
                cand |= missing & swarm_class[None, :] & (repl == 0)[None, :]
    else:
        cand = missing & swarm_class[None, :] & (repl > 0)[None, :]
    has_other = other >= 0
    cand[np.flatnonzero(has_other), other[has_other]] = False
    return cand


HTTP_FALLBACK = ("http", "swarm_first", True)
HTTP_ORIGIN = ("http", "swarm_first", False)
HTTP_FIRST = ("http", "http_first", False)
SWARM = ("swarm", "swarm_first", True)


@pytest.mark.parametrize("stream,mode,fallback,k,P,ties,chunk", [
    # 20 distinct rows of 45 pieces, one case per class rule
    pytest.param(*HTTP_FALLBACK, 20, 45, False, None,
                 id="http-swarm_first-True"),
    pytest.param(*HTTP_ORIGIN, 20, 45, False, None,
                 id="http-swarm_first-False"),
    pytest.param(*HTTP_FIRST, 20, 45, False, None,
                 id="http-http_first-False"),
    pytest.param(*SWARM, 20, 45, False, None, id="swarm-swarm_first-True"),
    # row counts around the 128-row bucket and the rows per grid step
    # (128 at these widths), with duplicated rows and all-masked rows
    pytest.param(*SWARM, 1, 125, False, None, id="k1-P125"),
    pytest.param(*HTTP_FALLBACK, 127, 128, False, None, id="k127-P128"),
    pytest.param(*HTTP_FIRST, 128, 300, True, None, id="k128-P300-ties"),
    pytest.param(*HTTP_ORIGIN, 129, 45, True, None, id="k129-P45-ties"),
    # rows wider than one reduction chunk (64 x 128 pieces): one chunk
    # and a tail at 64 rows a step; exact (availability, jitter) ties
    # across chunks and row groups
    pytest.param(*HTTP_FIRST, 45, 9000, True, None, id="k45-P9000-ties"),
    # the same merges on narrower rows, with the kernel's chunk and 8 rows
    # a step given: three whole chunks of 8 sublanes, then a chunk of 16
    # and a tail of 8
    pytest.param(*SWARM, 200, 3000, True, 8, id="k200-P3000-chunk8-ties"),
    pytest.param(*HTTP_FALLBACK, 129, 2100, True, 16,
                 id="k129-P2100-chunk16-ties"),
])
def test_device_select_matches_engine_cand_build(stream, mode, fallback, k,
                                                 P, ties, chunk):
    n = 60
    swarm_class = RNG.random(P) < 0.6
    if ties:
        # rows 0 and 1 hold every piece and the other even rows the first
        # half, so the second half's pieces all have two replicas; with
        # two jitter values, exact ties run across chunks and picks land
        # past the first chunk
        jitter = (RNG.integers(0, 2, (n, P)) / 2).astype(np.float32)
        half = np.arange(2, n, 2)[:, None] * P + np.arange(P // 2)
        flat = np.union1d(np.arange(2 * P), half)
    else:
        jitter = RNG.random((n, P), dtype=np.float32)
        flat = np.unique(RNG.integers(0, n * P, 4 * P))  # unique pairs
        flat = np.union1d(flat, np.arange(2 * P))  # rows 0, 1 hold all
    dev = FleetDeviceState(jitter, swarm_class)
    have_rows, have_pieces = flat // P, flat % P
    dev.add_pieces(have_rows, have_pieces)
    have = np.zeros((n, P), dtype=bool)
    have[have_rows, have_pieces] = True
    repl = have.sum(axis=0)
    np.testing.assert_array_equal(dev.have, have)

    if k == 20:
        rows = np.unique(RNG.integers(0, n, k))
    else:  # with repeats, and the all-masked rows 0 and 1 among them
        rows = RNG.integers(0, n, k)
        rows[: min(k, 4)] = [0, 1, 0, 1][: min(k, 4)]
    # the other stream's piece: for a third of the rows the pick this
    # stream would make without it, for a third any piece, else none
    none = np.full(rows.size, -1)
    first = batched_rarest(
        _engine_cand(have, repl, swarm_class, rows, none, stream, mode,
                     fallback), repl, jitter[rows])
    draw = RNG.integers(0, 3, rows.size)
    other = np.select([draw == 0, draw == 1],
                      [first, RNG.integers(0, P, rows.size)], -1)
    cand = _engine_cand(have, repl, swarm_class, rows, other, stream, mode,
                        fallback)
    want = batched_rarest(cand, repl, jitter[rows])
    # the whole padded bucket, whose padding rows pick nothing: the select
    # program, or the kernel at the case's chunk and 8 rows a step
    kp = 1 << max(7, (rows.size - 1).bit_length())
    rows_p = np.full(kp, -1, dtype=np.int32)
    rows_p[: rows.size] = rows
    other_p = np.full(kp, -1, dtype=np.int32)
    other_p[: rows.size] = other
    rule = swarm_ops._select_rule(stream, mode, fallback)
    if chunk is None:
        full = swarm_ops._select_jit(rule, dev.interpret, dev.mesh)(
            dev.have_rows, dev.jitter_rows, dev.repl, dev.class_rows,
            rows_p, other_p)
    else:
        plan = swarm_ops.select_plan(P)
        repl_p = np.zeros(plan.width * 128, dtype=np.int32)
        repl_p[:P] = repl
        full = swarm_kernel.select_rows_call(
            dev.have_rows, dev.jitter_rows, repl_p.reshape(plan.width, 128),
            dev.class_rows, rows_p, other_p, n_pieces=P, rule=rule,
            rows_per_step=8, chunk=chunk,
            vmem_limit_bytes=plan.vmem_limit, interpret=dev.interpret)
    full = np.asarray(full)
    np.testing.assert_array_equal(full[: rows.size], want)
    assert (full[rows.size:] == -1).all()
    if k > 20:
        assert (want[np.isin(rows, [0, 1])] == -1).all()
    if chunk is None:  # the same program, through the engine's entry point
        np.testing.assert_array_equal(
            dev.select(rows, other, stream=stream, mode=mode,
                       fallback=fallback), want)


# ------------------------------------------------------------------ engine parity


def test_fleet_backend_pallas_raises_without_pallas(monkeypatch):
    # no Pallas in the installed jax -> the device backend fails loudly;
    # nothing turns backend="pallas" into another path
    from repro.core.fleet import FleetSpec, FleetSwarmSim
    from repro.core.metainfo import MetaInfo
    from repro.core.webseed import MirrorSpec

    for name in [m for m in sys.modules if m.startswith("repro.kernels")]:
        monkeypatch.delitem(sys.modules, name)
    monkeypatch.setitem(sys.modules, "jax.experimental.pallas", None)
    mi = MetaInfo.from_sizes_only(int(64e6), int(8e6), name="x")
    sim = FleetSwarmSim(mi, fleet=FleetSpec(backend="pallas"))
    sim.add_mirrors([MirrorSpec("origin", up_bps=50e6)])
    sim.add_peers([("p0", 0.0)], up_bps=25e6, down_bps=50e6)
    with pytest.raises(ImportError, match="pallas"):
        sim.run()
    assert sim.fleet_cfg.backend == "pallas"


# the spine case's cross-pod link: about a fifth of the crowd's 5 GB/s
# of uplinks, so that it binds while pieces circulate
SPINE_BPS = 1e9


def _smoke_sim(backend: str, spine: bool):
    """The smoke scenario downsized to 200 clients on ``backend``; with
    ``spine``, the clients alternate between two pods joined by a
    :data:`SPINE_BPS` link that every cross-pod and origin flow crosses."""
    from repro.core.fleet import FleetSwarmSim
    from repro.core.scenario import ScenarioSpec

    spec = json.loads((SCENARIOS / "fleet_smoke.json").read_text())
    spec["arrivals"][0]["n"] = 200
    spec["fleet"] = {"dt": 1.0, "fanout": None, "backend": backend}
    scenario = ScenarioSpec.from_dict(spec)
    if not spine:
        return scenario.build("fleet").sim
    mi, _ = scenario.content.manifests[0].build()
    sim = FleetSwarmSim(mi, scenario.policy, scenario.swarm,
                        fleet=scenario.fleet, seed=scenario.seed,
                        num_pods=2, spine_bps=SPINE_BPS)
    sim.add_mirrors(list(scenario.fabric.mirrors))
    group = scenario.arrivals[0]
    raw = group.generate()
    sim.add_peers(raw, up_bps=group.up_bps, down_bps=group.down_bps,
                  pods=np.arange(len(raw)) % 2)
    return sim


@pytest.mark.parametrize("spine", [False, True], ids=["flat", "spine"])
def test_fleet_backend_pallas_matches_numpy_engine(spine):
    """backend="pallas" (interpret) reproduces the numpy engine on the
    (downsized) smoke scenario, flat and across a binding spine link.

    Piece selection is index-exact, so the *byte ledgers* — who downloaded
    what, piece-granular — match exactly. Completion *times* are compared
    at the distribution level: the float32 water-fill rates sit ~1e-7
    relative off the float64 path, which integrates to tens of bytes per
    piece — more than the 1e-6-byte completion tolerance — so a piece
    landing within that sliver of a tick boundary can quantize one tick
    differently; the first such shift changes which rows hit the host-RNG
    rechoke draws, after which individual trajectories decorrelate while
    the aggregate completion profile stays tight.
    """
    results = {backend: _smoke_sim(backend, spine).run()
               for backend in ("numpy", "pallas")}
    ref, dev = results["numpy"], results["pallas"]
    assert ref.completed == dev.completed == dev.n == 200
    assert abs(dev.ticks - ref.ticks) <= max(5, 0.02 * ref.ticks)
    # piece-granular ledgers: every peer fetched every piece exactly once
    np.testing.assert_array_equal(dev.downloaded, ref.downloaded)
    np.testing.assert_allclose(
        dev.mirror_uploaded, ref.mirror_uploaded,
        atol=2 * 32e6, rtol=0.02,  # at most a couple of rescue pieces
    )
    # completion profile: distribution-level band (see docstring)
    for q in (50, 90, 99):
        lo = np.percentile(ref.durations, q)
        hi = np.percentile(dev.durations, q)
        assert abs(hi - lo) <= max(5 * dev.dt, 0.03 * lo), (q, lo, hi)
    assert abs(dev.uploaded_wire.sum() - ref.uploaded_wire.sum()) \
        <= 0.02 * ref.uploaded_wire.sum()
    assert set(dev.phase_seconds) == {
        "select", "waterfill", "bookkeeping", "telemetry"
    }
    if spine:
        # the link binds: on average over half full for the whole run
        # (0.63 on the numpy engine; 349 ticks without it, 381 with it)
        assert ref.spine_bytes > 0.5 * SPINE_BPS * ref.ticks * ref.dt
        assert abs(dev.spine_bytes - ref.spine_bytes) \
            <= 0.02 * ref.spine_bytes
    else:
        assert ref.spine_bytes == dev.spine_bytes == 0
