"""The fleet engine's device state split over four chips' rows gives what
one chip gives, bit for bit, on four virtual CPU devices (a subprocess
for its own device count; Pallas in interpret mode).

A 90-client crowd (not a multiple of 4, so the last chip holds padding
rows) of 300 pieces, with seeds departing 3 s after they finish, runs
tick by tick with ``devices=4`` and ``devices=1`` from one seed. After
every tick the calls' picks, the device have matrix and replica counts,
and the completion and departure times must agree exactly, until the
last client finishes; then the outcomes
must match the float64 numpy backend's within the band that
``test_kernels_swarm.test_fleet_backend_pallas_matches_numpy_engine``
gives the float32 water-fill.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = r"""
import json, sys
import numpy as np
import jax
from repro.core.scenario import ScenarioSpec

assert jax.device_count() == 4, jax.devices()
spec = json.loads(open({scenario!r}).read())
spec["content"]["manifests"][0].update(size_bytes=300 * 4_000_000,
                                       piece_length=4_000_000)
spec["arrivals"][0].update(n=90, seed_linger=3.0)


def build(backend, devices=1):
    spec["fleet"] = {{"dt": 1.0, "fanout": None, "backend": backend,
                     "devices": devices}}
    return ScenarioSpec.from_dict(spec).build("fleet").sim


def logged(sim, calls):
    select = sim.device.select

    def wrapped(rows, other, **kw):
        pick = select(rows, other, **kw)
        calls.append((rows.copy(), other.copy(), kw, pick.copy(),
                      sim.device.shard_rows_max))
        return pick

    sim.device.select = wrapped


one, four = build("pallas"), build("pallas", 4)
one.run(until=0.0)
four.run(until=0.0)
assert (four.device.devices, four.device.rows_per_device,
        four.device.n_pad) == (4, 23, 92)
calls1, calls4 = [], []
logged(one, calls1)
logged(four, calls4)
idle_chip = drops = 0
drop = four.device.drop_rows


def counted(rows):
    global drops
    drops += 1
    drop(rows)


four.device.drop_rows = counted
t = 0.0
while not np.isfinite(one.completed_at).all():
    t += 1.0
    r1, r4 = one.run(until=t), four.run(until=t)
    assert len(calls1) == len(calls4), t
    for a, b in zip(calls1, calls4):
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        assert a[2] == b[2]
        assert np.array_equal(a[3], b[3]), ("picks", t)
        owners = np.bincount(b[0] // 23, minlength=4)
        assert b[4] == owners.max()
        idle_chip += bool((owners == 0).any())
    calls1.clear()
    calls4.clear()
    assert np.array_equal(one.device.have, four.device.have), ("have", t)
    assert np.array_equal(np.asarray(one.device.repl),
                          np.asarray(four.device.repl)), ("repl", t)
    assert np.array_equal(one.completed_at, four.completed_at), t
    assert np.array_equal(one.departed, four.departed), t
    assert np.array_equal(one.departed_at, four.departed_at), t
    assert t < 400, "the crowd never finished"
ref = build("numpy").run()
print(json.dumps({{
    "ticks": [ref.ticks, r1.ticks, r4.ticks],
    "completed": [int(ref.completed), int(r1.completed), int(r4.completed)],
    "downloaded": [ref.downloaded.tolist(), r4.downloaded.tolist()],
    "origin": [ref.mirror_uploaded.tolist(), r4.mirror_uploaded.tolist()],
    "durations": [ref.durations.tolist(), r4.durations.tolist()],
    "same_result": bool(
        np.array_equal(r1.completed_at, r4.completed_at)
        and np.array_equal(r1.downloaded, r4.downloaded)
        and np.array_equal(r1.uploaded_wire, r4.uploaded_wire)
        and np.array_equal(r1.mirror_uploaded, r4.mirror_uploaded)),
    "idle_chip_calls": idle_chip, "drops": drops,
    "departed": int(four.departed.sum()),
}}))
"""


def test_four_chip_rows_match_one_chip_and_the_numpy_engine():
    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    scenario = ROOT / "benchmarks" / "scenarios" / "fleet_smoke.json"
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(scenario=str(scenario))],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["same_result"]
    # some call selected no row of some chip, and departures reached the
    # device state
    assert got["idle_chip_calls"] > 0
    assert got["drops"] > 0 and got["departed"] > 0
    ref_ticks, ticks1, ticks4 = got["ticks"]
    assert ticks1 == ticks4
    assert got["completed"] == [90, 90, 90]
    assert abs(ticks4 - ref_ticks) <= max(5, 0.02 * ref_ticks)
    ref, dev = (np.asarray(v) for v in got["downloaded"])
    np.testing.assert_array_equal(dev, ref)
    ref, dev = (np.asarray(v) for v in got["origin"])
    np.testing.assert_allclose(dev, ref, atol=2 * 4e6, rtol=0.02)
    ref, dev = (np.asarray(v) for v in got["durations"])
    for q in (50, 90, 99):
        lo, hi = np.percentile(ref, q), np.percentile(dev, q)
        assert abs(hi - lo) <= max(5.0, 0.03 * lo), (q, lo, hi)
