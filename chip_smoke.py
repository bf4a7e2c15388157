"""Chip smoke: drive the system's device path once on a TPU and check it.

Run from the repository root, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips

One chip: the committed ``benchmarks/scenarios/fleet_scaling.json`` flash
crowd at 100,000 clients on ``FleetSpec(backend="pallas")``, built through
``ScenarioSpec.build("fleet")`` and run to completion. The outcome must
match the pinned float64 row ``scaling/fleet_n100000`` of
``BENCH_swarm_scaling.json`` within the engine-parity bands: every client
done, ticks within ``max(5, 2%)``, origin bytes within 2%.

Four chips: a checkpoint bundle the size of ``granite_3_2b``'s bf16
parameters, made from a seed, striped over a ``(4, 1)`` ``("data",
"model")`` mesh and all-gathered (``broadcast_bundle``). Every replica's
device checksum, taken on its own device, must equal the payload's, and
one replica's bytes must equal the payload on the host.

Every phase runs in this one process. The script fails, and falls back to
nothing, where JAX finds no TPU. Its last line of output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path
from time import perf_counter

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.accel import enable_compile_cache  # noqa: E402

SCENARIO = ROOT / "benchmarks" / "scenarios" / "fleet_scaling.json"
GOLDENS = ROOT / "BENCH_swarm_scaling.json"
N_PEERS = 100_000
SIZE = 4e9  # the scenario's manifest, bytes


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def reference_row(n: int) -> dict:
    """The pinned float64 outcome of the ``n``-client fleet crowd."""
    rows = json.loads(GOLDENS.read_text())["rows"]
    derived = next(
        r["derived"] for r in rows if r["name"] == f"scaling/fleet_n{n}"
    )
    nums = dict(re.findall(r"(\w+)=([\d.]+)", derived))
    return {"ticks": int(nums["ticks"]), "copies": float(nums["copies"])}


def fleet_crowd(n: int = N_PEERS) -> dict:
    """Run the flash crowd of ``n`` clients on ``backend="pallas"`` and
    check it against its pinned row; raises on any miss."""
    from repro.core.scenario import ScenarioSpec
    from repro.kernels.swarm.ops import waterfill_plan

    spec = json.loads(SCENARIO.read_text())
    spec["arrivals"][0]["n"] = n
    spec["fleet"]["backend"] = "pallas"
    compiles = []

    def on_compile(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((fun_name, secs))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        t0 = perf_counter()
        compiled = ScenarioSpec.from_dict(spec).build("fleet")
        res = compiled.run().primary
        wall = perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    dev = compiled.sim.device
    ref = reference_row(n)
    compile_s = sum(secs for _, secs in compiles)
    by_program = {}
    for name, secs in compiles:
        count, total = by_program.get(name, (0, 0.0))
        by_program[name] = (count + 1, total + secs)
    out = {
        "n": n,
        "ticks": res.ticks,
        "ref_ticks": ref["ticks"],
        "done": res.completed,
        "copies": res.origin_uploaded / SIZE,
        "ref_copies": ref["copies"],
        "wall_s": wall,
        "compile_s": compile_s,
        "compiles": len(compiles),
        "steady_s": wall - compile_s,
        "waterfill_runs": dev.waterfill_runs,
        "waterfill_rounds": dev.rounds,
        "peak_flows": dev.peak_flows,
        "phase_s": res.phase_seconds,
    }
    print("fleet " + " ".join(f"{k}={v}" for k, v in out.items()), flush=True)
    print("compiles by program (count, seconds): " + " ".join(
        f"{k}={c},{s:.2f}" for k, (c, s) in sorted(by_program.items())
    ), flush=True)
    # the water-fill path is chosen from padded shapes alone
    plan = waterfill_plan(dev.peak_flows, n + len(spec["fabric"]["mirrors"]), 0)
    print(f"waterfill at peak flows: pf={plan.pf} pn={plan.pn} "
          f"-> {plan.impl}", flush=True)
    check(res.completed == n, f"{res.completed}/{n} clients done")
    check(abs(res.ticks - ref["ticks"]) <= max(5, 0.02 * ref["ticks"]),
          f"ticks {res.ticks} vs pinned {ref['ticks']}")
    check(abs(out["copies"] - ref["copies"]) <= 0.02 * ref["copies"],
          f"origin copies {out['copies']} vs pinned {ref['copies']}")
    return out


def bundle_broadcast(nbytes: int | None = None, seed: int = 0) -> dict:
    """Stripe + all-gather a seeded bundle over four devices and verify
    every replica on its own device; raises on any miss."""
    from jax.sharding import Mesh

    from repro.configs.registry import get_config
    from repro.core.collective_fabric import (
        LANES, broadcast_bundle, bundle_to_bytes, stripe_shards,
    )
    from repro.kernels.checksum import device_checksum

    if nbytes is None:  # bf16 parameters of the granite_3_2b config
        nbytes = 2 * get_config("granite_3_2b").param_count()[0]
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"need 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices).reshape(4, 1), ("data", "model"))
    t0 = perf_counter()
    payload = np.random.default_rng(seed).bytes(nbytes)
    t_gen = perf_counter() - t0
    # the payload's checksum, over the same padded stripes, on one device
    t0 = perf_counter()
    padded = np.stack(stripe_shards(payload, 4)).reshape(4, -1, LANES)
    want = np.asarray(device_checksum(jax.device_put(padded, devices[0])))
    del padded
    t_want = perf_counter() - t0
    t0 = perf_counter()
    replicated, length = broadcast_bundle(payload, mesh, "data")
    replicated.block_until_ready()
    t_gather = perf_counter() - t0
    shards = replicated.addressable_shards
    t0 = perf_counter()
    sums = [np.asarray(device_checksum(s.data)) for s in shards]
    t_sums = perf_counter() - t0
    t0 = perf_counter()
    same_bytes = bundle_to_bytes(replicated, length) == payload
    t_host = perf_counter() - t0
    out = {
        "bytes": nbytes,
        "replicas": len(shards),
        "devices": len({s.device for s in shards}),
        "full_replicas": all(s.data.shape == replicated.shape for s in shards),
        "payload_checksum": want.tolist(),
        "replica_checksums": [c.tolist() for c in sums],
        "host_bytes_equal": same_bytes,
        "gen_s": t_gen, "payload_checksum_s": t_want,
        "broadcast_s": t_gather, "replica_checksums_s": t_sums,
        "host_compare_s": t_host,
    }
    print("broadcast " + " ".join(f"{k}={v}" for k, v in out.items()),
          flush=True)
    check(out["devices"] == 4 and out["full_replicas"],
          "the bundle is not replicated whole on 4 distinct devices")
    check(all((c == want).all() for c in sums), "replica checksum mismatch")
    check(same_bytes, "replica bytes differ from the payload")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform})")
    print(f"cache {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        bundle_broadcast()
    else:
        fleet_crowd()
        stats = devices[0].memory_stats() or {}
        print(f"device peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
