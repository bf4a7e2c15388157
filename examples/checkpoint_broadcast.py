"""Checkpoint distribution three ways: origin-only vs swarm vs
collective-assisted (ICI all-gather) — the paper's Table-1 economics
applied to model weights.

Run:  PYTHONPATH=src python examples/checkpoint_broadcast.py
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.core import (
    ClusterTopology, LocalSwarm, MetaInfo, broadcast_bundle, bundle_to_bytes,
    coldstart_time, stripe_shards,
)
from repro.kernels.checksum import device_checksum, verify_replicas
from repro.launch.mesh import make_test_mesh


def main() -> None:
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, 4 << 20, np.uint8).tobytes()  # 4 MB demo
    mi = MetaInfo.from_bytes(payload, 1 << 16, name="ckpt_demo_0")
    print(f"bundle: {mi.length/1e6:.1f} MB, {mi.num_pieces} pieces")

    print("\n--- functional swarm broadcast to 8 hosts (verified bytes) ---")
    t0 = time.perf_counter()
    swarm = LocalSwarm(mi, dict(mi.split_pieces(payload)),
                       [f"host{i}" for i in range(8)], seed=0)
    rounds = swarm.run()
    print(f"rounds={rounds} origin_served={swarm.origin.ledger.uploaded/1e6:.1f}MB "
          f"ud={swarm.ud_ratio:.1f} wall={time.perf_counter()-t0:.2f}s")

    print("\n--- collective-assisted: stripe + all-gather on a jax mesh ---")
    n_dev = len(jax.devices())
    mesh = make_test_mesh((n_dev, 1), ("data", "model"))
    replicated, ln = broadcast_bundle(payload, mesh, "data")
    assert bundle_to_bytes(replicated, ln) == payload
    # each replica is checked on its own device against the checksum of
    # the payload's stripes; interpret mode is resolved from the platform
    stripes = np.stack(stripe_shards(payload, n_dev)).reshape(n_dev, -1, 128)
    want = device_checksum(jax.numpy.asarray(stripes))
    sums = [device_checksum(s.data) for s in replicated.addressable_shards]
    print(f"replicated on {n_dev} device(s); payload checksum="
          f"{np.asarray(want)} replicas_match={verify_replicas([want, *sums])}")

    print("\n--- projected wall times, 512-host fleet, 1 TB checkpoint ---")
    topo = ClusterTopology(num_pods=2, hosts_per_pod=256)
    for strat in ("origin_only", "swarm", "collective"):
        est = coldstart_time(topo, 1e12, strat)
        print(f"{strat:12s} t={est.seconds:8.1f}s  origin_egress="
          f"{est.origin_bytes/1e12:7.2f} TB")


if __name__ == "__main__":
    main()
