"""Collective-assisted distribution (beyond-paper, DESIGN.md §2).

The paper's insight — "downloaders re-serve, so the origin uploads ~one
copy" — has a degenerate, *faster* form inside a pod: fetch a distinct
1/N stripe of the bundle to each host (origin uploads exactly one copy,
like a fully-efficient swarm), then replicate pod-wide with one ICI
all-gather. The interconnect performs the swarm's amplification in a single
collective instead of O(N log N) piece exchanges.

Two layers here:

* a **time model** (`coldstart_time`) comparing origin-only / swarm /
  stripe+all-gather for a cluster cold start (benchmarked in
  ``benchmarks/bench_cluster_coldstart.py``);
* a **functional JAX path** (`stripe_shards` / `allgather_bundle`) used by
  checkpoint broadcast: the bundle lives as a uint8 array sharded across the
  'data' axis, and one `jax.lax.all_gather` replicates it. Works on any
  mesh; on TPU the gather rides the ICI rings. Each stripe is laid out as
  ``(rows, 128)`` bytes with ``rows`` a multiple of 32, the TPU's byte
  tile, so no replica carries layout padding.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .topology import ClusterTopology


@dataclasses.dataclass(frozen=True)
class ColdstartEstimate:
    strategy: str
    origin_bytes: float
    seconds: float


def coldstart_time(
    topo: ClusterTopology,
    size_bytes: float,
    strategy: str,
    swarm_efficiency: float = 0.85,
) -> ColdstartEstimate:
    """Analytic cold-start time for distributing ``size_bytes`` to every host.

    origin_only:    every host pulls the full bundle from the origin;
                    origin egress is the bottleneck.
    swarm:          origin uploads ~1 copy; the swarm pipelines pieces, so
                    steady-state per-host rate approaches
                    ``swarm_efficiency x`` min(host NIC, aggregate fair
                    share); time ~ max(1-copy origin time, piece-pipelined
                    replication time).
    collective:     stripe 1/N per host over DCN, then ICI all-gather
                    within each pod + one cross-pod swarm/relay of stripes.
    """
    n = topo.num_hosts
    if strategy == "origin_only":
        t = size_bytes * n / topo.origin_up_bps
        t = max(t, size_bytes / topo.host_down_bps)
        return ColdstartEstimate(strategy, size_bytes * n, t)
    if strategy == "swarm":
        t_origin = size_bytes / topo.origin_up_bps  # one copy out of the origin
        per_host = min(topo.host_down_bps, topo.host_up_bps) * swarm_efficiency
        t_replicate = size_bytes / per_host
        return ColdstartEstimate(strategy, size_bytes, max(t_origin, t_replicate))
    if strategy == "collective":
        stripe = size_bytes / n
        t_stripe = max(
            size_bytes / topo.origin_up_bps,  # origin still ships one copy total
            stripe / topo.host_down_bps,
        )
        # ring all-gather within a pod: each host receives (H-1)/H of the pod
        # bundle over ICI; pods exchange their missing stripes over DCN.
        h = topo.hosts_per_pod
        t_ici = size_bytes * (h - 1) / h / topo.ici_bps_per_host
        t_xpod = 0.0
        if topo.num_pods > 1:
            cross = size_bytes * (topo.num_pods - 1) / topo.num_pods / topo.num_pods
            t_xpod = cross / (topo.host_up_bps / topo.cross_pod_penalty)
        return ColdstartEstimate(strategy, size_bytes, t_stripe + t_ici + t_xpod)
    raise ValueError(f"unknown strategy {strategy!r}")


# --------------------------------------------------------------------------- functional path


LANES = 128
STRIPE_ALIGN = 32 * LANES  # one (32, 128) uint8 tile


def stripe_shards(payload: bytes, n: int) -> list[np.ndarray]:
    """Split a bundle into n equal uint8 stripes (zero-padded tail), each
    a whole number of ``STRIPE_ALIGN``-byte tiles."""
    pad = (-len(payload)) % (n * STRIPE_ALIGN)
    buf = np.frombuffer(payload + b"\x00" * pad, dtype=np.uint8)
    return list(buf.reshape(n, -1))


def allgather_bundle(striped: jax.Array, mesh: jax.sharding.Mesh, axis: str) -> jax.Array:
    """Replicate a host-striped uint8 bundle via one all-gather over ``axis``.

    ``striped`` is sharded over ``axis`` on its leading (stripe) dimension;
    the result is fully replicated — every device (host) holds the whole
    bundle.
    """

    def gather(x):
        return jax.lax.all_gather(x, axis, axis=0, tiled=True)

    fn = jax.shard_map(
        gather,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)(striped)


def broadcast_bundle(
    payload: bytes, mesh: jax.sharding.Mesh, axis: str
) -> tuple[jax.Array, int]:
    """End-to-end: stripe -> place sharded -> all-gather. Returns
    (replicated uint8 array of shape (n, rows, 128), original length)."""
    n = mesh.shape[axis]
    stripes = np.stack(stripe_shards(payload, n)).reshape(n, -1, LANES)
    sharding = NamedSharding(mesh, P(axis))
    placed = jax.device_put(stripes, sharding)
    return allgather_bundle(placed, mesh, axis), len(payload)


def bundle_to_bytes(replicated: jax.Array, length: int) -> bytes:
    return np.asarray(replicated).reshape(-1).tobytes()[:length]
