"""Compile the device path's kernels for one described TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached. This refuses what interpret mode
accepts — blocks that do not match the chip's tiling, primitives Mosaic
cannot lower, more VMEM or HBM than the chip has. Nothing runs, so the
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.checksum import device_checksum
from repro.kernels.swarm import kernel as swarm_kernel
from repro.kernels.swarm import ops as swarm_ops


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    mp.undo()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k,P,bp", [
    (131072, 128, 128),  # the largest row bucket, one piece tile
    (1024, 1024, 256),   # several piece tiles
])
def test_rarest_argmin_compiles(one_chip, k, P, bp):
    fn = functools.partial(
        swarm_kernel.rarest_argmin_call,
        block_rows=swarm_ops.BLOCK_ROWS, block_pieces=bp, interpret=False,
    )
    hlo = _compile(
        fn, [((k, P), jnp.bool_), ((P,), jnp.float32),
             ((k, P), jnp.float32)], one_chip,
    )
    assert "tpu_custom_call" in hlo


def _select_lowered(n, P, k, sharding):
    """The device select program for ``n`` clients of ``P`` pieces and a
    ``k``-row bucket (the HTTP stream's rule with the origin rescue)."""
    width = swarm_ops.select_plan(P).width
    fn = swarm_ops._select_jit("origin_or_unserved", False)
    shapes = [((n, width, 128), jnp.uint8), ((n, width, 128), jnp.float32),
              ((P,), jnp.int32), ((width, 128), jnp.int32),
              ((k,), jnp.int32), ((k,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return fn.lower(*args).compile()


def _hlo_results(hlo):
    """(opcode, dims) of every instruction in an HLO text dump."""
    inst = re.compile(r"=\s*\w+\[([\d,]*)\][^ ]*\s+([\w-]+)\(")
    for line in hlo.splitlines():
        m = inst.search(line)
        if m:
            dims = tuple(int(d) for d in m.group(1).split(",") if d)
            yield m.group(2), dims


def test_fleet_select_compiles_at_100k(one_chip):
    # the 100k-client crowd's largest bucket: its rows and picks go
    # through SMEM a grid step at a time, never all at once
    hlo = _select_lowered(100_000, 125, 131072, one_chip).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [2048, 16384])
def test_fleet_select_reads_rows_in_place(one_chip, k):
    # the ImageNet crowd: 16,384 clients of 37,504 pieces. The program
    # copies neither matrix nor gathers or pads (k, P) rows: besides its
    # parameters, no result holds more than one value per row or one per
    # (padded) piece, and its temporaries stay small
    n, P = 16384, 37504
    compiled = _select_lowered(n, P, k, one_chip)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    per_piece = swarm_ops.select_plan(P).width * 128
    for op, dims in _hlo_results(hlo):
        if op == "parameter":
            continue
        assert "gather" not in op, (op, dims)
        assert math.prod(dims) <= max(k, per_piece), (op, dims)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _waterfill_hlo(plan, sharding):
    fn = swarm_ops._waterfill_jit(2 * plan.pn, plan.impl, False)
    flows = [((plan.pf,), jnp.int32)] * 3
    caps = [((plan.pn,), jnp.float32)] * 2 + [((plan.pnl,), jnp.float32)]
    return _compile(fn, flows + caps, sharding)


def test_waterfill_at_100k_runs_the_xla_fixed_point(one_chip):
    # ~700k flows over 100,001 nodes: the kernel's table cannot fit VMEM
    plan = swarm_ops.waterfill_plan(705_210, 100_001, 0)
    assert (plan.pf, plan.pn, plan.impl) == (1 << 20, 1 << 17, "xla")
    assert "tpu_custom_call" not in _waterfill_hlo(plan, one_chip)


def test_waterfill_kernel_compiles_where_the_plan_picks_it(one_chip):
    # the 2k-client crowd's largest table: ~14k flows over 2,001 nodes
    plan = swarm_ops.waterfill_plan(14_000, 2_001, 0)
    assert plan.impl == "pallas"
    assert "tpu_custom_call" in _waterfill_hlo(plan, one_chip)


def test_checksum_compiles_at_1gib(one_chip):
    fn = functools.partial(device_checksum, interpret=False)
    hlo = _compile(fn, [((1 << 28,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in hlo
