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
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as PS

from repro.kernels.checksum import device_checksum
from repro.kernels.swarm import kernel as swarm_kernel
from repro.kernels.swarm import ops as swarm_ops


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The fleet device state's mesh over the described host's four
    chips."""
    return Mesh(np.array(topo.devices), (swarm_ops.PEERS,))


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


def _select_lowered(n, P, k, mesh):
    """The device select program for ``n`` clients of ``P`` pieces over
    ``mesh`` and a ``k``-row bucket a chip (the HTTP stream's rule with
    the origin rescue)."""
    width = swarm_ops.select_plan(P).width
    fn = swarm_ops._select_jit("origin_or_unserved", False, mesh)
    by_row = NamedSharding(mesh, PS(swarm_ops.PEERS))
    whole = NamedSharding(mesh, PS())
    kb = k * mesh.size
    shapes = [((n, width, 128), jnp.uint8, by_row),
              ((n, width, 128), jnp.float32, by_row),
              ((P,), jnp.int32, whole), ((width, 128), jnp.int32, whole),
              ((kb,), jnp.int32, by_row), ((kb,), jnp.int32, by_row)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in shapes]
    return fn.lower(*args).compile()


def _mesh_of(sharding):
    return Mesh(np.array(list(sharding.device_set)), (swarm_ops.PEERS,))


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
    hlo = _select_lowered(100_000, 125, 131072, _mesh_of(one_chip)).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [2048, 16384])
def test_fleet_select_reads_rows_in_place(one_chip, k):
    # the ImageNet crowd: 16,384 clients of 37,504 pieces. The program
    # copies neither matrix nor gathers or pads (k, P) rows: besides its
    # parameters, no result holds more than one value per row or one per
    # (padded) piece, and its temporaries stay small
    n, P = 16384, 37504
    compiled = _select_lowered(n, P, k, _mesh_of(one_chip))
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    per_piece = swarm_ops.select_plan(P).width * 128
    for op, dims in _hlo_results(hlo):
        if op == "parameter":
            continue
        assert "gather" not in op, (op, dims)
        assert math.prod(dims) <= max(k, per_piece), (op, dims)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _state_args(mesh, n, P, *tail):
    """Shapes of the device state over ``mesh`` (have matrix by rows,
    replica counts on every chip), then ``tail`` int32 vectors on every
    chip."""
    width = swarm_ops.select_plan(P).width
    by_row = NamedSharding(mesh, PS(swarm_ops.PEERS))
    whole = NamedSharding(mesh, PS())
    return ([jax.ShapeDtypeStruct((n, width, 128), jnp.uint8,
                                  sharding=by_row),
             jax.ShapeDtypeStruct((P,), jnp.int32, sharding=whole)]
            + [jax.ShapeDtypeStruct((k,), jnp.int32, sharding=whole)
               for k in tail])


def test_fleet_select_sharded_compiles_on_2x2(four_chips):
    # the whole ImageNet crowd over four chips, 25,000 rows of 296 x 128
    # pieces a chip, and the 32,768-row bucket a chip: the select reads
    # rows in place on each chip (no gather, small temporaries) and, like
    # the completions scatter, needs no collective; departures add the
    # chips' sums with the path's one all-reduce
    n, P = 100_000, 37504
    width = swarm_ops.select_plan(P).width
    sel = _select_lowered(n, P, 32768, four_chips)
    hlo = sel.as_text()
    assert "tpu_custom_call" in hlo
    assert "gather" not in hlo and "all-reduce" not in hlo
    mem = sel.memory_analysis()
    assert mem.temp_size_in_bytes < 64 << 20
    # each chip holds its own quarter of have and jitter
    assert mem.argument_size_in_bytes < 25_000 * width * 128 * 5 + (8 << 20)
    add = swarm_ops._add_pieces_jit(four_chips).lower(
        *_state_args(four_chips, n, P, 131072, 131072)).compile()
    assert "all-reduce" not in add.as_text()
    # over four chips the scatter writes each chip's rows in place
    assert add.memory_analysis().alias_size_in_bytes >= 25_000 * width * 128
    drop = swarm_ops._drop_rows_jit(four_chips).lower(
        *_state_args(four_chips, n, P, 1024)).compile()
    assert drop.as_text().count("all-reduce(") == 1


def test_fleet_add_pieces_donates_on_one_chip(one_chip):
    # the one-chip ImageNet cell, 16,384 rows of 296 x 128 pieces: the
    # completions scatter writes the have matrix in place, as over four
    # chips, and needs no collective
    n, P = 16384, 37504
    width = swarm_ops.select_plan(P).width
    mesh = _mesh_of(one_chip)
    add = swarm_ops._add_pieces_jit(mesh).lower(
        *_state_args(mesh, n, P, 16384, 16384)).compile()
    assert "all-reduce" not in add.as_text()
    assert add.memory_analysis().alias_size_in_bytes >= n * width * 128


def _waterfill_compiled(plan, sharding):
    fn = swarm_ops._waterfill_jit(2 * plan.pn, plan.impl)
    flows = [((plan.pf,), jnp.int32)] * 3
    caps = [((plan.pn,), jnp.float32)] * 2 + [((plan.pnl,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in flows + caps]
    return fn.lower(*args).compile()


def _waterfill_hlo(plan, sharding):
    return _waterfill_compiled(plan, sharding).as_text()


def test_waterfill_at_100k_runs_the_onehot_contraction(one_chip):
    # ~700k flows over 100,001 nodes: pn 2^17 is within the one-hot
    # contraction's reach
    plan = swarm_ops.waterfill_plan(705_210, 100_001, 0)
    assert (plan.pf, plan.pn, plan.impl) == (1 << 20, 1 << 17, "onehot")
    hlo = _waterfill_hlo(plan, one_chip)
    assert "tpu_custom_call" not in hlo
    assert "scatter" not in {op for op, _ in _hlo_results(hlo)}


@pytest.mark.parametrize("pf,pn", [
    (1 << 17, 1 << 15),  # the one-chip ImageNet cell's largest table
    (1 << 18, 1 << 17),  # the 100k crowd's, on the four-chip cell's chip 0
])
def test_waterfill_onehot_contracts_without_scatters(one_chip, pf, pn):
    # every round's counts and lookups are contractions (convolutions)
    # whose one-hot operands are built inside their fusions: no scatter
    # or gather is left, and no (pf, pn / 128) incidence, 64 MiB and up
    # here, is kept in HBM
    plan = swarm_ops.WaterfillPlan(pf, pn, 128, "onehot")
    compiled = _waterfill_compiled(plan, one_chip)
    ops = {op for op, _ in _hlo_results(compiled.as_text())}
    assert "convolution" in ops
    assert "scatter" not in ops and "gather" not in ops
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_waterfill_kernel_compiles_where_the_plan_picks_it(one_chip):
    # the 2k-client crowd's largest table: ~14k flows over 2,001 nodes
    # compiles as the contraction, with no Pallas call and no scatter
    plan = swarm_ops.waterfill_plan(14_000, 2_001, 0)
    assert (plan.pf, plan.pn, plan.impl) == (1 << 14, 1 << 11, "onehot")
    compiled = _waterfill_compiled(plan, one_chip)
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo
    assert "scatter" not in {op for op, _ in _hlo_results(hlo)}
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_checksum_compiles_at_1gib(one_chip):
    fn = functools.partial(device_checksum, interpret=False)
    hlo = _compile(fn, [((1 << 28,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in hlo
