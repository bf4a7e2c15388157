"""Pallas swarm kernels: masked rarest-argmin + max-min water-filling.

The fleet engine's two per-tick hot loops, device-shaped:

**Rarest-argmin** — piece selection over the ``(k, P)`` candidate matrix is
a masked lexicographic argmin of ``(availability, jitter, piece index)``.
The kernel tiles rows and pieces on a ``(row_blocks, piece_blocks)`` grid
(pieces innermost) and carries per-row running minima ``(min_avail,
min_jitter, min_index)`` in VMEM scratch across piece tiles. Availability
and jitter are never *added* (a float32 sum would quantize the jitter away
at large replica counts — see ``piece_selection.batched_rarest``); the
cross-tile merge is strictly-less lexicographic, so an earlier tile wins
exact ties and the result is the global first-occurrence argmin, making
parity with the numpy engine *index-exact*, not a tolerance band.

**Water-filling** — max-min progressive filling as a fixed-point
``lax.while_loop`` (all unfrozen flows grow equally until a node or
spine-link constraint saturates; flows through it freeze; repeat — at
least one constraint binds per round, so ``2*nodes + links + 2`` rounds
bound the loop and the early-exit fires long before). Two paths run it:

- the Pallas kernel keeps the whole flow table in VMEM and forms the
  per-round segment sums (active flows per node/link) and per-flow
  saturation gathers in flow tiles of ``block`` as one-hot matmuls —
  MXU-shaped, and exact even under bfloat16 MXU inputs because every
  operand is 0/1 or a small integer count with float32 accumulation. Its
  one-hot tiles grow with the node count, so it serves tables whose
  footprint (:func:`waterfill_vmem_bytes`) fits the VMEM budget;
- :func:`waterfill_xla` runs the same rounds as plain XLA ops in device
  memory (scatter-add segment sums, direct gathers) for larger tables.

Both produce bit-identical float32 results (all segment values are exact
integers, gathers touch one element), pinned by the parity suite.

Exactness contract: the bit-for-bit oracle is ``ref.waterfill_jnp_ref``
(:func:`waterfill_xla` on the unpadded table), which pins everything the
device paths add — tiling, padding, the dummy link slot, one-hot segment
math. The numpy transliteration ``ref.waterfill_f32_ref``
is ulp-close but *not* bitwise: XLA:CPU unconditionally contracts the
``alloc + count * delta`` multiply-adds into single-rounded FMAs
(``lax.optimization_barrier`` does not reach LLVM's codegen), while numpy
rounds the multiply and add separately.

Padding conventions (``ops.py`` supplies them): argmin pads rows/pieces
with ``cand=False``; water-filling pads flows with ``src = dst = -1``
(pre-frozen at rate 0, matching one-hot rows of zeros), nodes with zero
capacity and zero degree, and maps unlinked flows to a dummy link slot of
infinite capacity so the link channel always exists and the kernel takes
the same branches with and without a spine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain-float inf stays a weakly-typed literal (folds to float32 in
# kernel bodies without becoming a captured traced constant)
F32_INF = jnp.inf


# --------------------------------------------------------------------------- rarest-argmin


def _rarest_argmin_kernel(
    cand_ref, avail_ref, jit_ref, pick_ref, a_min, j_min, i_min,
    *, npb: int, bp: int
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        a_min[...] = jnp.full_like(a_min, F32_INF)
        j_min[...] = jnp.full_like(j_min, F32_INF)
        i_min[...] = jnp.full_like(i_min, -1)

    c = cand_ref[...]
    # stage 1: masked availability minimum per row within this piece tile;
    # every per-row value below is a (rows, 1) column
    a = jnp.where(c, avail_ref[...], F32_INF)
    tile_a = a.min(axis=1, keepdims=True)
    # stage 2: jitter among this tile's minimal-availability candidates
    # (the `c &` guard keeps inf==inf rows of all-masked tiles out)
    jm = jnp.where(c & (a == tile_a), jit_ref[...], F32_INF)
    tile_j = jm.min(axis=1, keepdims=True)
    # first occurrence of the minimum -> lowest piece index in the tile
    col = lax.broadcasted_iota(jnp.int32, jm.shape, 1)
    tile_i = jnp.where(jm == tile_j, col, bp).min(axis=1, keepdims=True)
    tile_i = tile_i + j * bp
    prev_a = a_min[...]
    prev_j = j_min[...]
    # strictly-less merge: on exact (avail, jitter) ties the earlier tile
    # (lower piece index) wins, matching the global first-occurrence argmin
    better = (tile_a < prev_a) | ((tile_a == prev_a) & (tile_j < prev_j))
    a_min[...] = jnp.where(better, tile_a, prev_a)
    j_min[...] = jnp.where(better, tile_j, prev_j)
    i_min[...] = jnp.where(better, tile_i, i_min[...])

    @pl.when(j == npb - 1)
    def _emit():
        pick_ref[...] = i_min[...]  # rows never updated keep the -1 init


def rarest_argmin_call(
    cand: jax.Array,
    avail: jax.Array,
    jitter: jax.Array,
    *,
    block_rows: int = 128,
    block_pieces: int = 256,
    interpret: bool,
):
    """``(k, P)`` bool candidates + ``(P,)`` float32 availability + ``(k, P)``
    float32 jitter -> ``(k,)`` int32 picks (``-1`` = no candidate).

    Shapes must already be multiples of the block sizes (``ops.py`` pads);
    traceable, so it composes under ``jax.jit``. Every block is 2-D:
    availability enters as one ``(1, P)`` row and picks leave as a
    ``(k, 1)`` column, so each block matches the TPU's native tiling.
    """
    k, P = cand.shape
    assert k % block_rows == 0 and P % block_pieces == 0
    nkb, npb = k // block_rows, P // block_pieces
    kernel = functools.partial(
        _rarest_argmin_kernel, npb=npb, bp=block_pieces
    )
    col = (block_rows, 1)
    picks = pl.pallas_call(
        kernel,
        grid=(nkb, npb),
        in_specs=[
            pl.BlockSpec((block_rows, block_pieces), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_pieces), lambda i, j: (0, j)),
            pl.BlockSpec((block_rows, block_pieces), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec(col, lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM(col, jnp.float32),
            pltpu.VMEM(col, jnp.float32),
            pltpu.VMEM(col, jnp.int32),
        ],
        interpret=interpret,
    )(cand, avail.reshape(1, P), jitter)
    return picks[:, 0]


# --------------------------------------------------------------------------- water-filling


def _fill_round(counts, alloc, caps):
    """One progressive-filling round at the constraints, shared by the
    kernel and :func:`waterfill_xla` so both run the same float ops.

    ``counts`` / ``alloc`` / ``caps`` are ``(up, down, link)`` triples of
    active-flow counts, allocated rate and capacity per constraint. Returns
    the round's growth ``delta``, whether it was finite, the new
    allocations, and the 0/1 float saturation mask of every constraint.
    """
    ds = [
        jnp.where(n > 0, (c - a) / n, F32_INF)
        for n, a, c in zip(counts, alloc, caps)
    ]
    delta = jnp.minimum(jnp.minimum(ds[0].min(), ds[1].min()), ds[2].min())
    # a non-finite delta means no active flow touches any finite capacity;
    # delta = 0 then makes every update an exact no-op and the loop exits
    ok = delta < F32_INF
    delta = jnp.where(ok, jnp.maximum(delta, jnp.float32(0.0)), 0.0)
    alloc = tuple(a + n * delta for a, n in zip(alloc, counts))
    tol = delta + jnp.float32(1e-6)
    sats = tuple(
        ((d <= tol) & (n > 0)).astype(jnp.float32)
        for d, n in zip(ds, counts)
    )
    return delta, ok, alloc, sats


def _waterfill_kernel(
    src_ref, dst_ref, lnk_ref, up_ref, dn_ref, lcap_ref,
    rate_ref, rounds_ref, frozen_ref, *, n_iter: int
):
    nt, block = src_ref.shape
    idx_refs = (src_ref, dst_ref, lnk_ref)
    caps = (up_ref[...], dn_ref[...], lcap_ref[...])  # (1, width) rows
    widths = tuple(c.shape[1] for c in caps)

    def tile(ref, t):
        return ref[pl.ds(t, 1), :]  # one (1, block) row of flows

    def onehot(idx, width):
        # (1, block) indices -> (width, block) 0/1, flows along lanes;
        # -1 padding matches no row
        rows = lax.broadcasted_iota(jnp.int32, (width, block), 0)
        return (rows == idx).astype(jnp.float32)

    def seg_sum(w, idx, width):  # (1, block) weights -> (1, width) sums
        return lax.dot_general(
            w, onehot(idx, width), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def gather(v, idx, width):  # (1, width) values -> (1, block)
        return jnp.dot(
            v, onehot(idx, width), preferred_element_type=jnp.float32
        )

    rate_ref[...] = jnp.zeros_like(rate_ref)
    # padded flows (src = -1) start frozen at rate 0
    frozen_ref[...] = (src_ref[...] < 0).astype(jnp.float32)

    def body(state):
        it, _, alloc = state

        def count(t, acc):
            act = 1.0 - tile(frozen_ref, t)
            return tuple(
                a + seg_sum(act, tile(r, t), w)
                for a, r, w in zip(acc, idx_refs, widths)
            )

        zeros = tuple(jnp.zeros((1, w), jnp.float32) for w in widths)
        counts = lax.fori_loop(0, nt, count, zeros)
        delta, ok, alloc, sats = _fill_round(counts, alloc, caps)

        def advance(t, carry):
            any_new, any_live = carry
            fr = tile(frozen_ref, t)
            act = 1.0 - fr
            rate_ref[pl.ds(t, 1), :] = tile(rate_ref, t) + act * delta
            hit = sum(
                gather(s, tile(r, t), w)
                for s, r, w in zip(sats, idx_refs, widths)
            )
            newly = jnp.where(hit > 0, act, 0.0)
            fr = fr + newly
            frozen_ref[pl.ds(t, 1), :] = fr
            return (
                jnp.maximum(any_new, newly.max()),
                jnp.maximum(any_live, (1.0 - fr).max()),
            )

        any_new, any_live = lax.fori_loop(
            0, nt, advance, (jnp.float32(0.0), jnp.float32(0.0))
        )
        stop = jnp.logical_not(ok & (any_new > 0)) | (any_live == 0)
        return it + 1, stop.astype(jnp.int32), alloc

    def cond(state):
        it, stop, _ = state
        return (stop == 0) & (it < n_iter)

    alloc0 = tuple(jnp.zeros((1, w), jnp.float32) for w in widths)
    it, _, _ = lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(0), alloc0))
    rounds_ref[0] = it


def waterfill_vmem_bytes(pf: int, pn: int, pnl: int, block: int) -> int:
    """Upper bound on the water-fill kernel's VMEM footprint.

    The kernel holds the whole flow table in VMEM: three index rows, the
    rates and the frozen mask (each ``pf`` 32-bit words, inputs and
    outputs double-buffered), the node and link rows (padded to 8
    sublanes), and per flow tile the one-hot ``(width, block)`` float32
    operands with their int32 iotas. Computed from shapes alone, so
    ``fleet_waterfill`` can choose its path before anything compiles.
    """
    flows = (2 * 4 + 1) * 4 * pf
    rows = 16 * 8 * 4 * (2 * pn + pnl)
    tiles = 3 * 2 * 4 * block * (2 * pn + pnl)
    return flows + rows + tiles


def waterfill_call(
    src: jax.Array,
    dst: jax.Array,
    lnk: jax.Array,
    up_cap: jax.Array,
    down_cap: jax.Array,
    link_cap: jax.Array,
    *,
    n_iter: int,
    block: int,
    vmem_limit_bytes: int,
    interpret: bool,
):
    """Padded flow table -> ``((pf,) float32 rates, (1,) int32 rounds)``.

    ``src``/``dst``/``lnk`` are int32 node/link indices per flow (``-1``
    src/dst = padding; ``lnk`` already maps unlinked flows to the dummy
    slot). The fixed point is sequential, so the kernel is single-program
    (no pallas grid): the whole table sits in VMEM as ``(pf // block,
    block)`` rows, and each round walks the rows, forming per-node and
    per-link segment sums and saturation gathers as one-hot matmuls.
    """
    pf = src.shape[0]
    assert pf % block == 0
    nt = pf // block
    flows = lambda x: x.reshape(nt, block)  # noqa: E731
    row = lambda x: x.reshape(1, x.shape[0])  # noqa: E731
    rate, rounds = pl.pallas_call(
        functools.partial(_waterfill_kernel, n_iter=n_iter),
        out_shape=(
            jax.ShapeDtypeStruct((nt, block), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        scratch_shapes=[pltpu.VMEM((nt, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes
        ),
        interpret=interpret,
    )(flows(src), flows(dst), flows(lnk),
      row(up_cap), row(down_cap), row(link_cap))
    return rate.reshape(pf), rounds


def waterfill_xla(src, dst, lnk, up_cap, down_cap, link_cap, *, n_iter: int):
    """The kernel's fixed point as plain XLA ops: scatter-add segment
    sums and direct gathers, over 1-D arrays in device memory, with no
    VMEM bound. Takes the kernel's padded table (``-1`` flows start
    frozen) or an unpadded one; returns ``((nf,) rates, rounds)``.
    """
    caps = (up_cap, down_cap, link_cap)
    idx = (src, dst, lnk)

    def body(state):
        rate, frozen, alloc, it, _ = state
        act = (~frozen).astype(jnp.float32)
        # -1 padding wraps to the last slot, where it adds 0
        counts = tuple(
            jnp.zeros(c.shape[0], jnp.float32).at[i].add(act)
            for c, i in zip(caps, idx)
        )
        delta, ok, alloc, sats = _fill_round(counts, alloc, caps)
        rate = rate + act * delta
        hit = sats[0][src] + sats[1][dst] + sats[2][lnk]
        newly = (~frozen) & (hit > 0)
        frozen = frozen | newly
        stop = ~(ok & newly.any()) | frozen.all()
        return rate, frozen, alloc, it + 1, stop

    def cond(state):
        *_, it, stop = state
        return (~stop) & (it < n_iter)

    init = (
        jnp.zeros(src.shape[0], jnp.float32),
        src < 0,
        tuple(jnp.zeros(c.shape[0], jnp.float32) for c in caps),
        jnp.int32(0),
        jnp.asarray(False),
    )
    rate, _, _, it, _ = lax.while_loop(cond, body, init)
    return rate, it.reshape(1)
