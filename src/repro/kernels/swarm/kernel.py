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

The fleet engine's device select (:func:`select_rows_call`) runs the same
tie-break without a ``(k, P)`` candidate matrix: one call copies each
selected row of the device-resident have and jitter matrices from HBM,
whole, into VMEM (the next rows' copies overlap these rows' reduction),
builds the row's candidate mask there from the stream's class rule, the
replica counts and the other stream's current piece, and reduces it with
the shared per-tile minimum (:func:`_lex_min`) and strictly-less merge
(:func:`_lex_merge`).

**Water-filling** — max-min progressive filling as a fixed-point
``lax.while_loop`` (all unfrozen flows grow equally until a node or
spine-link constraint saturates; flows through it freeze; repeat — at
least one constraint binds per round, so ``2*nodes + links + 2`` rounds
bound the loop and the early-exit fires long before). Two paths run it,
both plain XLA ops in device memory:

- :func:`waterfill_onehot` forms each round's segment sums (active flows
  per node/link) and per-flow saturation lookups as contractions of
  two-level one-hot incidences on the MXU (``pf * pn`` work a round),
  for tables over up to ``ops.ONEHOT_MAX_NODES`` padded nodes;
- :func:`waterfill_xla` runs them with per-flow scatter-adds and
  gathers (``pf`` element-wise work a round) for tables over more nodes.

Both produce bit-identical float32 results (all segment values are exact
integers, gathers touch one element), pinned by the parity suite.

Exactness contract: the bit-for-bit oracle is ``ref.waterfill_jnp_ref``
(:func:`waterfill_xla` on the unpadded table), which pins everything the
device paths add — padding, the dummy link slot, one-hot segment math.
The numpy transliteration ``ref.waterfill_f32_ref`` is ulp-close but
*not* bitwise: XLA:CPU unconditionally contracts the
``alloc + count * delta`` multiply-adds into single-rounded FMAs
(``lax.optimization_barrier`` does not reach LLVM's codegen), while numpy
rounds the multiply and add separately.

Padding conventions (``ops.py`` supplies them): argmin pads rows/pieces
with ``cand=False``; water-filling pads flows with ``src = dst = -1``
(pre-frozen at rate 0, matching one-hot rows of zeros), nodes with zero
capacity and zero degree, and maps unlinked flows to a dummy link slot of
infinite capacity so the link channel always exists and the fixed point
takes the same branches with and without a spine.
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

# index sentinel above any piece index (not a valid pick)
IDX_SENTINEL = 2**30
LANES = 128


def _lex_min(c, avail, jit, idx, axis):
    """Lexicographic minimum of ``(avail, jitter, index)`` over the
    candidates ``c`` along ``axis``, kept as size-1 dims.

    Availability and jitter are compared in stages, never added; among
    exact ``(avail, jitter)`` ties the lowest index wins. Where no
    candidate exists the minimum is ``(inf, inf, *)``.
    """
    a = jnp.where(c, avail, F32_INF)
    tile_a = a.min(axis=axis, keepdims=True)
    # the `c &` guard keeps inf==inf slots of masked entries out
    jm = jnp.where(c & (a == tile_a), jit, F32_INF)
    tile_j = jm.min(axis=axis, keepdims=True)
    tile_i = jnp.where(jm == tile_j, idx, IDX_SENTINEL).min(
        axis=axis, keepdims=True
    )
    return tile_a, tile_j, tile_i


def _lex_merge(prev, new):
    """Elementwise strictly-less merge of ``(avail, jitter, index)``
    triples: ``new`` replaces ``prev`` only when its ``(avail, jitter)`` is
    smaller, so on exact ties ``prev`` wins. Merging in ascending piece
    order therefore keeps the first occurrence."""
    pa, pj, pi = prev
    na, nj, ni = new
    better = (na < pa) | ((na == pa) & (nj < pj))
    return (
        jnp.where(better, na, pa),
        jnp.where(better, nj, pj),
        jnp.where(better, ni, pi),
    )


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
    pid = lax.broadcasted_iota(jnp.int32, c.shape, 1) + j * bp
    # per row of this piece tile, then merged into the running minimum:
    # tiles arrive in piece order, so an earlier tile wins exact ties
    tile = _lex_min(c, avail_ref[...], jit_ref[...], pid, axis=1)
    best = _lex_merge((a_min[...], j_min[...], i_min[...]), tile)
    a_min[...], j_min[...], i_min[...] = best

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


# --------------------------------------------------------------------------- fused row select

# which pieces a stream may take, before the row's own have bits and the
# other stream's current piece: FleetSwarmSim._select's class rules
SELECT_RULES = ("any", "origin", "origin_or_unserved", "swarm_served")


def _piece_rule(rule, swarm_class, repl):
    if rule == "any":  # HTTP stream, http_first
        return jnp.ones(repl.shape, jnp.bool_)
    if rule == "origin":  # HTTP stream, swarm_first
        return ~swarm_class
    if rule == "origin_or_unserved":  # ... with the origin rescue fallback
        return ~swarm_class | (repl == 0)
    return swarm_class & (repl > 0)  # swarm stream: served swarm pieces


def _select_rows_kernel(
    rows_ref, next_ref, other_ref, repl_ref, cls_ref, have_hbm, jit_hbm,
    pick_ref, have_buf, jit_buf, sem, avail, acc_a, acc_j, acc_i,
    *, n_pieces: int, rule: str, chunk: int,
):
    g = pl.program_id(0)
    slot = g % 2
    _, R, width, _ = jit_buf.shape
    nfull, tail = divmod(width, chunk)

    def copies(s, r, row):
        return (
            pltpu.make_async_copy(have_hbm.at[row], have_buf.at[s, r],
                                  sem.at[0, s]),
            pltpu.make_async_copy(jit_hbm.at[row], jit_buf.at[s, r],
                                  sem.at[1, s]),
        )

    def fetch(idx_ref, s):
        """Start the whole-row copies of one group; padding rows (-1) copy
        nothing."""
        def start(r, carry):
            row = idx_ref[0, r]

            @pl.when(row >= 0)
            def _():
                for cp in copies(s, r, row):
                    cp.start()

            return carry

        lax.fori_loop(0, R, start, 0)

    @pl.when(g == 0)
    def _first():
        # availability where the stream's rule admits the piece, inf
        # elsewhere (and on the padding past the last piece)
        repl = repl_ref[...]
        pid = (lax.broadcasted_iota(jnp.int32, repl.shape, 0) * LANES
               + lax.broadcasted_iota(jnp.int32, repl.shape, 1))
        ok = _piece_rule(rule, cls_ref[...] != 0, repl) & (pid < n_pieces)
        avail[...] = jnp.where(ok, repl.astype(jnp.float32), F32_INF)
        fetch(rows_ref, 0)

    # double buffering: the next group's rows stream in while this one
    # is reduced
    @pl.when(g + 1 < pl.num_programs(0))
    def _prefetch():
        fetch(next_ref, 1 - slot)

    local = (lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0) * LANES
             + lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1))

    def chunk_step(r, other, size):
        def step(c, acc):
            off = c * chunk
            miss = have_buf[slot, r, pl.ds(off, size), :].astype(
                jnp.int32) == 0
            # a peer's two streams exclude each other's current piece
            keep = local[:size] != other - off * LANES
            a = jnp.where(miss & keep, avail[pl.ds(off, size), :], F32_INF)
            j = jit_buf[slot, r, pl.ds(off, size), :]
            # each slot sees its pieces in ascending order, so the
            # strictly-less merge keeps the first occurrence; the jitter
            # of a non-candidate (a = inf) only lands where a stays inf
            return _lex_merge(acc, (a, j, jnp.full(a.shape, c, jnp.int32)))

        return step

    def reduce_row(r, carry):
        @pl.when(rows_ref[0, r] >= 0)
        def _():
            for cp in copies(slot, r, 0):
                cp.wait()
            other = other_ref[0, r]
            init = (jnp.full((chunk, LANES), F32_INF, jnp.float32),
                    jnp.full((chunk, LANES), F32_INF, jnp.float32),
                    jnp.zeros((chunk, LANES), jnp.int32))
            a, j, c = lax.fori_loop(0, nfull, chunk_step(r, other, chunk),
                                    init)
            acc_a[r], acc_j[r] = a, j
            acc_i[r] = c * (chunk * LANES) + local
            if tail:  # the last, shorter chunk folds into the first slots
                a, j, c = chunk_step(r, other, tail)(
                    nfull, (a[:tail], j[:tail], c[:tail]))
                acc_a[r, :tail], acc_j[r, :tail] = a, j
                acc_i[r, :tail] = c * (chunk * LANES) + local[:tail]

        @pl.when(rows_ref[0, r] < 0)
        def _():
            acc_a[r] = jnp.full((chunk, LANES), F32_INF, jnp.float32)

        return carry

    @pl.when(rows_ref[0, 0] >= 0)
    def _reduce():
        lax.fori_loop(0, R, reduce_row, 0)
        a, j, i = acc_a[...], acc_j[...], acc_i[...]
        # all R rows at once: over sublanes, then over lanes
        a, j, i = _lex_min(a < F32_INF, a, j, i, axis=1)
        a, j, i = _lex_min(a < F32_INF, a, j, i, axis=2)
        pick_ref[...] = jnp.where(a < F32_INF, i, -1).reshape(R, 1)

    @pl.when(rows_ref[0, 0] < 0)
    def _padding():
        pick_ref[...] = jnp.full((R, 1), -1, jnp.int32)


def select_rows_vmem_bytes(width: int, rows: int, chunk: int) -> int:
    """VMEM of one :func:`select_rows_call`: two slots of ``rows`` whole
    have (uint8) and jitter (float32) rows, the per-row accumulators, and
    the ``(width, 128)`` piece vectors (availability, and the two inputs
    double-buffered)."""
    slab = width * LANES
    return 2 * rows * slab * 5 + 3 * rows * chunk * LANES * 4 + 5 * slab * 4


def select_rows_call(
    have: jax.Array,
    jitter: jax.Array,
    repl: jax.Array,
    swarm_class: jax.Array,
    rows: jax.Array,
    other: jax.Array,
    *,
    n_pieces: int,
    rule: str,
    rows_per_step: int,
    chunk: int,
    vmem_limit_bytes: int,
    interpret: bool,
):
    """Candidate build + rarest-argmin for the selected rows, reading each
    row of the device state in place.

    ``have`` (uint8 0/1) and ``jitter`` (float32) are ``(n, width, 128)``:
    row ``i`` holds pieces ``[0, width * 128)`` as one contiguous slab, zero
    past ``n_pieces``. They stay in HBM; each grid step copies the whole
    slabs of ``rows_per_step`` selected rows into VMEM (the next step's
    copies start before this step's reduction) and reduces them in
    ``(chunk, 128)`` pieces. ``repl`` and ``swarm_class`` (int32, 0/1) are
    ``(width, 128)`` and stay in VMEM. ``rows`` and ``other`` are
    ``(kp,)`` int32 with ``kp`` a multiple of ``rows_per_step``; rows
    ``-1`` (padding, only at the end) are never copied and pick ``-1``.
    Returns ``(kp,)`` int32 picks, ``-1`` where a row has no candidate.
    """
    assert rule in SELECT_RULES, rule
    kp = rows.shape[0]
    _, width, _ = jitter.shape
    R = rows_per_step
    assert kp % R == 0 and width % 8 == 0 and chunk % 8 == 0
    chunk = min(chunk, width)
    ng = kp // R
    group = pl.BlockSpec((None, 1, R), lambda g: (g, 0, 0),
                         memory_space=pltpu.SMEM)
    nxt = pl.BlockSpec((None, 1, R),
                       lambda g: (jnp.minimum(g + 1, ng - 1), 0, 0),
                       memory_space=pltpu.SMEM)
    piece_vec = pl.BlockSpec((width, LANES), lambda g: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    by_group = rows.reshape(ng, 1, R)
    picks = pl.pallas_call(
        functools.partial(_select_rows_kernel, n_pieces=n_pieces,
                          rule=rule, chunk=chunk),
        grid=(ng,),
        in_specs=[group, nxt, group, piece_vec, piece_vec, hbm, hbm],
        out_specs=pl.BlockSpec((R, 1), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, R, width, LANES), have.dtype),
            pltpu.VMEM((2, R, width, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((width, LANES), jnp.float32),
            pltpu.VMEM((R, chunk, LANES), jnp.float32),
            pltpu.VMEM((R, chunk, LANES), jnp.float32),
            pltpu.VMEM((R, chunk, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(by_group, by_group, other.reshape(ng, 1, R), repl, swarm_class,
      have, jitter)
    return picks[:, 0]


# --------------------------------------------------------------------------- water-filling


def _fill_round(counts, alloc, caps):
    """One progressive-filling round at the constraints, which
    :func:`_fixed_point` runs for both paths, so both run the same float
    ops.

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


def _fixed_point(src, caps, counts_of, hits_of, n_iter):
    """The rounds of the two paths in device memory: ``counts_of(act)``
    gives each constraint's active-flow count from the 0/1 float
    ``act``, ``hits_of(sats)`` each flow's saturated constraints.
    Returns ``((pf,) rates, rounds)``."""

    def body(state):
        rate, frozen, alloc, it, _ = state
        act = (~frozen).astype(jnp.float32)
        delta, ok, alloc, sats = _fill_round(counts_of(act), alloc, caps)
        rate = rate + act * delta
        newly = (~frozen) & (hits_of(sats) > 0)
        frozen = frozen | newly
        stop = ~(ok & newly.any()) | frozen.all()
        return rate, frozen, alloc, it + 1, stop

    def cond(state):
        *_, it, stop = state
        return (~stop) & (it < n_iter)

    init = (
        jnp.zeros(src.shape[0], jnp.float32),
        src < 0,  # padded flows start frozen
        tuple(jnp.zeros(c.shape[0], jnp.float32) for c in caps),
        jnp.int32(0),
        jnp.asarray(False),
    )
    rate, _, _, it, _ = lax.while_loop(cond, body, init)
    return rate, it.reshape(1)


def waterfill_xla(src, dst, lnk, up_cap, down_cap, link_cap, *, n_iter: int):
    """The fixed point with scatter-add segment sums and direct
    gathers, over 1-D arrays in device memory. Takes the padded table of
    ``ops.py`` (``-1`` flows start frozen) or an unpadded one; returns
    ``((nf,) rates, rounds)``.
    """
    caps = (up_cap, down_cap, link_cap)

    def counts_of(act):
        # -1 padding wraps to the last slot, where it adds 0
        return tuple(jnp.zeros(c.shape[0], jnp.float32).at[i].add(act)
                     for c, i in zip(caps, (src, dst, lnk)))

    def hits_of(sats):
        return sats[0][src] + sats[1][dst] + sats[2][lnk]

    return _fixed_point(src, caps, counts_of, hits_of, n_iter)


def _incidence(idx, width):
    """``(pf,)`` indices into a ``width``-slot channel (a multiple of
    :data:`LANES`) -> its two-level 0/1 incidence, bfloat16: ``hi``
    ``(pf, width // LANES)`` on ``idx // LANES`` and ``lo`` ``(pf,
    LANES)`` on ``idx % LANES``. A ``-1`` pad matches no ``hi`` column,
    so it adds 0 and reads 0."""
    cols = lambda n: lax.broadcasted_iota(jnp.int32, (1, n), 1)  # noqa: E731
    idx = idx[:, None]
    hi = (idx // LANES == cols(width // LANES)).astype(jnp.bfloat16)
    lo = (idx % LANES == cols(LANES)).astype(jnp.bfloat16)
    return hi, lo


def waterfill_onehot(src, dst, lnk, up_cap, down_cap, link_cap, *,
                     n_iter: int):
    """:func:`waterfill_xla`'s fixed point with each round's segment sums
    and saturation lookups as two-level one-hot contractions on the MXU,
    in place of per-flow scatter-adds and gathers.

    A slot ``i`` of a channel is the pair ``(i // 128, i % 128)``: the
    active flows per slot are ``hiᵀ · (act ⊙ lo)`` and a flow's
    saturation is the row sum of ``(hi · sats) ⊙ lo``. Every operand is
    0/1 (bfloat16 holds it exactly) and every sum an integer below 2^24
    accumulated in float32, so counts, rounds and rates are the scatter
    form's bit for bit. XLA builds the incidences inside the contractions'
    fusions, so a round reads only the index vectors from HBM. Takes the
    padded table of ``ops.py`` (widths multiples of 128); returns
    ``((pf,) rates, rounds)``.
    """
    caps = (up_cap, down_cap, link_cap)
    inc = tuple(_incidence(i, c.shape[0])
                for i, c in zip((src, dst, lnk), caps))

    def counts_of(act):
        act = act.astype(jnp.bfloat16)[:, None]
        return tuple(
            lax.dot_general(hi, act * lo, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32).reshape(-1)
            for hi, lo in inc
        )

    def hits_of(sats):
        return sum(
            (jnp.dot(hi, s.astype(jnp.bfloat16).reshape(-1, LANES),
                     preferred_element_type=jnp.float32) * lo).sum(axis=1)
            for (hi, lo), s in zip(inc, sats)
        )

    return _fixed_point(src, caps, counts_of, hits_of, n_iter)
