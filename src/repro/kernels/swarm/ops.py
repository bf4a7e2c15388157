"""Host-facing wrappers for the swarm kernels + device-resident fleet state.

Three layers, mirroring the checksum/attention packages:

- :func:`rarest_argmin` / :func:`fleet_waterfill` — numpy-in/numpy-out
  convenience wrappers that pad to kernel tile multiples (rows/pieces with
  ``cand=False``) or to powers of two (flows with ``src = dst = -1``
  pre-frozen padding; unlinked flows onto the infinite-capacity dummy link
  slot) and cache one ``jax.jit`` entry point per static configuration.
  The water-fill runs one of two float32 device paths, chosen from the
  padded node count (:func:`waterfill_plan`).

- :class:`FleetDeviceState` — what ``FleetSpec.backend = "pallas"`` hangs
  onto: the have matrix, the fixed float32 jitter, and the replica counts
  live on device across ticks. Have and jitter are kept row-contiguous,
  ``(n, width, 128)`` with ``width * 128 >= P``, so that selection
  (:func:`~.kernel.select_rows_call`, one Pallas call) copies just the
  selected rows from HBM, whole, builds their candidate masks in VMEM and
  returns only the ``(k,)`` pick vector: no ``(k, P)`` array and no copy
  of the state is made per call. Completions/departures are incremental
  scatter updates sized by the number of finished pieces, not by
  ``n * P``. Padding rows use out-of-bounds indices, which jax scatter
  semantics drop (``mode="drop"`` made explicit below), so variable-size
  updates reuse a handful of power-of-two traces. Its programs run on
  every chip of a 1-D mesh of ``devices`` chips (one chip included), each
  on its own block of client rows.

``interpret=None`` resolves the Pallas calls (the select kernels) per
platform (:func:`repro.accel.pallas_interpret`): compiled on a TPU, the
Pallas interpreter on the CPU test backend. The water-fill is plain XLA
ops and needs no such flag.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as PS

from ...accel import pallas_interpret
from ...core.piece_selection import MAX_EXACT_AVAILABILITY
from .kernel import (
    LANES,
    rarest_argmin_call,
    select_rows_call,
    select_rows_vmem_bytes,
    waterfill_onehot,
    waterfill_xla,
)

BLOCK_ROWS = 128
BLOCK_PIECES = 256
# the largest padded node count the one-hot water-fill takes; above it
# the scatter fixed point is faster (``waterfill_plan``)
ONEHOT_MAX_NODES = 1 << 19
# the row select's scoped-VMEM limit; rows per grid step halve until its
# buffers take at most three quarters of it
SELECT_VMEM_LIMIT = 16 << 20
# sublane rows of 128 pieces reduced at a time in the row select
SELECT_CHUNK = 64
# the device state's mesh axis: chips share the client rows
PEERS = "peers"


def _next_pow2(x: int, lo: int = 0) -> int:
    return 1 << max(lo, int(x - 1).bit_length() if x > 1 else 0)


def _resolve_interpret(interpret) -> bool:
    return pallas_interpret() if interpret is None else bool(interpret)


def _piece_block(P: int) -> int:
    return min(BLOCK_PIECES, _next_pow2(P, 7))


# --------------------------------------------------------------------------- rarest-argmin


@functools.lru_cache(maxsize=None)
def _rarest_jit(bp: int, interpret: bool):
    return jax.jit(functools.partial(
        rarest_argmin_call,
        block_rows=BLOCK_ROWS, block_pieces=bp, interpret=interpret,
    ))


def rarest_argmin(
    cand: np.ndarray,
    availability: np.ndarray,
    jitter: np.ndarray,
    *,
    interpret=None,
) -> np.ndarray:
    """Kernel-backed :func:`~repro.core.piece_selection.batched_rarest`:
    identical signature and index-exact results (``-1`` = no candidate)."""
    cand = np.asarray(cand, dtype=bool)
    k, P = cand.shape
    if k == 0:
        return np.zeros(0, dtype=np.int64)
    avail = np.asarray(availability)
    assert int(avail.max(initial=0)) < MAX_EXACT_AVAILABILITY, (
        "replica counts no longer exact in float32 — fleet too large"
    )
    bp = _piece_block(P)
    kp = -(-k // BLOCK_ROWS) * BLOCK_ROWS
    Pp = -(-P // bp) * bp
    candp = np.zeros((kp, Pp), dtype=bool)
    candp[:k, :P] = cand
    availp = np.zeros(Pp, dtype=np.float32)
    availp[:P] = avail
    jitp = np.zeros((kp, Pp), dtype=np.float32)
    jitp[:k, :P] = jitter
    out = _rarest_jit(bp, _resolve_interpret(interpret))(
        candp, availp, jitp
    )
    return np.asarray(out)[:k].astype(np.int64)


# --------------------------------------------------------------------------- water-filling


class WaterfillPlan(NamedTuple):
    """Padded shapes of one water-fill call and the path that runs it."""

    pf: int  # flows
    pn: int  # nodes
    pnl: int  # spine links + the dummy slot
    # "onehot" (one-hot contractions) or "xla" (scatter fixed point)
    impl: str


def waterfill_plan(nf: int, nn: int, nl: int) -> WaterfillPlan:
    """Pad to powers of two (at least one lane width, so the few shapes a
    run sees compile once each) and pick the implementation from the
    padded node count alone: the one-hot contraction while it is at most
    :data:`ONEHOT_MAX_NODES`, else the scatter fixed point.

    A contraction round costs ``pf * pn`` MXU work, a scatter round
    ``pf`` element-wise scatters and gathers, so the contraction's lead
    shrinks as ``pn`` grows. Milliseconds a round on one TPU v5e
    (``benchmarks/waterfill_paths.py``; 16 rounds, best of 3):

        pf    pn    one-hot  scatter
        2^17  2^15     0.81     6.22
        2^17  2^17     1.26     6.23
        2^18  2^17     2.45    12.67
        2^20  2^17     9.64    50.18
        2^18  2^18     3.69    12.72
        2^20  2^18    14.53    50.22
        2^19  2^19    14.09    25.25
        2^20  2^19    31.75    50.26
        2^20  2^20    58.74    50.32
    """
    pf, pn, pnl = (_next_pow2(x, 7) for x in (nf, nn, nl + 1))
    impl = "onehot" if pn <= ONEHOT_MAX_NODES else "xla"
    return WaterfillPlan(pf, pn, pnl, impl)


@functools.lru_cache(maxsize=None)
def _waterfill_jit(n_iter: int, impl: str):
    # named functions, not partials: a profile shows each program by name
    def fleet_waterfill_onehot(src, dst, lnk, up, dn, lcap):
        return waterfill_onehot(src, dst, lnk, up, dn, lcap, n_iter=n_iter)

    def fleet_waterfill_xla(src, dst, lnk, up, dn, lcap):
        return waterfill_xla(src, dst, lnk, up, dn, lcap, n_iter=n_iter)

    return jax.jit({"onehot": fleet_waterfill_onehot,
                    "xla": fleet_waterfill_xla}[impl])


def _waterfill(src, dst, up_cap, down_cap, link_of, link_cap, impl=None):
    """Pad, run on the device, unpad: ``(rates, rounds, plan)``.
    ``impl=None`` takes :func:`waterfill_plan`'s choice; ``"onehot"`` or
    ``"xla"`` forces one path (the parity tests run each)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    nf = src.size
    nn = np.asarray(up_cap).size
    nl = 0
    if link_of is not None and link_cap is not None:
        link_of = np.asarray(link_of, dtype=np.int64)
        if (link_of >= 0).any():
            nl = np.asarray(link_cap).size
    plan = waterfill_plan(nf, nn, nl)
    if impl is not None:
        plan = plan._replace(impl=impl)
    n_iter = 2 * nn + nl + 2  # real constraint count bounds the fixed point

    s = np.full(plan.pf, -1, dtype=np.int32)
    d = np.full(plan.pf, -1, dtype=np.int32)
    s[:nf] = src
    d[:nf] = dst
    lk = np.full(plan.pf, nl, dtype=np.int32)  # dummy slot (also for padding)
    if nl:
        lk[:nf] = np.where(link_of >= 0, link_of, nl)
    up = np.zeros(plan.pn, dtype=np.float32)
    dn = np.zeros(plan.pn, dtype=np.float32)
    up[:nn] = up_cap
    dn[:nn] = down_cap
    lc = np.zeros(plan.pnl, dtype=np.float32)
    lc[nl] = np.inf
    if nl:
        lc[:nl] = link_cap
    rate, rounds = _waterfill_jit(n_iter, plan.impl)(s, d, lk, up, dn, lc)
    # unpad on the host: a device-side slice would compile once per nf
    rate = np.asarray(rate, dtype=np.float64)[:nf]
    return rate, int(np.asarray(rounds)[0]), plan


def fleet_waterfill(
    src: np.ndarray,
    dst: np.ndarray,
    up_cap: np.ndarray,
    down_cap: np.ndarray,
    link_of: Optional[np.ndarray] = None,
    link_cap: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Device-backed :func:`~repro.core.fleet.waterfill_rates` (float32;
    spine links supported), on the path :func:`waterfill_plan` picks from
    the padded node count. Bit-identical to ``ref.waterfill_jnp_ref``;
    within a band of the float64 goldens path.
    """
    if np.asarray(src).size == 0:
        return np.zeros(0, dtype=np.float64)
    return _waterfill(src, dst, up_cap, down_cap, link_of, link_cap)[0]


# --------------------------------------------------------------------------- device state


class SelectPlan(NamedTuple):
    """Row layout and grid of the device select, from the piece count."""

    width: int  # sublane rows of 128 pieces per have/jitter row
    rows: int  # selected rows copied and reduced per grid step
    vmem_limit: int  # scoped VMEM of the call (bytes)


def select_plan(P: int) -> SelectPlan:
    """A state row holds ``width * 128 >= P`` pieces, ``width`` a multiple
    of 8 so that each row is one run of whole ``(8, 128)`` tiles. Rows are
    reduced in chunks of ``min(SELECT_CHUNK, width)`` sublanes. Rows per
    step start at one row tile (128) and halve, down to 8, until two slots
    of them and their accumulators fit three quarters of
    :data:`SELECT_VMEM_LIMIT`; wider rows than that raise the limit
    instead."""
    width = -(-P // (8 * LANES)) * 8
    chunk = min(SELECT_CHUNK, width)
    rows = BLOCK_ROWS
    while rows > 8 and select_rows_vmem_bytes(
            width, rows, chunk) > SELECT_VMEM_LIMIT * 3 // 4:
        rows //= 2
    need = select_rows_vmem_bytes(width, rows, chunk)
    return SelectPlan(width, rows, max(SELECT_VMEM_LIMIT, need * 4 // 3))


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(rows, block, start):
    """Write a ``(b, P)`` block into rows ``start:start + b`` of an
    ``(n, width, 128)`` array, zero past the last piece."""
    b, P = block.shape
    width = rows.shape[1]
    block = jnp.pad(block, ((0, 0), (0, width * LANES - P)))
    return lax.dynamic_update_slice(
        rows, block.reshape(b, width, LANES), (start, 0, 0))


def _zeros(shape, dtype, sharding) -> jax.Array:
    """Zeros made where ``sharding`` puts them, each device writing its
    own shard (``jnp.zeros(device=...)`` fills one device's shard and
    copies it to the others)."""
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)()


def _as_rows(x: np.ndarray, width: int, dtype, mesh: Mesh) -> jax.Array:
    """A host ``(n, P)`` matrix as a device ``(n_pad, width, 128)`` array
    sharded by contiguous row blocks over ``mesh`` (``n_pad`` the next
    multiple of its size, zero rows past ``n``). Each chip's block is
    built on that chip, sent in pieces of about 256 MB: padding the matrix
    whole on the host costs seconds at fleet size, and on a device two
    more copies of it."""
    n, P = x.shape
    devs = mesh.devices.ravel()
    per = -(-n // devs.size)
    block = max(1, (256 << 20) // (P * np.dtype(dtype).itemsize))
    shards = [_zeros((per, width, LANES), dtype, SingleDeviceSharding(d))
              for d in devs]
    # round-robin over the chips, so that their copies overlap
    for off in range(0, per, block):
        for i in range(devs.size):
            lo = i * per + off
            hi = min(n, i * per + min(per, off + block))
            if lo < hi:
                shards[i] = _put_rows(shards[i], np.asarray(x[lo:hi], dtype),
                                      np.int32(off))
    return jax.make_array_from_single_device_arrays(
        (per * devs.size, width, LANES), NamedSharding(mesh, PS(PEERS)),
        shards)


def _select_rule(stream: str, mode: str, fallback: bool) -> str:
    """``FleetSwarmSim._select``'s class rule for one stream."""
    if stream != "http":
        return "swarm_served"
    if mode == "http_first":
        return "any"
    return "origin_or_unserved" if fallback else "origin"


def _local_rows(rows, per):
    """Global row indices as this chip's own (``per`` rows a chip), with
    rows another chip owns out of bounds (``per``)."""
    local = rows - lax.axis_index(PEERS) * per
    return jnp.where((local >= 0) & (local < per), local, per)


def _mesh_jit(fn, mesh: Mesh, in_specs, out_specs, donate=()):
    """``jax.jit`` of ``fn`` run on every chip of ``mesh``, each on its own
    blocks (``in_specs`` / ``out_specs`` over :data:`PEERS`), donating
    the arguments ``donate``."""
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)

    @functools.wraps(fn)  # a profile shows each program by name
    def program(*args):
        return mapped(*args)

    def shardings(specs):
        return jax.tree.map(lambda spec: NamedSharding(mesh, spec), specs)

    return jax.jit(program, in_shardings=shardings(in_specs),
                   out_shardings=shardings(out_specs), donate_argnums=donate)


# The programs below run one body per chip of a 1-D ``("peers",)`` mesh,
# each on its own block of rows. Named functions, not partials: a profile
# shows each program by name.
ROW, WHOLE = PS(PEERS), PS()


@functools.lru_cache(maxsize=None)
def _select_jit(rule: str, interpret: bool, mesh: Mesh):
    def fleet_select(have, jitter, repl, swarm_class, rows, other):
        P = repl.shape[0]
        plan = select_plan(P)
        repl = jnp.pad(repl, (0, plan.width * LANES - P))
        return select_rows_call(
            have, jitter, repl.reshape(plan.width, LANES), swarm_class,
            rows, other, n_pieces=P, rule=rule, rows_per_step=plan.rows,
            chunk=SELECT_CHUNK, vmem_limit_bytes=plan.vmem_limit,
            interpret=interpret,
        )

    return _mesh_jit(fleet_select, mesh, (ROW, ROW, WHOLE, WHOLE, ROW, ROW),
                     ROW)


@functools.lru_cache(maxsize=None)
def _add_pieces_jit(mesh: Mesh):
    def fleet_add_pieces(have, repl, rows, pieces):
        # every chip gets the whole list: it writes the rows it owns, and
        # adds every piece to its own copy of the replica counts, so the
        # copies stay equal with no collective. Out-of-bounds padding
        # indices are dropped, so one trace serves every power-of-two
        # batch size
        rows = _local_rows(rows, have.shape[0])
        have = have.at[rows, pieces // LANES, pieces % LANES].set(
            1, mode="drop")
        repl = repl.at[pieces].add(1, mode="drop")
        return have, repl

    # the have matrix is donated, so the scatter writes each chip's rows
    # in place: a copy would cost a pass over the whole matrix per call,
    # and queued calls would each hold one
    return _mesh_jit(fleet_add_pieces, mesh, (ROW, WHOLE, WHOLE, WHOLE),
                     (ROW, WHOLE), donate=(0,))


@functools.lru_cache(maxsize=None)
def _drop_rows_jit(mesh: Mesh):
    def fleet_drop_rows(have, repl, rows):
        got = have.at[_local_rows(rows, have.shape[0])].get(
            mode="fill", fill_value=0)
        held = got.sum(axis=0, dtype=repl.dtype).reshape(-1)
        # each chip sums its own rows: the one collective
        held = lax.psum(held, PEERS)
        return repl - held[: repl.shape[0]]

    return _mesh_jit(fleet_drop_rows, mesh, (ROW, WHOLE, WHOLE), WHOLE)


class FleetDeviceState:
    """Device-resident tick state for ``FleetSpec.backend="pallas"``.

    Holds the have matrix (0/1 uint8), fixed jitter, replica counts, and
    the static swarm-routing class on device across ticks; have and jitter
    in the row-contiguous layout of :func:`select_plan`. The engine keeps
    its numpy mirrors for scalar control flow (leech masks, host-RNG source
    sampling); the ``O(k * P)`` candidate-mask + argmin traffic — the
    fleet tick's dominant term — happens here, reading only the selected
    rows, and only ``(k,)`` pick vectors cross back per call.

    ``devices`` chips (the first of ``jax.devices()``, a 1-D mesh on
    :data:`PEERS`) share the client rows: have and jitter are split into
    contiguous blocks of ``rows_per_device`` rows (``n`` padded up to a
    multiple of ``devices``), the replica counts and the class vector are
    copied to every chip. A selection sends each chip its own rows, in one
    bucket sized by the busiest chip (``shard_rows_max`` keeps the last
    call's count); completions go to every chip, each writing the rows it
    owns and adding all of them to its replica counts; departures sum each
    chip's rows and add the sums over the mesh. ``add_pieces`` consumes
    the previous ``have_rows``: its scatter writes into that donated
    buffer, so no caller may keep it across the call. Water-filling runs
    on the mesh's first chip; ``waterfill_runs`` counts the calls per
    path (``"onehot"``, ``"xla"``), and ``peak_flows`` / ``rounds``
    record the largest flow table and the fixed-point rounds summed over
    the run. ``interpret`` applies to the select kernel.
    """

    def __init__(self, jitter: np.ndarray, swarm_class: np.ndarray,
                 *, devices: int = 1, interpret=None) -> None:
        n, P = jitter.shape
        assert n < MAX_EXACT_AVAILABILITY, (
            "replica counts no longer exact in float32 — fleet too large"
        )
        have_devs = jax.devices()
        if not 1 <= devices <= len(have_devs):
            raise ValueError(f"the device state wants {devices} devices; "
                             f"JAX sees {len(have_devs)}")
        self.n, self.P = n, P
        self.devices = devices
        self.rows_per_device = -(-n // devices)
        self.n_pad = self.rows_per_device * devices
        self.mesh = Mesh(np.array(have_devs[:devices]), (PEERS,))
        self.interpret = _resolve_interpret(interpret)
        width = select_plan(P).width
        self.have_rows = _zeros((self.n_pad, width, LANES), jnp.uint8,
                                NamedSharding(self.mesh, PS(PEERS)))
        self.jitter_rows = _as_rows(jitter, width, np.float32, self.mesh)
        whole = NamedSharding(self.mesh, PS())
        self.repl = jax.device_put(np.zeros(P, dtype=np.int32), whole)
        cls = np.zeros(width * LANES, dtype=np.int32)
        cls[:P] = swarm_class
        self.class_rows = jax.device_put(cls.reshape(width, LANES), whole)
        self.shard_rows_max = 0
        self.waterfill_runs = {"onehot": 0, "xla": 0}
        self.peak_flows = 0
        self.rounds = 0

    @property
    def have(self) -> np.ndarray:
        """The ``(n, P)`` 0/1 have matrix (uint8), copied to the host."""
        return np.asarray(self.have_rows).reshape(
            self.n_pad, -1)[: self.n, : self.P]

    def select(self, rows: np.ndarray, other: np.ndarray, *,
               stream: str, mode: str, fallback: bool) -> np.ndarray:
        """Device cand-build + rarest-argmin for ``rows`` on one stream.

        Semantics mirror ``FleetSwarmSim._select`` exactly (index-exact
        parity is pinned by the engine-equivalence test).
        """
        k = rows.size
        owner = rows // self.rows_per_device
        counts = np.bincount(owner, minlength=self.devices)
        self.shard_rows_max = int(counts.max(initial=0))
        # whole row tiles; pow2 bounds retraces
        kb = _next_pow2(self.shard_rows_max, 7)
        # chip by chip, in call order within each: flat slots of the
        # (devices, kb) bucket, padded with -1 (nothing to copy)
        order = np.argsort(owner, kind="stable")
        owner = owner[order]
        at = owner * kb + np.arange(k) - np.repeat(
            np.cumsum(counts) - counts, counts)
        rows_p = np.full(self.devices * kb, -1, dtype=np.int32)
        rows_p[at] = rows[order] - owner * self.rows_per_device
        other_p = np.full(self.devices * kb, -1, dtype=np.int32)
        other_p[at] = other[order]
        fn = _select_jit(_select_rule(stream, mode, fallback), self.interpret,
                         self.mesh)
        out = np.asarray(fn(
            self.have_rows, self.jitter_rows, self.repl, self.class_rows,
            rows_p, other_p,
        ))
        pick = np.empty(k, dtype=np.int64)
        pick[order] = out[at]
        return pick

    def _padded(self, rows: np.ndarray) -> tuple[np.ndarray, int]:
        """``rows`` padded to a power of two with ``n_pad``, a row no chip
        owns."""
        k = rows.size
        r = np.full(_next_pow2(k, 3), self.n_pad, dtype=np.int32)
        r[:k] = rows
        return r, k

    def add_pieces(self, rows: np.ndarray, pieces: np.ndarray) -> None:
        """Piece completions: scatter ``have[rows, pieces] = True`` and
        bump replica counts (padded with out-of-bounds drops)."""
        r, k = self._padded(rows)
        p = np.full(r.size, self.P, dtype=np.int32)
        p[:k] = pieces
        self.have_rows, self.repl = _add_pieces_jit(self.mesh)(
            self.have_rows, self.repl, r, p)

    def drop_rows(self, rows: np.ndarray) -> None:
        """Departures: remove the rows' held pieces from the replica
        counts (the have rows themselves stay, as on the host)."""
        r, _ = self._padded(rows)  # out-of-bounds gather -> fill 0
        self.repl = _drop_rows_jit(self.mesh)(self.have_rows, self.repl, r)

    def waterfill(self, src, dst, up_cap, down_cap, link_of, link_cap):
        """:func:`fleet_waterfill` on the device, keeping the run's
        water-fill statistics."""
        rates, rounds, plan = _waterfill(
            src, dst, up_cap, down_cap, link_of, link_cap)
        self.waterfill_runs[plan.impl] += 1
        self.peak_flows = max(self.peak_flows, np.asarray(src).size)
        self.rounds += rounds
        return rates
