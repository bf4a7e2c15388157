"""Reference implementations for the swarm kernels.

Three oracles, three exactness contracts:

- :func:`rarest_argmin_ref` — piece selection is *index-exact*: the fixed
  per-(peer, piece) jitter makes ties deterministic, so the Pallas kernel
  must return the identical index vector, not an approximation. The oracle
  is :func:`repro.core.piece_selection.batched_rarest` itself (the engine
  hot path), re-exported so the parity suite pins kernel == engine.

- :func:`waterfill_jnp_ref` — the *bit-for-bit* water-filling oracle
  (checksum-idiom pure-jnp): the XLA fixed point
  :func:`~.kernel.waterfill_xla` on the unpadded table, scatter-based.
  Comparing the device paths against it pins exactly what they add —
  the padding conventions, the dummy link slot and the contraction's
  two-level one-hot incidences — with zero tolerance.

- :func:`waterfill_f32_ref` — a float32 numpy transliteration of
  :func:`repro.core.fleet.waterfill_rates` (same bincount / min ordering,
  same ``newly``-freeze rule, ``1e-6`` saturation tolerance in place of
  the float64 path's ``1e-12``). It is ulp-close to the device paths but
  not bitwise: XLA:CPU unconditionally contracts ``alloc + count * delta``
  into single-rounded FMAs, numpy rounds multiply and add separately, so
  cross-domain parity is pinned at a tight relative band instead. The
  float64 ``waterfill_rates`` remains the goldens semantics.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.piece_selection import batched_rarest
from .kernel import waterfill_xla

F32 = np.float32
F32_INF = np.float32(np.inf)


def rarest_argmin_ref(
    cand: np.ndarray, availability: np.ndarray, jitter: np.ndarray
) -> np.ndarray:
    """The engine's masked rarest-first argmin (lexicographic minimum of
    ``(availability, jitter, piece index)`` over candidates; ``-1`` for
    all-masked rows)."""
    return batched_rarest(cand, availability, jitter)


def _link_channel(nf, link_of, link_cap):
    """Unlinked flows map onto a dummy slot of infinite capacity, so the
    link channel always exists and every path takes identical branches."""
    nl = 0
    if link_of is not None and link_cap is not None:
        link_of = np.asarray(link_of, dtype=np.int64)
        if (link_of >= 0).any():
            nl = np.asarray(link_cap).size
    if nl:
        lnk = np.where(link_of >= 0, link_of, nl)
        lcap = np.concatenate([np.asarray(link_cap, dtype=F32), [F32_INF]])
    else:
        lnk = np.zeros(nf, dtype=np.int64)
        lcap = np.array([F32_INF], dtype=F32)
    return nl, lnk, lcap


def waterfill_f32_ref(
    src: np.ndarray,
    dst: np.ndarray,
    up_cap: np.ndarray,
    down_cap: np.ndarray,
    link_of: Optional[np.ndarray] = None,
    link_cap: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Float32 numpy max-min progressive filling (algorithmic reference).

    Returns the ``(nf,)`` float32 rate vector. See the module docstring
    for the exactness contract; ``tests/test_fleet.py`` separately pins
    the float64 :func:`~repro.core.fleet.waterfill_rates` to the netsim.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    nf = src.size
    if nf == 0:
        return np.zeros(0, dtype=F32)
    up = np.asarray(up_cap, dtype=F32)
    dn = np.asarray(down_cap, dtype=F32)
    nn = up.size
    nl, lnk, lcap = _link_channel(nf, link_of, link_cap)

    rate = np.zeros(nf, dtype=F32)
    frozen = np.zeros(nf, dtype=bool)
    up_a = np.zeros(nn, dtype=F32)
    dn_a = np.zeros(nn, dtype=F32)
    lk_a = np.zeros(nl + 1, dtype=F32)

    for _ in range(2 * nn + nl + 2):  # each round saturates >= 1 constraint
        active = ~frozen
        if not active.any():
            break
        n_up = np.bincount(src[active], minlength=nn).astype(F32)
        n_dn = np.bincount(dst[active], minlength=nn).astype(F32)
        n_lk = np.bincount(lnk[active], minlength=nl + 1).astype(F32)
        with np.errstate(divide="ignore", invalid="ignore"):
            du = np.where(n_up > 0, (up - up_a) / n_up, F32_INF)
            dd = np.where(n_dn > 0, (dn - dn_a) / n_dn, F32_INF)
            dl = np.where(n_lk > 0, (lcap - lk_a) / n_lk, F32_INF)
        delta = min(du.min(), dd.min(), dl.min())
        if not np.isfinite(delta):
            break
        delta = max(delta, F32(0.0))
        rate[active] += delta
        up_a += n_up * delta
        dn_a += n_dn * delta
        lk_a += n_lk * delta
        tol = F32(delta + F32(1e-6))
        sat_u = (du <= tol) & (n_up > 0)
        sat_d = (dd <= tol) & (n_dn > 0)
        sat_l = (dl <= tol) & (n_lk > 0)
        newly = active & (sat_u[src] | sat_d[dst] | sat_l[lnk])
        if not newly.any():
            break
        frozen |= newly
    return rate


@functools.lru_cache(maxsize=None)
def _jnp_fill(n_iter: int):
    return jax.jit(functools.partial(waterfill_xla, n_iter=n_iter))


def waterfill_jnp_ref(
    src: np.ndarray,
    dst: np.ndarray,
    up_cap: np.ndarray,
    down_cap: np.ndarray,
    link_of: Optional[np.ndarray] = None,
    link_cap: Optional[np.ndarray] = None,
    *,
    with_rounds: bool = False,
):
    """Pure-jnp water-filling oracle: :func:`~.kernel.waterfill_xla` (the
    scatter fixed point) on the unpadded, untiled table.

    Every device path must match this *bit for bit* — the diff is
    precisely the machinery under test (the contraction's two-level
    incidences; the paths' padding and dummy slots). ``with_rounds`` returns ``(rates,
    rounds)``, the fixed point's rounds beside the rates.
    """
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    nf = src.size
    if nf == 0:
        rates, rounds = np.zeros(0, dtype=F32), 0
        return (rates, rounds) if with_rounds else rates
    nn = np.asarray(up_cap).size
    nl, lnk, lcap = _link_channel(nf, link_of, link_cap)
    rates, rounds = _jnp_fill(2 * nn + nl + 2)(
        jnp.asarray(src, dtype=jnp.int32),
        jnp.asarray(dst, dtype=jnp.int32),
        jnp.asarray(lnk, dtype=jnp.int32),
        jnp.asarray(np.asarray(up_cap, dtype=F32)),
        jnp.asarray(np.asarray(down_cap, dtype=F32)),
        jnp.asarray(lcap),
    )
    rates = np.asarray(rates)
    return (rates, int(np.asarray(rounds)[0])) if with_rounds else rates
