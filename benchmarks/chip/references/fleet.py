"""Plain reference of the fleet tick's layers, written from their stated
semantics and importing nothing of the program.

- Piece selection: rarest-first over a peer's candidate pieces; ties in
  replica count go to the smallest per-(peer, piece) jitter, ties in
  jitter to the lowest piece index. The jitter is one float32 draw per
  (peer, piece) from ``numpy.random.default_rng(seed)``, drawn as the
  scenario's first use of its generator.
- Candidates per stream: a peer's HTTP stream takes HTTP-routed pieces it
  lacks, plus (with ``http_fallback``) swarm-routed pieces nobody holds
  (every piece it lacks under ``http_first``); its swarm stream takes
  swarm-routed pieces it lacks that somebody holds; neither takes the
  piece the other stream is fetching.
- A tick (``replay_tick``): selection, the flow table's rules, progress,
  completions and the byte ledgers, as its docstring sets out.
- Rates: max-min fair progressive filling. All unfrozen flows grow alike
  until a node's uplink or downlink (or a shared link) is full; the flows
  through a full constraint freeze; repeat.
"""

from __future__ import annotations

import numpy as np


def jitter(seed: int, n: int, pieces: int) -> np.ndarray:
    return np.random.default_rng(seed).random((n, pieces), dtype=np.float32)


def swarm_routed(pieces: int, fraction: float) -> np.ndarray:
    """Which pieces travel by the swarm. Only the two ends of the range are
    defined without the pieces' hashes, and only they are used here."""
    if fraction >= 1.0:
        return np.ones(pieces, dtype=bool)
    if fraction <= 0.0:
        return np.zeros(pieces, dtype=bool)
    raise ValueError("the reference covers swarm_fraction 0 or 1 only")


def stream_pieces(avail, swarm, stream, mode, fallback):
    """The pieces one stream may take at all, whoever asks."""
    if stream == "http":
        if mode == "http_first":
            return np.ones(swarm.size, dtype=bool)
        ok = ~swarm
        if fallback:
            ok = ok | (swarm & (avail == 0))
        return ok
    return swarm & (avail > 0)


def select(have, rows, other, avail, swarm, jit, stream, mode, fallback):
    """Picks of one selection call: for each of ``rows``, the rarest piece
    (``rarest_picks``) among those its stream may take (``stream_pieces``)
    that it lacks and its other stream is not fetching; -1 for none."""
    cols = np.flatnonzero(stream_pieces(avail, swarm, stream, mode,
                                        fallback))
    if cols.size == 0 or rows.size == 0:
        return np.full(rows.size, -1, dtype=np.int64)
    cand = ~have[np.ix_(rows, cols)]
    where = np.searchsorted(cols, other)
    busy = np.flatnonzero((other >= 0) & (where < cols.size))
    busy = busy[cols[where[busy]] == other[busy]]
    cand[busy, where[busy]] = False
    got = rarest_picks(cand, avail[cols], jit[np.ix_(rows, cols)])
    return np.where(got >= 0, cols[np.clip(got, 0, None)], -1)


def rarest_picks(cand, avail, jit, rows_per_block: int = 1024):
    """Lexicographic argmin of (replicas, jitter, index) per row of the
    candidate mask; -1 where a row has no candidate. The jitter draws are
    whole multiples of 2**-24, so (replicas, jitter) is one exact integer
    key: replicas * 2**24 + jitter * 2**24."""
    base = np.asarray(avail, dtype=np.int64)[None, :] << 24
    big = np.iinfo(np.int64).max
    picks = np.full(cand.shape[0], -1, dtype=np.int64)
    for lo in range(0, cand.shape[0], rows_per_block):
        c = cand[lo:lo + rows_per_block]
        j = np.asarray(jit[lo:lo + rows_per_block], dtype=np.float64) * 2**24
        if not np.array_equal(j, np.floor(j)):
            raise ValueError("jitter is not a multiple of 2**-24")
        key = np.where(c, base + j.astype(np.int64), big)
        p = key.argmin(axis=1)  # the first least key: the lowest index
        picks[lo:lo + rows_per_block] = np.where(c.any(axis=1), p, -1)
    return picks


def waterfill(src, dst, up_cap, down_cap, link_of=None, link_cap=None,
              dtype=np.float64) -> np.ndarray:
    """Max-min fair rates of flows ``src -> dst`` (node indices into the
    capacity vectors), computed in ``dtype``. ``link_of`` puts a flow on at
    most one shared link (-1 for none)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    nf = src.size
    rate = np.zeros(nf, dtype=dtype)
    if nf == 0:
        return rate
    channels = [(src, np.asarray(up_cap, dtype=np.float64)),
                (dst, np.asarray(down_cap, dtype=np.float64))]
    if link_of is not None and link_cap is not None:
        link_of = np.asarray(link_of, dtype=np.int64)
        if (link_of >= 0).any():
            channels.append((link_of, np.asarray(link_cap, dtype=np.float64)))
    info = _finfo(dtype)
    caps = [np.minimum(c, float(info.max)).astype(dtype) for _, c in channels]
    alloc = [np.zeros(c.size, dtype=dtype) for c in caps]
    rtol = 1e-9 if info.bits >= 64 else float(info.eps)
    frozen = np.zeros(nf, dtype=bool)
    n_nodes = caps[0].size
    for _ in range(2 * n_nodes + len(caps) + 2):
        active = ~frozen
        if not active.any():
            break
        counts, room = [], []
        for (idx, _), c, a in zip(channels, caps, alloc):
            sel = active & (idx >= 0)
            n = np.bincount(idx[sel], minlength=c.size).astype(dtype)
            counts.append(n)
            with np.errstate(divide="ignore", invalid="ignore"):
                room.append(np.where(n > 0, (c - a) / n,
                                     np.asarray(np.inf, dtype=dtype)))
        delta = min(float(r.min()) for r in room)
        if not np.isfinite(delta):
            break
        delta = max(delta, 0.0)
        d = np.asarray(delta, dtype=dtype)
        rate[active] += d
        alloc = [a + n * d for a, n in zip(alloc, counts)]
        newly = np.zeros(nf, dtype=bool)
        for (idx, _), n, r in zip(channels, counts, room):
            full = (n > 0) & (r.astype(np.float64) <= delta * (1 + rtol))
            newly |= active & (idx >= 0) & full[np.clip(idx, 0, None)]
        if not newly.any():
            break
        frozen |= newly
    return rate


def _finfo(dtype):
    try:
        return np.finfo(dtype)
    except ValueError:  # bfloat16 and kin
        import ml_dtypes

        return ml_dtypes.finfo(dtype)


def rate_gap(rates, ref) -> float:
    """Widest gap of a program's rates from the reference's, as a share of
    the reference rate (of 1 B/s where that is smaller)."""
    rates = np.asarray(rates, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if rates.shape != ref.shape:
        return float("inf")
    if ref.size == 0:
        return 0.0
    return float(np.max(np.abs(rates - ref) / np.maximum(np.abs(ref), 1.0)))


def replay_tick(before, flows, t, dt, *, swarm, jitter, sizes, arrive, mode,
                fallback, mirror_caps, pair_budget, ppr) -> dict:
    """One tick of the engine from the state ``before`` it (host arrays by
    name), with the tick's flow table and rates ``flows`` as given:

    1. Peers that have arrived and not departed are present; present
       peers without a completion stamp are leechers. Replica counts are
       the have matrix's column sums over peers that have not departed.
    2. Under ``swarm_first``, an HTTP pick with no progress yet that is
       swarm-routed and now held by somebody is given up.
    3. Leechers with an idle HTTP stream pick (``select``); then, where any piece has a replica, leechers
       with an idle swarm stream do. A stream left without a piece holds
       no progress.
    4. The flow table must keep its rules: swarm flows first, each from a
       present peer other than the leecher that holds the leecher's swarm
       piece; each (source, leecher) pair carried by a whole multiple of
       ``ppr`` flows; at most ``pair_budget * ppr`` flows from one
       uploader; then HTTP flows from the origin to the first
       ``mirror_caps[0]`` leechers with an HTTP pick, by index. Each flow
       that breaks a rule is a violation.
    5. Each stream's progress grows by its flows' rates times ``dt``.
    6. Until none is left: every stream (HTTP first) whose progress has
       reached its piece's size completes it (the piece is held, the
       replica count and the peer's count rise, the size moves from
       progress to the peer's downloaded bytes, and to the origin's
       served bytes for HTTP) and picks its next piece.
    7. A stream without a piece holds no progress; a leecher that holds
       every piece is stamped complete at ``t + dt``.
    """
    if len(mirror_caps) != 1:
        raise ValueError("the reference covers one origin")
    s = {key: np.array(value, copy=True) for key, value in before.items()}
    have = s["have"]
    n, P = have.shape
    src = np.asarray(flows["src"], dtype=np.int64)
    dst = np.asarray(flows["dst"], dtype=np.int64)
    rates = np.asarray(flows["rates"], dtype=np.float64)
    present = (arrive <= t + 1e-9) & ~s["departed"]
    leech = present & ~np.isfinite(s["completed_at"])
    avail = have[~s["departed"]].sum(axis=0).astype(np.int64)
    picks = 0

    def pick(rows, stream):
        nonlocal picks
        cur, other = ((s["cur_http"], s["cur_swarm"]) if stream == "http"
                      else (s["cur_swarm"], s["cur_http"]))
        got = select(have, rows, other[rows], avail, swarm, jitter, stream,
                     mode, fallback)
        cur[rows] = got
        s["prog_" + stream][rows[got < 0]] = 0.0
        picks += rows.size

    if mode == "swarm_first":
        rows = np.flatnonzero(leech & (s["cur_http"] >= 0)
                              & (s["prog_http"] <= 0.0))
        p = s["cur_http"][rows]
        s["cur_http"][rows[swarm[p] & (avail[p] > 0)]] = -1
    pick(np.flatnonzero(leech & (s["cur_http"] < 0)), "http")
    if avail.max() > 0:
        pick(np.flatnonzero(leech & (s["cur_swarm"] < 0)), "swarm")

    is_sw = src < n
    nsw = int(is_sw.sum())
    bad = int((~is_sw[:nsw]).sum())
    ss, sd = src[:nsw], dst[:nsw]
    piece = s["cur_swarm"][sd]
    ok = (leech[sd] & (piece >= 0) & present[np.clip(ss, 0, n - 1)]
          & (ss >= 0) & (ss != sd) & have[np.clip(ss, 0, n - 1),
                                          np.clip(piece, 0, P - 1)])
    bad += int((~ok).sum())
    if nsw:
        per_up = np.bincount(np.clip(ss, 0, n - 1), minlength=n)
        bad += int(np.maximum(per_up - pair_budget * ppr, 0).sum())
        _, per_pair = np.unique(ss * n + sd, return_counts=True)
        bad += int((per_pair % ppr != 0).sum())
    http_rows = np.flatnonzero(leech & (s["cur_http"] >= 0))
    admitted = http_rows[:int(mirror_caps[0])]
    hs, hd = src[nsw:], dst[nsw:]
    same = min(hd.size, admitted.size)
    bad += int((hd[:same] != admitted[:same]).sum()) + int((hs != n).sum())
    bad += abs(hd.size - admitted.size)
    mirror_of = np.full(n, -1, dtype=np.int64)
    mirror_of[admitted] = 0

    s["prog_swarm"] += np.bincount(sd, weights=rates[:nsw], minlength=n) * dt
    s["prog_http"] += np.bincount(hd, weights=rates[nsw:], minlength=n) * dt
    for _ in range(P + 1):
        did = False
        for stream in ("http", "swarm"):
            cur, prog = s["cur_" + stream], s["prog_" + stream]
            rows = np.flatnonzero(
                (cur >= 0) & (prog >= sizes[np.clip(cur, 0, None)] - 1e-6))
            if rows.size == 0:
                continue
            did = True
            got = cur[rows]
            size = sizes[got]
            have[rows, got] = True
            s["nhave"][rows] += 1
            np.add.at(avail, got, 1)
            prog[rows] -= size
            s["downloaded"][rows] += size
            if stream == "http":
                np.add.at(s["mirror_uploaded"], mirror_of[rows], size)
            cur[rows] = -1
            pick(rows, stream)
        if not did:
            break
    s["prog_http"][s["cur_http"] < 0] = 0.0
    s["prog_swarm"][s["cur_swarm"] < 0] = 0.0
    s["completed_at"][leech & (s["nhave"] >= P)] = t + dt
    s["flow_violations"] = bad
    s["picks"] = picks
    return s
